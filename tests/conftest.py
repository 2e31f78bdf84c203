import os

import numpy as np
import pytest

# NOTE: deliberately no XLA_FLAGS here — tests must see the real (single)
# device; only launch/dryrun.py forces 512 host devices. CI covers the
# sharding paths by exporting XLA_FLAGS=--xla_force_host_platform_
# device_count=8 itself; tests needing multiple devices skip without it.


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(1234)


@pytest.fixture(scope="session", autouse=True)
def _tpu_interpret_golden():
    """CI golden lane: REPRO_FORCE_TPU_INTERPRET=1 runs every Pallas
    call through pltpu.force_tpu_interpret_mode, so the compiled-path
    branch of kernels.csb_mvm.default_interpret (interpret=False, the
    TPU route) is exercised on CPU runners."""
    if os.environ.get("REPRO_FORCE_TPU_INTERPRET", "0") in ("", "0"):
        yield
        return
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        yield
