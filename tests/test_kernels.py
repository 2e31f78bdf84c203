"""Pallas CSB-MVM kernel vs the pure-jnp oracle — shape/dtype sweeps in
interpret mode (per-kernel allclose deliverable)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CSBSpec, csb_masks, csb_project, padded_csb_from_dense
from repro.kernels import ops
from repro.kernels.ops import csb_matvec, csb_tiling, csb_vmem_bytes
from repro.kernels.ref import csb_mvm_ref, densify
from repro.obs import metrics as obs_metrics


def make_padded(rng, shape, bm, bn, rate, pad_to=8, dtype=jnp.float32):
    w = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    spec = CSBSpec(bm=bm, bn=bn, prune_rate=rate)
    z = csb_project(w, spec)
    rm, cm = csb_masks(w, spec)
    return padded_csb_from_dense(
        np.asarray(z), bm, bn, pad_to=pad_to, dtype=dtype,
        row_mask=np.asarray(rm), col_mask=np.asarray(cm)), np.asarray(z)


@pytest.mark.parametrize("shape,bm,bn", [
    ((32, 32), 16, 16),
    ((64, 48), 16, 16),
    ((48, 64), 16, 32),
    ((128, 96), 32, 32),
    ((40, 24), 8, 8),      # non-divisible -> padded grid
])
@pytest.mark.parametrize("rate", [0.3, 0.75])
def test_kernel_matches_ref_shapes(rng, shape, bm, bn, rate):
    p, z = make_padded(rng, shape, bm, bn, rate)
    x = jnp.asarray(rng.normal(size=(5, shape[1])).astype(np.float32))
    y_ref = csb_mvm_ref(p, x)
    y_ker = csb_matvec(p, x)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    # and both match the dense masked matmul
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(x) @ z.T,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(rng, dtype):
    p, z = make_padded(rng, (64, 64), 16, 16, 0.5, dtype=dtype)
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32)).astype(dtype)
    y_ref = csb_mvm_ref(p, x)
    y_ker = csb_matvec(p, x)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(y_ker, np.float32), np.asarray(y_ref, np.float32),
        rtol=tol, atol=tol)


def test_kernel_batch_shapes(rng):
    p, _ = make_padded(rng, (48, 32), 16, 16, 0.5)
    for batch_shape in [(), (1,), (3,), (2, 5)]:
        x = jnp.asarray(
            rng.normal(size=(*batch_shape, 32)).astype(np.float32))
        y = csb_matvec(p, x)
        assert y.shape == (*batch_shape, 48)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(csb_mvm_ref(p, x)), rtol=1e-5,
            atol=1e-5)


@pytest.mark.parametrize("group", [2, 4, None])
def test_kernel_group_fusion(rng, group):
    """group > 1 fuses several blocks per grid step — same results;
    None is the default tiling (all four block-columns in one step)."""
    p, _ = make_padded(rng, (64, 64), 16, 16, 0.5)
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    y1 = csb_matvec(p, x, batch_tile=8, group=1)
    yg = csb_matvec(p, x, group=group)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(yg), rtol=1e-5)


@pytest.mark.parametrize("bt", [8, 16, None])
def test_kernel_batch_tiles(rng, bt):
    p, _ = make_padded(rng, (32, 32), 16, 16, 0.5)
    x = jnp.asarray(rng.normal(size=(13, 32)).astype(np.float32))
    y = csb_matvec(p, x, batch_tile=bt)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(csb_mvm_ref(p, x)), rtol=1e-5,
        atol=1e-5)


# (out, in) of products the benchmark serves: SR1 (LSTMP 153 -> 1024,
# projection 512) and He et al. 2019's RNN-T (2048 cells, projection
# 640, 1,280 inputs above the time reduction), 128x128 blocks at 13x
SERVING_SHAPES = [(1024, 153), (1024, 512), (512, 1024),
                  (2048, 320), (2048, 1280), (640, 2048)]


@functools.lru_cache(maxsize=None)
def _serving_csb(shape):
    return make_padded(np.random.default_rng(sum(shape)), shape, 128, 128,
                       1 - 1 / 13)[0]


@pytest.mark.parametrize("b", [8, 64, 256])
@pytest.mark.parametrize("shape", SERVING_SHAPES)
def test_kernel_default_tiling_at_serving_shapes(rng, shape, b):
    """One grid step per block-row over the whole batch: equal to the
    oracle, and to the 8-row, one-block tiling up to rounding. Inputs
    are scaled by 1/sqrt(fan-in), so outputs are of unit size, as a
    layer's pre-activations are: the absolute tolerance then measures
    rounding, not the size of N(0, 1) sums over 2,048 inputs."""
    p = _serving_csb(shape)
    assert csb_tiling(b, p.grid, p.block, p.pm, p.pn) == (b, p.grid[1])
    x = jnp.asarray((rng.normal(size=(b, shape[1])) / np.sqrt(shape[1]))
                    .astype(np.float32))
    y = np.asarray(csb_matvec(p, x))
    np.testing.assert_allclose(y, np.asarray(csb_mvm_ref(p, x)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        y, np.asarray(csb_matvec(p, x, batch_tile=8, group=1)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b", [1, 8, 64, 256])
def test_csb_tiling_whole_batch_in_one_tile(b):
    tb, group = csb_tiling(b, (16, 10), (128, 128), 56, 112)
    assert tb == max(-(-b // 8) * 8, 8) and group == 10


@pytest.mark.parametrize("bc", [2, 4, 5, 8, 10, 16])
@pytest.mark.parametrize("b", [64, 256])
def test_csb_tiling_whole_block_row(b, bc):
    """SR1's (Bc 2, 4, 8) and the RNN-T's (5, 10, 16) widths."""
    assert csb_tiling(b, (16, bc), (128, 128), 56, 112) == (b, bc)


def test_csb_tiling_holds_the_vmem_budget():
    """A 4,096-row batch of a 4,096-wide matrix cannot stage whole: the
    tile or the group shrinks until the step fits."""
    tb, group = csb_tiling(4096, (16, 32), (128, 128), 56, 112)
    assert 32 % group == 0
    assert tb < 4096 or group < 32
    assert csb_vmem_bytes(tb, group, (128, 128), 56, 112) <= ops._VMEM_BUDGET
    assert (tb, group) == (2048, 2)


@pytest.mark.parametrize("batch_tile,group,steps", [
    (None, None, 4),          # one tile of 16 rows, one step a block-row
    (8, 1, 2 * 4 * 4),        # given: 2 tiles x 4 block-rows x 4 blocks
    (8, None, 2 * 4),         # given tile, the whole block-row per step
    (None, 2, 4 * 2),         # given group, the whole batch per step
])
def test_explicit_tiling_wins_and_grid_steps_recorded(rng, batch_tile,
                                                      group, steps):
    p, _ = make_padded(rng, (64, 64), 16, 16, 0.5)
    x = jnp.asarray(rng.normal(size=(13, 64)).astype(np.float32))
    jax.clear_caches()        # the count is taken when _run traces
    obs_metrics.enable()
    try:
        y = csb_matvec(p, x, batch_tile=batch_tile, group=group)
        hist = obs_metrics.get().histogram("kernel/csb/grid_steps")
        assert hist.count == 1 and hist.percentile(50) == steps
    finally:
        obs_metrics.disable()
    np.testing.assert_allclose(np.asarray(y), np.asarray(csb_mvm_ref(p, x)),
                               rtol=1e-5, atol=1e-5)


def test_empty_blocks(rng):
    """Blocks fully pruned away (m=0 or n=0) must contribute zero."""
    z = np.zeros((32, 32), np.float32)
    z[:16, :16] = rng.normal(size=(16, 16))  # only one block alive
    p = padded_csb_from_dense(z, 16, 16)
    x = jnp.asarray(rng.normal(size=(3, 32)).astype(np.float32))
    y = csb_matvec(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) @ z.T,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(densify(p)), z, atol=0)


@pytest.mark.parametrize("weight", ["dense", "padded", "sharded_no_mesh"])
def test_matvec_picks_the_program_by_weight(rng, weight):
    """``ops.matvec`` runs a dense weight as an einsum, a ``PaddedCSB``
    through the kernel, and refuses a ``ShardedCSB`` outside a model
    mesh."""
    from repro.dist.csb_partition import partition_padded
    p, z = make_padded(rng, (48, 32), 16, 16, 0.5)
    x = jnp.asarray(rng.normal(size=(3, 32)).astype(np.float32))
    if weight == "sharded_no_mesh":
        with pytest.raises(ValueError, match="model"):
            ops.matvec(partition_padded(p, 2)[1], x)
        return
    w = jnp.asarray(z) if weight == "dense" else p
    with jax.default_matmul_precision("highest"):
        y = ops.matvec(w, x)
    assert y.dtype == x.dtype and y.shape == (3, 48)
    want = jnp.dot(x, jnp.asarray(z).T, precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
