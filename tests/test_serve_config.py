"""EngineConfig: validation and the post-shim strict signature.

The unified config is the API surface every serve entry point consumes,
so this file holds the contract: every cross-field rule fails at
construction, loose kwargs raise TypeError from the real signature (the
one-release DeprecationWarning shim and the ServeConfig subclass are
gone), and ``resolve_config`` rejects anything that is not an
EngineConfig.
"""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.models import ModelConfig
from repro.models import init_params as lm_init
from repro.serve import EngineConfig, Request, generate, serve_continuous
from repro.serve.config import resolve_config

CFG = ModelConfig(name="tiny-cfg", mixer="attn", ffn="swiglu", n_layers=2,
                  d_model=32, n_heads=2, n_kv=2, head_dim=16, d_ff=64,
                  vocab=50, dtype="float32", logit_chunk=16, remat=False)


@pytest.fixture(scope="module")
def params():
    return lm_init(jax.random.PRNGKey(0), CFG)


def _requests(n=4, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    tokens=rng.integers(0, 50, size=int(
                        rng.integers(4, 10))),
                    max_new_tokens=int(rng.integers(3, 7)))
            for i in range(n)]


# ---------------------------------------------------------------------------
# construction-time validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,match", [
    (dict(max_new_tokens=0), "max_new_tokens"),
    (dict(temperature=-0.5), "temperature"),
    (dict(cache_len=0), "cache_len"),
    (dict(n_slots=0), "n_slots"),
    (dict(page_size=0), "page_size"),
    (dict(use_kernel=True), "use_kernel=True requires paged=True"),
    (dict(prefix_cache=True), "prefix_cache=True requires paged=True"),
    (dict(pool_pages=8), "pool_pages requires paged=True"),
    (dict(paged=True, pool_pages=0), "pool_pages"),
    (dict(spec_k=0), "spec_k"),
    (dict(draft_prune_rate=1.0), "draft_prune_rate"),
    (dict(draft_prune_rate=-0.1), "draft_prune_rate"),
])
def test_invalid_configs_raise(kw, match):
    with pytest.raises(ValueError, match=match):
        EngineConfig(**kw)


def test_valid_paged_combination():
    c = EngineConfig(paged=True, page_size=8, pool_pages=4,
                     prefix_cache=True, use_kernel=True)
    assert c.paged and c.prefix_cache and c.use_kernel


def test_valid_speculative_combination():
    c = EngineConfig(paged=True, speculative=True, spec_k=2,
                     draft_prune_rate=0.0)
    assert c.speculative and c.spec_k == 2 and c.draft_prune_rate == 0.0


def test_replace_revalidates_and_returns_base():
    c = EngineConfig(n_slots=2)
    c2 = c.replace(paged=True, page_size=8)
    assert type(c2) is EngineConfig and c2.paged and c2.n_slots == 2
    assert not c.paged                       # frozen original untouched
    with pytest.raises(ValueError, match="prefix_cache"):
        c.replace(prefix_cache=True)         # still not paged


def test_config_is_frozen_and_hashable():
    c = EngineConfig()
    with pytest.raises(dataclasses.FrozenInstanceError):
        c.n_slots = 8
    assert hash(c) == hash(EngineConfig())


# ---------------------------------------------------------------------------
# the shim is gone: loose kwargs are real TypeErrors now
# ---------------------------------------------------------------------------

def test_loose_kwargs_raise_typeerror(params):
    with pytest.raises(TypeError, match="n_slots"):
        serve_continuous(params, CFG, _requests(2), n_slots=2)
    with pytest.raises(TypeError, match="paged"):
        serve_continuous(params, CFG, _requests(2),
                         EngineConfig(n_slots=2), paged=True)


def test_serveconfig_is_gone():
    with pytest.raises(ImportError):
        from repro.serve import ServeConfig  # noqa: F401
    with pytest.raises(ImportError):
        from repro.serve.config import ServeConfig  # noqa: F401


def test_resolve_rejects_non_config():
    with pytest.raises(TypeError, match="generate\\(\\) expects"):
        resolve_config({"n_slots": 2}, caller="generate")


def test_resolve_passthrough_no_warning():
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert resolve_config(None, caller="t") == EngineConfig()
        c = EngineConfig(n_slots=2)
        assert resolve_config(c, caller="t") is c


def test_new_style_emits_no_deprecation(params):
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        serve_continuous(params, CFG, _requests(2),
                         EngineConfig(n_slots=2))


def test_generate_accepts_config(params):
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 50)
    out = generate(params, CFG, prompt, EngineConfig(max_new_tokens=4))
    assert np.asarray(out).shape == (2, 10)
