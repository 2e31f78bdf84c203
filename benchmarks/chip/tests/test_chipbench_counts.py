"""The configurations' counts and references at tiny sizes, on the CPU:
the CSB operation and byte count against a hand count, the served SR1
weights against their dense twin, and the plain mamba2 reference
against the program's own prefill logits where the configuration states
the block the program runs, and apart from them where it states
mamba_ssm's."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

SR1 = harness.load_module(HERE / "configs" / "sr1.py")
MAMBA2 = harness.load_module(HERE / "configs" / "mamba2-370m.py")


def tiny_sr1() -> dict:
    cfg = json.loads((HERE / "configs" / "sr1.json").read_text())
    return dict(cfg, block=[8, 8], layers=[
        {"cell": "lstmp", "n_input": 12, "n_hidden": 32, "proj": 16},
        {"cell": "lstmp", "n_input": 16, "n_hidden": 32, "proj": 16}])


def tiny_mamba2(**block) -> dict:
    """A tiny float32 copy of mamba2-370m with the block the program runs
    (``MAMBA2.PROGRAM_BLOCK``) unless ``block`` says otherwise."""
    cfg = json.loads((HERE / "configs" / "mamba2-370m.json").read_text())
    return dict(cfg, d_model=64, n_layer=2, vocab_size=250, d_state=16,
                headdim=16, chunk_size=16, dtype="float32",
                ssm_state_dtype="float32", **dict(MAMBA2.PROGRAM_BLOCK,
                                                  **block))


def test_csb_count_against_a_hand_count():
    """8x8 matrix in 4x4 blocks; survivors chosen by hand per block."""
    from repro.core import padded_csb_from_dense

    rm = np.zeros((2, 2, 4), bool)
    cm = np.zeros((2, 2, 4), bool)
    rm[0, 0, [0, 2]] = True        # block (0, 0): 2 x 2
    cm[0, 0, [1, 3]] = True
    rm[0, 1, [1]] = True           # block (0, 1): 1 x 3
    cm[0, 1, [0, 1, 2]] = True
    rm[1, 1, :] = True             # block (1, 1): 4 x 1; (1, 0) empty
    cm[1, 1, [2]] = True
    w = np.arange(1, 65, dtype=np.float32).reshape(8, 8)
    p = padded_csb_from_dense(w, 4, 4, row_mask=rm, col_mask=cm)
    assert p.pm == 8 and p.pn == 8          # padded well past the survivors
    ops, nbytes = SR1.matrix_work(p.m, p.n, p.shape, streams=5)
    nnz = 2 * 2 + 1 * 3 + 0 + 4 * 1          # 11 survivors
    idx = (2 + 1 + 0 + 4) + (2 + 3 + 0 + 1)  # row and column indices
    assert ops == 2 * nnz * 5 == 110
    assert nbytes == 4 * (nnz + idx + 5 * (8 + 8)) == 416
    assert ops < 5 * p.padded_flops_per_mvm()


def test_sr1_served_weights_match_their_dense_twin():
    from repro.kernels.ref import csb_mvm_ref

    cfg = tiny_sr1()
    key = harness.seed_key(2**31 + 99)
    prog = SR1.program_params(cfg, key)
    dense = SR1.dense_params(cfg, key)
    st = SR1.structure(cfg)
    for layer, ref, s in zip(prog, dense, st):
        for name, p in layer.items():
            if name.startswith("b_"):
                np.testing.assert_array_equal(p, ref[name])
                continue
            np.testing.assert_array_equal(np.asarray(p.m), s[name]["m"])
            x = jax.random.normal(jax.random.PRNGKey(0), (8, p.shape[1]))
            np.testing.assert_allclose(
                csb_mvm_ref(p, x),
                jnp.dot(x, ref[name].T, precision="highest"), atol=1e-5)
            # every survivor is a distinct row and column of its block
            for b in range(p.vals.shape[0]):
                m, n = int(p.m[b]), int(p.n[b])
                assert len(set(np.asarray(p.row_idx[b, :m]))) == m
                assert len(set(np.asarray(p.col_idx[b, :n]))) == n
    ops = sum(f for f, _ in SR1.csb_work(cfg, 1))
    assert ops == 2 * SR1.survivors(cfg)
    nnz = sum(int((np.asarray(ref[k]) != 0).sum())
              for ref in dense for k in ref if not k.startswith("b_"))
    assert nnz == SR1.survivors(cfg)


def test_sr1_same_work_on_every_seed():
    cfg = tiny_sr1()
    a = SR1.program_params(cfg, harness.seed_key(1))
    b = SR1.program_params(cfg, harness.seed_key(2))
    for la, lb in zip(a, b):
        for name in la:
            if not name.startswith("b_"):
                assert la[name].vals.shape == lb[name].vals.shape
                np.testing.assert_array_equal(la[name].m, lb[name].m)
                assert not np.array_equal(la[name].vals, lb[name].vals)


def _prefill_logits(cfg, key, toks):
    """The program's last-position prefill logits, at ``highest``."""
    from repro.models import lm as LM

    mcfg = MAMBA2.program_config(cfg)
    params = MAMBA2.program_params(cfg, key)
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(lambda p, t: LM.prefill(p, {"tokens": t},
                                                 cfg=mcfg))(params, toks)
    return got[:, :cfg["vocab_size"]]


@pytest.mark.parametrize("length", [5, 37])
def test_mamba2_reference_matches_program_prefill(length):
    """Where the configuration states the block the program runs, the
    reference and the program agree to float32 rounding."""
    cfg = tiny_mamba2()
    key = harness.seed_key(7)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, length), 0,
                              cfg["vocab_size"])
    got = _prefill_logits(cfg, key, toks)
    want = MAMBA2.reference_logits(
        cfg, MAMBA2.reference_params(cfg, key), toks, length - 1)
    np.testing.assert_allclose(got, want[:, 0], atol=2e-4, rtol=1e-4)
    # the control departs from the reference
    ctl = MAMBA2.reference_logits(
        cfg, MAMBA2.reference_params(cfg, key), toks, length - 1,
        mode="fp8")
    assert float(jnp.abs(ctl - want).max()) > 1e-3


@pytest.mark.parametrize("setting", [{"conv_bias": True},
                                     {"norm_before_gate": False}])
def test_mamba2_program_departs_from_published_block(setting):
    """mamba_ssm's Mamba2 block (conv bias, norm after the gate) is not
    what the program runs: with the published setting the reference
    parts from the program's prefill far beyond rounding."""
    key = harness.seed_key(7)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 37), 0, 250)
    got = _prefill_logits(tiny_mamba2(), key, toks)
    cfg = tiny_mamba2(**setting)
    want = MAMBA2.reference_logits(
        cfg, MAMBA2.reference_params(cfg, key), toks, 36)
    assert float(jnp.abs(got - want[:, 0]).max()) > 1e-2


def test_mamba2_published_config_is_refused_by_the_program():
    cfg = json.loads((HERE / "configs" / "mamba2-370m.json").read_text())
    assert cfg["reduced"] == []
    found = MAMBA2.departures(cfg)
    assert len(found) == 4, found
    for k in ("conv_bias", "norm_before_gate", "norm_epsilon",
              "residual_in_fp32"):
        assert any(d.startswith(k) for d in found), k
    with pytest.raises(ValueError, match="cannot run"):
        MAMBA2.program_config(cfg)


def test_mamba2_flops():
    cfg = json.loads((HERE / "configs" / "mamba2-370m.json").read_text())
    per_token = MAMBA2.flops(cfg, 1, 0)
    # about twice the 316M weights of the blocks' products
    assert 6.4e8 < per_token < 7.0e8
    assert MAMBA2.flops(cfg, 0, 1) == 2 * 1024 * 50277


def test_sr1_survivors_at_published_widths():
    cfg = json.loads((HERE / "configs" / "sr1.json").read_text())
    dense = sum(int(np.prod(s)) for layer in cfg["layers"]
                for s in SR1.layer_shapes(layer).values() if len(s) == 2)
    assert dense == 7_966_720
    # every matrix keeps at most 1/13 of its real weights, and little less
    for st in SR1.structure(cfg):
        for s in st.values():
            rows, cols = s["shape"]
            nnz = int((s["m"].astype(np.int64) * s["n"]).sum())
            assert rows * cols / 13.5 < nnz <= rows * cols / 13
    assert SR1.survivors(cfg) == 610_084
