"""Smoke run of the system's main paths on a TPU, through the normal
entry points, at published widths with random weights from a seed.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: the sharded paths

One chip, three phases in this one process:

1. ``kernel``: every CSB-pruned SR1 weight through the compiled CSB
   kernel, against ``kernels.ref.csb_mvm_ref`` and against the dense
   float32 product of the pruned matrix.
2. ``frames``: SR1 (paper Table 1: LSTMP 153->1024 with projection 512,
   then LSTMP 512->1024 with projection 512), every 2-D weight
   CSB-pruned at 13x with 128x128 blocks, served layer by layer through
   ``serve.rnn_serve_frames`` and compared with a plain dense float32
   LSTMP at highest precision.
3. ``lm``: mamba2-370m (48 layers, d_model 1024, vocab 50280) through
   ``serve_continuous(paged=True)``: every request finishes with its
   ``max_new_tokens`` and prefill logits are finite. Greedy tokens are
   compared with ``generate`` on the same prompts twice. In the
   published bf16 the two compiled programs round differently, so a
   request may part from ``generate`` only at a near-tie: where the
   float32 logits of the two tokens lie closer than bf16's own logit
   error on that prefix (``near_tie``). ``lm-f32`` then runs the same
   widths in float32 at ``Precision.HIGHEST``, where every token must
   be equal.

``--four-chips`` runs only the sharded paths and what they are compared
with: SR1 frames on a 1x4 ("data", "model") mesh against one device,
and mamba2-370m ``serve_continuous`` on the same mesh against the
unsharded run, token for token (bf16 with the near-tie rule, float32
exactly).

Without a TPU the script exits non-zero before any work. Any failed
check or exception exits non-zero. On success the last line of stdout
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.cells import make_cell  # noqa: E402
from repro.cells.dataflow import init_params  # noqa: E402
from repro.configs import PAPER_MODELS, get_config  # noqa: E402
from repro.core import (  # noqa: E402
    CSBSpec, PaddedCSB, csb_masks, csb_project, padded_csb_from_dense,
)
from repro.kernels.csb_mvm import default_interpret  # noqa: E402
from repro.kernels.ops import csb_matvec  # noqa: E402
from repro.kernels.ref import csb_mvm_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import lm as LM  # noqa: E402
from repro.serve import (  # noqa: E402
    EngineConfig, Request, generate, rnn_serve_frames, serve_continuous,
)

SEED = 0
BLOCK = 128                 # CSB block (bm = bn)
SR1_RATE = 13.0             # SR1's compression, benchmarks/bench_latency.py
STREAMS, FRAMES = 8, 100    # 8 streams x 1 s of 10 ms frames
KERNEL_TOL = 1e-4           # max |kernel - reference|, fp32 at HIGHEST
FRAME_TOL = 1e-4            # max |served frames - dense LSTMP reference|
SHARD_TOL = 1e-5            # max |sharded frames - one-device frames|
LM_ARCH = "mamba2-370m"
# (prompt length, max_new_tokens, arrival step): three lengths, more
# requests than slots, two arriving mid-decode
LM_TRAFFIC = ((16, 12, 0), (40, 20, 0), (72, 12, 0), (16, 20, 0),
              (40, 12, 3), (72, 20, 6))
LM_SLOTS = 4


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def tpu_or_exit():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    return dev


# ---------------------------------------------------------------------------
# SR1: CSB-pruned weights and a plain dense reference
# ---------------------------------------------------------------------------

def sr1_layers(key):
    """[(graph, dense pruned params, CSB params)] for SR1's layers."""
    spec = CSBSpec(bm=BLOCK, bn=BLOCK, prune_rate=1.0 - 1.0 / SR1_RATE)
    layers = []
    for cfg in PAPER_MODELS["SR1"].layers:
        graph = make_cell(cfg.cell, cfg.n_input, cfg.n_hidden,
                          proj_dim=cfg.proj)
        key, k_w, k_b = jax.random.split(key, 3)
        dense = init_params(graph, k_w)
        csb = {}
        for name, w in sorted(dense.items()):
            if w.ndim == 1:
                k_b, sub = jax.random.split(k_b)
                dense[name] = csb[name] = 0.1 * jax.random.normal(
                    sub, w.shape)
                continue
            rm, cm = csb_masks(w, spec)
            dense[name] = csb_project(w, spec)
            csb[name] = padded_csb_from_dense(
                np.asarray(dense[name]), BLOCK, BLOCK,
                row_mask=np.asarray(rm), col_mask=np.asarray(cm))
        layers.append((graph, dense, csb))
    return layers


@jax.jit
def lstmp_reference(p, xs):
    """Dense float32 LSTMP over (T, B, in) at highest precision, written
    out by hand — independent of the dataflow executor and the kernel."""
    def mv(w, v):
        return jnp.dot(v, w.T, precision=jax.lax.Precision.HIGHEST)

    def step(carry, x):
        h, c = carry

        def gate(k):
            return mv(p[f"W_{k}"], x) + mv(p[f"U_{k}"], h) + p[f"b_{k}"]

        i, f, o = (jax.nn.sigmoid(gate(k)) for k in "ifo")
        c = f * c + i * jnp.tanh(gate("g"))
        h = mv(p["W_proj"], o * jnp.tanh(c))
        return (h, c), h

    b = xs.shape[1]
    init = (jnp.zeros((b, p["W_proj"].shape[0])),
            jnp.zeros((b, p["W_i"].shape[0])))
    return jax.lax.scan(step, init, xs)[1]


def kernel_phase(layers, key) -> None:
    if default_interpret():
        fail("the CSB kernel would run in interpret mode")
    err_ref = err_dense = 0.0
    n = 0
    for _, dense, csb in layers:
        for name, p in sorted(csb.items()):
            if not isinstance(p, PaddedCSB):
                continue
            key, sub = jax.random.split(key)
            x = jax.random.normal(sub, (STREAMS, p.shape[1]))
            y = csb_matvec(p, x)
            want = jnp.dot(x, dense[name].T,
                           precision=jax.lax.Precision.HIGHEST)
            err_ref = max(err_ref,
                          float(jnp.abs(y - csb_mvm_ref(p, x)).max()))
            err_dense = max(err_dense, float(jnp.abs(y - want).max()))
            n += 1
    say("kernel", matrices=n, block=BLOCK, max_err_vs_ref=err_ref,
        max_err_vs_dense=err_dense, tol=KERNEL_TOL)
    if not max(err_ref, err_dense) <= KERNEL_TOL:
        fail(f"CSB kernel error {max(err_ref, err_dense)} > {KERNEL_TOL}")


def frames_phase(layers, key) -> None:
    xs = jax.random.normal(key, (FRAMES, STREAMS, layers[0][0].input_dim))
    got = want = xs
    for li, (graph, dense, csb) in enumerate(layers):
        t0 = time.perf_counter()
        got, _, us, frame_us = rnn_serve_frames(
            graph, csb, got, config=EngineConfig(collect_frame_times=True))
        wall = time.perf_counter() - t0
        want = lstmp_reference(dense, want)
        err = float(jnp.abs(got - want).max())
        say("frames", layer=li + 1, shape=tuple(got.shape),
            compile_warmup_s=round(
                wall - (FRAMES * us + frame_us.sum()) / 1e6, 3),
            us_per_frame=round(us, 2),
            p50_frame_us=round(float(np.percentile(frame_us, 50)), 2),
            p99_frame_us=round(float(np.percentile(frame_us, 99)), 2),
            max_err=err, tol=FRAME_TOL)
        if not bool(jnp.isfinite(got).all()) or not err <= FRAME_TOL:
            fail(f"SR1 layer {li + 1} frames off the dense reference "
                 f"by {err} (tol {FRAME_TOL})")


# ---------------------------------------------------------------------------
# LM serving
# ---------------------------------------------------------------------------

def lm_requests(cfg, seed: int):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, tokens=rng.integers(0, cfg.vocab, size=plen,
                                               dtype=np.int32),
                    max_new_tokens=new, arrival=arr)
            for i, (plen, new, arr) in enumerate(LM_TRAFFIC)]


def lm_serve(params, cfg, requests, phase: str, mesh=None):
    res = serve_continuous(
        params, cfg, requests,
        EngineConfig(paged=True, n_slots=LM_SLOTS), mesh=mesh)
    st = res.stats
    say(phase, arch=cfg.name, dtype=cfg.dtype, requests=len(requests),
        generated_tokens=st["generated_tokens"], sharded=st["sharded"],
        compile_time_s=st["compile_time_s"],
        steady_tokens_per_sec=st["steady_tokens_per_sec"],
        wall_s=round(res.wall_s, 3))
    for r in requests:
        got = res.tokens.get(r.rid, [])
        if len(got) != r.max_new_tokens:
            fail(f"request {r.rid} finished with {len(got)} of "
                 f"{r.max_new_tokens} tokens")
    return res.tokens


def first_divergence(a, b) -> int:
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                min(len(a), len(b)))


def f32_model(cfg, params):
    """The same model in float32 (weights, activations, SSM state)."""
    cfg32 = dataclasses.replace(cfg, dtype="float32",
                                ssd_state_dtype="float32")
    return cfg32, jax.tree.map(lambda a: a.astype(jnp.float32), params)


@functools.lru_cache(maxsize=None)
def prefill_fn(cfg):
    return jax.jit(functools.partial(LM.prefill, cfg=cfg))


def last_logits(params, cfg, tokens) -> np.ndarray:
    logits, _ = prefill_fn(cfg)(params, {"tokens": jnp.asarray(tokens)[None]})
    return np.asarray(logits[0, :cfg.vocab], np.float64)


def near_tie(params, cfg, tokens, a: int, b: int) -> tuple[float, float]:
    """(gap, err) after ``tokens``: ``gap`` = |logit[a] - logit[b]| of the
    float32 model at HIGHEST, ``err`` = max |logit - float32 logit| of
    ``cfg``'s own dtype. ``gap <= err``: this dtype cannot tell a from b."""
    cfg32, params32 = f32_model(cfg, params)
    lg = last_logits(params, cfg, tokens)
    with jax.default_matmul_precision("highest"):
        lg32 = last_logits(params32, cfg32, tokens)
    return float(abs(lg32[a] - lg32[b])), float(np.abs(lg - lg32).max())


def compare_tokens(phase, requests, got, want, params, cfg,
                   exact: bool) -> None:
    """Greedy tokens ``got`` against ``want``, request by request. With
    ``exact`` any difference fails. Without it (bf16), a request may
    diverge only at a near-tie (:func:`near_tie`) — after which the two
    runs continue from different tokens and are not compared further."""
    equal, ties = 0, []
    for r in requests:
        g, w = got[r.rid], want[r.rid]
        if g == w:
            equal += 1
            continue
        i = first_divergence(g, w)
        if exact:
            fail(f"{phase}: request {r.rid} differs from token {i}: "
                 f"{g[i:i + 4]} vs {w[i:i + 4]}")
        prefix = np.concatenate([r.tokens, np.asarray(w[:i], np.int32)])
        gap, err = near_tie(params, cfg, prefix, g[i], w[i])
        say(phase, rid=r.rid, diverges_at=i, tokens=(g[i], w[i]),
            f32_logit_gap=gap, dtype_logit_err=err)
        if not gap <= err:
            fail(f"{phase}: request {r.rid} diverges at token {i} where "
                 f"float32 separates the two tokens by {gap} > {err}")
        ties.append(r.rid)
    say(phase, tokens_equal=f"{equal}/{len(requests)}",
        near_tie_divergences=len(ties), exact=exact)


def generate_tokens(params, cfg, requests) -> dict[int, list[int]]:
    """Greedy ``generate`` per request; checks its prefill logits."""
    cache_len = max(r.prompt_len + r.max_new_tokens for r in requests)
    out = {}
    for r in requests:
        if not np.isfinite(last_logits(params, cfg, r.tokens)).all():
            fail(f"request {r.rid}: non-finite prefill logits")
        ref = generate(params, cfg, jnp.asarray(r.tokens)[None],
                       EngineConfig(max_new_tokens=r.max_new_tokens,
                                    cache_len=cache_len))
        out[r.rid] = np.asarray(ref)[0, r.prompt_len:].tolist()
    return out


def lm_phase(key) -> None:
    """The published bf16 model, then the same widths in float32 at
    HIGHEST, where serve and generate must agree token for token."""
    cfg = get_config(LM_ARCH)
    params = LM.init_params(key, cfg)
    requests = lm_requests(cfg, SEED)
    served = lm_serve(params, cfg, requests, "lm")
    compare_tokens("lm", requests, served,
                   generate_tokens(params, cfg, requests), params, cfg,
                   exact=False)
    cfg32, params32 = f32_model(cfg, params)
    with jax.default_matmul_precision("highest"):
        served = lm_serve(params32, cfg32, requests, "lm-f32")
        compare_tokens("lm-f32", requests, served,
                       generate_tokens(params32, cfg32, requests),
                       params32, cfg32, exact=True)


# ---------------------------------------------------------------------------
# four chips: the sharded paths against one device
# ---------------------------------------------------------------------------

def four_chip_phase(key) -> None:
    devices = jax.devices()
    if len(devices) < 4:
        fail(f"--four-chips needs 4 devices, JAX found {len(devices)}")
    mesh = Mesh(np.asarray(devices[:4]).reshape(1, 4), ("data", "model"))

    k_sr1, k_x, k_lm = jax.random.split(key, 3)
    layers = sr1_layers(k_sr1)
    one = four = jax.random.normal(
        k_x, (FRAMES, STREAMS, layers[0][0].input_dim))
    for li, (graph, _, csb) in enumerate(layers):
        one, _, us1 = rnn_serve_frames(graph, csb, one)
        four, _, us4 = rnn_serve_frames(graph, csb, four, mesh=mesh)
        diff = float(jnp.abs(one - four).max())
        say("frames-4chip", layer=li + 1, mesh="1x4",
            us_per_frame_1chip=round(us1, 2),
            us_per_frame_4chip=round(us4, 2), max_diff=diff,
            identical=bool((one == four).all()), tol=SHARD_TOL)
        if not diff <= SHARD_TOL:
            fail(f"sharded SR1 layer {li + 1} differs from one device "
                 f"by {diff} (tol {SHARD_TOL})")

    cfg = get_config(LM_ARCH)
    params = LM.init_params(k_lm, cfg)
    requests = lm_requests(cfg, SEED)
    ref = lm_serve(params, cfg, requests, "lm-1chip")
    got = lm_serve(params, cfg, requests, "lm-4chip", mesh=mesh)
    compare_tokens("lm-4chip", requests, got, ref, params, cfg, exact=False)
    cfg32, params32 = f32_model(cfg, params)
    with jax.default_matmul_precision("highest"):
        ref = lm_serve(params32, cfg32, requests, "lm-f32-1chip")
        got = lm_serve(params32, cfg32, requests, "lm-f32-4chip", mesh=mesh)
        compare_tokens("lm-f32-4chip", requests, got, ref, params32, cfg32,
                       exact=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths, on 4 devices")
    args = ap.parse_args()

    dev = tpu_or_exit()
    say("setup", compile_cache=enable_compile_cache(),
        device_kind=dev.device_kind, devices=len(jax.devices()))
    key = jax.random.PRNGKey(SEED)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(key)
    else:
        k_sr1, k_kernel, k_frames, k_lm = jax.random.split(key, 4)
        layers = sr1_layers(k_sr1)
        kernel_phase(layers, k_kernel)
        frames_phase(layers, k_frames)
        lm_phase(k_lm)
    say("done", total_s=round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
