"""End-to-end serving driver (the paper's workload shape: inference).

Seven parts:
1. Continuous batching: mixed-length prompts arriving over time flow
   through a fixed set of decode slots — finished requests are evicted
   and the next queued prompt prefilled into the freed slot mid-decode.
   With >= 8 host devices (CI sets
   XLA_FLAGS=--xla_force_host_platform_device_count=8) the whole loop
   runs sharded on a 2x4 ("data", "model") mesh: params placed by
   param_specs/csb_shard_specs, cache + token batch data-parallel via
   cache_specs/batch_specs. The run goes through the PAGED cache
   (paged=True): fixed-size token pages from a shared pool, pow2
   prompt-bucketed prefill — and its tokens are asserted identical to
   the contiguous engine's.
2. The paging win: the same token budget is handed to both engines as
   a hard cap. The contiguous engine can only carve it into 2
   worst-case-length slots and queues the rest; the paged pool
   reserves per-request pages and runs more of the mixed-length trace
   concurrently — asserted, not just printed.
3. Prefix sharing: every request opens with the same system prompt, so
   with prefix_cache=True the first admission registers the prompt
   pages in the radix trie and every later admission maps them
   directly — zero prefill compute for the shared span, copy-on-write
   at the divergence page. Hit count, prefill-token reduction and
   token-for-token parity with the non-shared engine are asserted.
3b. Speculative decoding: the part-1 trace re-runs with
   speculative=True — a CSB-pruned copy of the target (the paper's own
   compression scheme as the draft model) proposes spec_k tokens per
   round and the target verifies them in one multi-position paged
   decode step. Greedy trace, so token-for-token parity with part 1 is
   asserted; acceptance counters are printed.
4. Fixed-batch LM serving: prefill a batch of prompts and greedily
   decode through the jitted single-token step.
5. Faster-than-realtime RNN frame serving: an LSTM with CSB-compressed
   weights processes a stream of frames — on the mesh the CSB block
   grid is cycle-balanced over the "model" axis and executed by the
   shard_map kernel; reports us/frame against the paper's 500 us
   realtime bar (CPU-interpret numbers are illustrative — the bar is
   meaningful on real hardware).
6. Request-lifecycle tracing: the part-1 trace re-runs with
   ``repro.obs`` enabled, exports a Chrome-trace JSON
   (``serve_trace.json``, loadable in https://ui.perfetto.dev — CI
   uploads it as an artifact) and prints the latency breakdown
   (per-span percentiles + the queue-wait -> prefill -> TTFT -> decode
   request table). See docs/observability.md.

Run:  PYTHONPATH=src python examples/serve_batched.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.cells import init_params as cell_init, make_cell
from repro.core import CSBSpec, csb_masks, csb_project, padded_csb_from_dense
from repro.dist import Rules, use_rules
from repro.models import ModelConfig, init_params
from repro.serve import (
    EngineConfig, Request, generate, rnn_serve_frames, serve_continuous,
    shard_cell_params,
)

mesh = None
if len(jax.devices()) >= 8:
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    print(f"mesh: {dict(mesh.shape)} over {mesh.size} devices")
else:
    print("single device (set XLA_FLAGS=--xla_force_host_platform_"
          "device_count=8 for the sharded path)")

cfg = ModelConfig(name="serve-demo", mixer="attn", ffn="swiglu",
                  n_layers=4, d_model=128, n_heads=4, n_kv=2, head_dim=32,
                  d_ff=256, vocab=512, dtype="float32", remat=False)
params = init_params(jax.random.PRNGKey(0), cfg)

# -- 1. continuous batching: mixed lengths, arriving over time -------------
rng = np.random.default_rng(7)
requests = [
    Request(rid=i, tokens=rng.integers(0, cfg.vocab,
                                       size=int(rng.integers(6, 20))),
            max_new_tokens=int(rng.integers(8, 17)), arrival=(i // 3) * 6)
    for i in range(9)
]
print(f"\n{len(requests)} requests, prompt lens "
      f"{[r.prompt_len for r in requests]}, arrivals "
      f"{[r.arrival for r in requests]}, 4 slots, PAGED cache")
paged_cfg = EngineConfig(n_slots=4, paged=True, page_size=8)
res = serve_continuous(params, cfg, requests, paged_cfg, mesh=mesh)
st = res.stats
pg = st["paging"]
print(f"paged serve: {st['requests']} requests, "
      f"{st['generated_tokens']} tokens in {res.wall_s:.2f}s "
      f"({st['tokens_per_sec']:.1f} tok/s, occupancy "
      f"{st['occupancy']:.0%}, {st['prefills']} prefills over "
      f"{st['decode_steps']} decode steps, sharded={st['sharded']}, "
      f"bucketed_prefill={st['bucketed_prefill']})")
print(f"  pages: peak {pg['peak_pages']}/{pg['n_pages']} x "
      f"{pg['page_size']} tokens, internal fragmentation "
      f"{pg['internal_fragmentation']:.1%}")
res_contig = serve_continuous(params, cfg, requests,
                              EngineConfig(n_slots=4), mesh=mesh)
assert res.tokens == res_contig.tokens, \
    "paged and contiguous engines must emit identical tokens"
print("  paged tokens == contiguous tokens: verified")

# -- 2. the paging win: same token budget, more concurrency ----------------
long_req = Request(rid=100, tokens=rng.integers(0, cfg.vocab, size=16),
                   max_new_tokens=32)                       # total 48
shorts = [Request(rid=101 + i,
                  tokens=rng.integers(0, cfg.vocab, size=8),
                  max_new_tokens=8) for i in range(4)]      # total 16
cache_len = 48
budget = 2 * cache_len                                      # 96 tokens
paged = serve_continuous(
    params, cfg, [long_req] + shorts,
    EngineConfig(n_slots=4, paged=True, page_size=8, cache_len=cache_len,
                 pool_pages=budget // 8), mesh=mesh)
contig = serve_continuous(
    params, cfg, [long_req] + shorts,
    EngineConfig(n_slots=budget // cache_len, cache_len=cache_len),
    mesh=mesh)
assert paged.tokens == contig.tokens
assert paged.stats["peak_active"] > contig.stats["peak_active"]
print(f"\nsame {budget}-token budget: contiguous fits "
      f"{contig.stats['peak_active']} concurrent requests "
      f"({contig.stats['decode_steps']} decode steps), paged fits "
      f"{paged.stats['peak_active']} ({paged.stats['decode_steps']} "
      f"steps) — identical outputs")

# -- 3. prefix sharing: a common system prompt across every request --------
sys_prompt = rng.integers(0, cfg.vocab, size=21)    # 2 whole pages + 5
shared_reqs = [
    Request(rid=200 + i,
            tokens=np.concatenate(
                [sys_prompt,
                 rng.integers(0, cfg.vocab, size=int(rng.integers(2, 7)))]),
            max_new_tokens=int(rng.integers(6, 13)), arrival=(i // 3) * 4)
    for i in range(9)
]
base = serve_continuous(params, cfg, shared_reqs, paged_cfg, mesh=mesh)
shared = serve_continuous(
    params, cfg, shared_reqs,
    paged_cfg.replace(prefix_cache=True), mesh=mesh)
assert shared.tokens == base.tokens, \
    "prefix sharing must not change a single output token"
# every request past the first matches the system prompt in the trie
assert shared.stats["prefix_hits"] == len(shared_reqs) - 1, shared.stats
assert shared.stats["prefill_tokens"] < base.stats["prefill_tokens"]
saved = base.stats["prefill_tokens"] - shared.stats["prefill_tokens"]
print(f"\nshared {len(sys_prompt)}-token system prompt x "
      f"{len(shared_reqs)} requests, prefix_cache=True: "
      f"{shared.stats['prefix_hits']} trie hits, "
      f"{shared.stats['shared_pages']} pages mapped shared, "
      f"{shared.stats['paging']['cow_copies']} CoW copies")
print(f"  prefill compute: {base.stats['prefill_tokens']} tokens without "
      f"sharing -> {shared.stats['prefill_tokens']} with "
      f"({saved} saved) — identical outputs")

# -- 3b. speculative decoding: CSB-pruned self-draft + k-token verify ------
spec_cfg = paged_cfg.replace(speculative=True, spec_k=4,
                             draft_prune_rate=0.5)
spec = serve_continuous(params, cfg, requests, spec_cfg, mesh=mesh)
assert spec.tokens == res.tokens, \
    "speculative decoding must not change a single output token at T=0"
sp = spec.stats["speculative"]
print(f"\nspeculative decode (k={sp['spec_k']}, draft = target CSB-pruned "
      f"at {sp['draft_prune_rate']:.0%}): {sp['rounds']} verify rounds, "
      f"{sp['proposed']} drafted, {sp['accepted']} accepted "
      f"(acceptance {sp['acceptance_rate']:.0%}), "
      f"{spec.stats['generated_tokens'] / max(sp['rounds'], 1):.2f} "
      f"tokens per target step — identical outputs")

# -- 4. fixed-batch LM serving ---------------------------------------------
prompts = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
t0 = time.perf_counter()
out = generate(params, cfg, prompts, EngineConfig(max_new_tokens=16),
               mesh=mesh)
jax.block_until_ready(out)
dt = time.perf_counter() - t0
new_tokens = 8 * 16
print(f"\nbatched generate: {out.shape[0]} seqs x {out.shape[1]} tokens "
      f"({new_tokens} new) in {dt:.2f}s "
      f"-> {dt / new_tokens * 1e3:.1f} ms/token (CPU)")

# -- 5. CSB-RNN frame serving ----------------------------------------------
cell = make_cell("lstm", 64, 128)
wparams = cell_init(cell, jax.random.PRNGKey(2))
spec = CSBSpec(bm=16, bn=16, prune_rate=0.9)     # 10x compression
csb_params = {}
for k, w in wparams.items():
    if w.ndim == 2:
        z = csb_project(w, spec)
        rm, cm = csb_masks(w, spec)
        csb_params[k] = padded_csb_from_dense(
            np.asarray(z), 16, 16, row_mask=np.asarray(rm),
            col_mask=np.asarray(cm))
    else:
        csb_params[k] = w

frames = jax.random.normal(jax.random.PRNGKey(3), (32, 4, 64))
if mesh is None:
    outs, _, us = rnn_serve_frames(cell, csb_params, frames)
else:
    # shard once: weights over "model", the stream batch over "data"
    sharded = shard_cell_params(csb_params, mesh)
    frames = jax.device_put(frames, NamedSharding(mesh, P(None, "data")))
    with use_rules(Rules({}, mesh=mesh)):
        outs, _, us = rnn_serve_frames(cell, sharded, frames)
where = "sharded mesh" if mesh is not None else "single device"
print(f"\nCSB-RNN frames ({where}): {frames.shape[0]} frames x batch "
      f"{frames.shape[1]} -> {us:.1f} us/frame "
      f"(interpret mode; realtime bar: 500 us)")

# -- 6. request-lifecycle tracing ------------------------------------------
from repro import obs
from repro.obs import trace as obs_trace
from repro.obs.summary import report

obs.enable_all()
traced = serve_continuous(params, cfg, requests, paged_cfg, mesh=mesh)
assert traced.tokens == res.tokens          # tracing changes nothing
trace_path = obs_trace.export_chrome("serve_trace.json")
obs.disable_all()
st = traced.stats
print(f"\ntraced re-run: compile {st['compile_time_s']:.2f}s (warm), "
      f"steady {st['steady_tokens_per_sec']:.1f} tok/s "
      f"(blended {st['tokens_per_sec']:.1f})")
print(report(trace_path))
print(f"\nopen {trace_path} in https://ui.perfetto.dev to see the "
      f"engine + per-request tracks")
print("done")
