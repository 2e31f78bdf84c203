"""Sharded batched + continuous serving.

``generate`` — prefill a batch of prompts, then greedy/temperature decode
with the jitted single-token step (the decode_32k / long_500k workload).

``serve_continuous`` — the production shape: a fixed batch of decode
*slots* fed by :class:`repro.serve.scheduler.SlotScheduler`. Requests
with mixed prompt lengths arrive over time; a finished request's slot is
evicted and the next queued prompt prefilled into it mid-decode, so the
jitted step (compiled once) keeps every slot busy. ``paged=True`` backs
the slots with the ``serve.paging`` block pool (admission by free
pages, page-table decode, pow2 prompt-bucketed prefill) instead of
contiguous worst-case-length slot caches.

``rnn_serve_frames`` — the paper's own serving shape: frame-by-frame RNN
inference (one MVM-bound cell step per frame) with CSB-compressed
weights; returns per-frame outputs and the wall-clock per frame so the
faster-than-realtime criterion (<500 us/frame for speech) can be checked
on real hardware.

All three run under the ``dist`` sharding rules: pass ``mesh=`` (or call
inside a ``use_rules`` scope whose Rules carry a mesh) and parameters
are placed via ``param_specs``/``csb_shard_specs`` on the "model" axis
(CSB weights route through ``csb_matvec_sharded``), while the decode
cache and token batch shard over the "data" axes via
``cache_specs``/``batch_specs`` — the data axes act as a replica set
for continuous batching, each replica carrying its share of the slots.
Without a mesh everything degrades to the single-device paths the CPU
tests use.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.cells import CellGraph, cell_apply, init_state
from repro.dist import (
    Rules, ShardingPolicy, activation_rules, batch_specs, cache_specs,
    csb_shard_specs, current_rules, fit_spec, use_rules,
)
from repro.models import ModelConfig
from repro.models import lm as LM
from repro.obs import metrics as obs_metrics, trace as obs_trace

from .config import EngineConfig, resolve_config
from .paging import PagePool, pages_for
from .scheduler import (
    _TIME_KEYS, Request, SlotScheduler, cache_len_of, copy_page_cache,
    evict_slot, evict_slot_state, fit_cache_len, grow_cache,
    insert_paged_cache, insert_paged_span, insert_slot_cache,
)

PyTree = Any


def bucket_len(n: int, floor: int = 8) -> int:
    """Smallest power of two >= max(n, floor): the prefill-shape bucket.

    Padding prompts up to pow2 buckets bounds the number of compiled
    prefill executables at O(log max_len) for arbitrary length traces
    (the floor merges the tiny lengths into one bucket)."""
    return 1 << max(max(n, floor) - 1, 0).bit_length()


def _resolve_mesh(mesh):
    """Explicit mesh arg, else the active Rules' mesh; trivial -> None."""
    if mesh is None:
        mesh = getattr(current_rules(), "mesh", None)
    if mesh is None or math.prod(dict(mesh.shape).values()) <= 1:
        return None
    return mesh


def _dp_spec(mesh, shape: tuple[int, ...], batch_axis: int = 0) -> P:
    """Spec sharding ``batch_axis`` over the non-model (data) axes,
    divisibility-guarded; every other dim replicated."""
    from repro.dist.rules import _dp_entry
    entries: list[Any] = [None] * len(shape)
    entries[batch_axis] = _dp_entry(mesh)
    fitted = fit_spec(P(*entries), shape, mesh)
    return fitted if fitted is not None else P(*([None] * len(shape)))


@functools.lru_cache(maxsize=64)
def _jitted(cfg: ModelConfig, rules_key):
    """Jitted prefill + decode-step wrappers, cached per (cfg, rules)
    so repeated generate/serve_continuous calls (benchmarks, request
    waves) reuse compiled executables instead of retracing. The traced
    program depends on the active Rules (sharding constraints), hence
    ``rules_key`` — (mesh, policy) for derived rules, the caller's
    Rules instance (identity-hashed) for ambient ones, None for the
    inert single-device path; params are call arguments, so fresh
    weights hit the same cache."""
    return {
        "prefill": jax.jit(partial(LM.prefill, cfg=cfg)),
        # prefix-cache hits prefill only the unmatched suffix against the
        # gathered shared pages; variants bounded by (pow2 suffix bucket)
        # x (pow2 context page count)
        "prefill_partial": jax.jit(partial(LM.prefill_partial, cfg=cfg)),
        # one jitted step per pos rank: scalar (fixed batch) / (B,) slots
        "steps": {},
    }


class _Runner:
    """One (params, cfg, mesh, policy) serving context: places the
    parameter tree once, owns the jitted prefill/decode callables, and
    re-installs its Rules around every traced call so model-side
    ``shard()`` tags resolve.

    Rules precedence: an explicit ``mesh=`` derives the canonical
    ``activation_rules`` for it; with no mesh argument, a caller's
    ambient ``use_rules`` scope is honored verbatim — both its mesh and
    its table (a caller that hand-built cache layouts keeps them)."""

    def __init__(self, params, cfg: ModelConfig, mesh=None, policy=None):
        self.cfg = cfg
        # cold-call tracking: ``last_cold`` is True when the preceding
        # prefill/step call compiled (or at least first-traced) its
        # executable — the engine charges that call's wall time to
        # ``compile_time_s`` instead of the steady-state throughput
        self.last_cold = False
        self._seen_keys: set = set()
        ambient = current_rules()
        self.mesh = _resolve_mesh(mesh)
        self.policy = policy or ShardingPolicy()
        if self.mesh is not None:
            if mesh is None and ambient is not None:
                self.rules = ambient
                rules_key: Any = ambient
            else:
                self.rules = activation_rules(cfg, self.mesh, self.policy)
                rules_key = (self.mesh, self.policy)
            specs = csb_shard_specs(params, self.mesh, policy=self.policy)
            self.params = jax.tree.map(
                lambda leaf, sp: jax.device_put(
                    leaf, NamedSharding(self.mesh, sp)), params, specs)
        else:
            # meshless rules are inert for shard(): one shared trace
            self.rules = ambient or Rules({})
            self.params = params
            rules_key = None
        jt = _jitted(cfg, rules_key)
        self._prefill = jt["prefill"]
        self._prefill_partial = jt["prefill_partial"]
        self._steps = jt["steps"]
        # per-shape NamedSharding cache: spec derivation is loop-
        # invariant, and place_tokens/place_pos sit on the per-token
        # path the serve benchmark gates
        self._shardings: dict = {}

    def _batch_sharding(self, key: str, shape) -> NamedSharding | None:
        ck = (key, shape)
        if ck not in self._shardings:
            spec = batch_specs(self.cfg, "decode", self.mesh)[key]
            fitted = fit_spec(spec, shape, self.mesh)
            self._shardings[ck] = (None if fitted is None
                                   else NamedSharding(self.mesh, fitted))
        return self._shardings[ck]

    def _call_cold(self, fn, key, call):
        """Run ``call()`` and set :attr:`last_cold`. jax's jit cache
        size is the exact signal (a growth means this call traced +
        compiled); fall back to first-sight-of-shape-key when the
        private ``_cache_size`` hook is unavailable."""
        sizer = getattr(fn, "_cache_size", None)
        before = None
        if sizer is not None:
            try:
                before = sizer()
            except Exception:
                before = None
        out = call()
        if before is not None:
            try:
                self.last_cold = sizer() > before
            except Exception:
                self.last_cold = key not in self._seen_keys
        else:
            self.last_cold = key not in self._seen_keys
        self._seen_keys.add(key)
        return out

    def prefill(self, tokens: jax.Array, last_pos=None):
        with use_rules(self.rules):
            if last_pos is None:
                return self._call_cold(
                    self._prefill, ("prefill", tokens.shape),
                    lambda: self._prefill(self.params, {"tokens": tokens}))
            return self._call_cold(
                self._prefill, ("prefill", tokens.shape, "lp"),
                lambda: self._prefill(
                    self.params, {"tokens": tokens},
                    last_pos=jnp.asarray(last_pos, jnp.int32)))

    def prefill_partial(self, tokens: jax.Array, ctx: PyTree, start,
                        last_pos):
        """Prefill a prompt suffix against gathered shared-prefix pages
        (``ctx`` rides replicated — same GSPMD workaround as
        :meth:`place_slot_cache`, and it is one request's worth)."""
        ctx = self.place_slot_cache(ctx)
        ctx_len = cache_len_of(ctx)
        with use_rules(self.rules):
            return self._call_cold(
                self._prefill_partial,
                ("prefill_partial", tokens.shape, ctx_len),
                lambda: self._prefill_partial(
                    self.params, {"tokens": tokens}, ctx,
                    start=jnp.asarray(start, jnp.int32),
                    last_pos=jnp.asarray(last_pos, jnp.int32)))

    def place_cache(self, cache: PyTree, paged: bool = False) -> PyTree:
        if self.mesh is None:
            return cache
        specs = cache_specs(self.cfg, cache, self.mesh, self.policy,
                            paged=paged)
        return jax.tree.map(
            lambda leaf, sp: jax.device_put(
                leaf, NamedSharding(self.mesh, sp)), cache, specs)

    def place_table(self, table: jax.Array) -> jax.Array:
        """Page table: replicated — every data replica indexes the whole
        pool (dist.rules cache_specs keeps pool pages data-parallel;
        the table must see all of them)."""
        if self.mesh is None:
            return table
        return jax.device_put(table, NamedSharding(
            self.mesh, P(*([None] * table.ndim))))

    def place_tokens(self, tokens: jax.Array) -> jax.Array:
        if self.mesh is None:
            return tokens
        sh = self._batch_sharding("tokens", tokens.shape)
        return tokens if sh is None else jax.device_put(tokens, sh)

    def place_pos(self, pos: jax.Array) -> jax.Array:
        if self.mesh is None or pos.ndim == 0:
            return pos
        sh = self._batch_sharding("pos", pos.shape)
        return pos if sh is None else jax.device_put(pos, sh)

    def place_slot_cache(self, req_cache: PyTree) -> PyTree:
        """Replicate a freshly prefilled single-request cache before it
        is written into the batch cache. Prefill tags its KV with the
        time-sharded ``kv_cache`` layout; letting GSPMD transition that
        straight into the batch cache's layout inside the jitted insert
        is the involuntary-full-rematerialization path (see
        ``dist.api.shard``) — an explicit host-side replication copy is
        tiny (one request) and keeps the insert a plain masked update."""
        if self.mesh is None:
            return req_cache
        return jax.tree.map(
            lambda leaf: jax.device_put(leaf, NamedSharding(
                self.mesh, P(*([None] * leaf.ndim)))), req_cache)

    def step(self, cache, tokens, pos):
        fn = self._steps.get(jnp.ndim(pos))
        if fn is None:
            fn = jax.jit(partial(LM.decode_step, cfg=self.cfg),
                         donate_argnums=(1,))
            self._steps[jnp.ndim(pos)] = fn
        with use_rules(self.rules):
            return self._call_cold(
                fn, ("step", jnp.ndim(pos)),
                lambda: fn(self.params, cache, tokens, pos))

    def step_paged(self, cache, tokens, pos, page_table,
                   use_kernel: bool = False):
        key = ("paged", jnp.ndim(pos), use_kernel)
        fn = self._steps.get(key)
        if fn is None:
            fn = jax.jit(partial(LM.decode_step_paged, cfg=self.cfg,
                                 use_kernel=use_kernel),
                         donate_argnums=(1,))
            self._steps[key] = fn
        with use_rules(self.rules):
            return self._call_cold(
                fn, key,
                lambda: fn(self.params, cache, tokens, pos, page_table))


def _sampler(cfg: ModelConfig, temperature: float):
    def sample(lg, key):
        if temperature <= 0.0:
            return jnp.argmax(lg, axis=-1)
        return jax.random.categorical(key, lg / temperature, axis=-1)

    return sample


# ---------------------------------------------------------------------------
# fixed-batch generate
# ---------------------------------------------------------------------------

def generate(params, cfg: ModelConfig, tokens,
             config: EngineConfig | None = None,
             rng: jax.Array | None = None, *, mesh=None, policy=None):
    """tokens: (B, S_prompt) (or (B, S, K) codebooks). Returns (B, S+new).

    ``config`` is the unified :class:`EngineConfig`. With a mesh
    (argument or active Rules), params/cache/batch run sharded; results
    match the single-device path token-for-token. With
    ``config.speculative`` a CSB-pruned self-draft proposes
    ``spec_k``-token runs the target verifies in one multi-position
    decode step (see serve.speculative); tokens are identical to the
    plain path at temperature 0.
    """
    scfg = resolve_config(config, caller="generate")
    if scfg.speculative:
        from .speculative import generate_speculative
        return generate_speculative(params, cfg, tokens, scfg, rng,
                                    mesh=mesh, policy=policy)
    b, s = tokens.shape[:2]
    total = scfg.cache_len or (s + scfg.max_new_tokens)
    runner = _Runner(params, cfg, mesh, policy)

    logits, cache = runner.prefill(jnp.asarray(tokens))
    # right-size the cache for the decode loop
    cache = grow_cache(cache, total - cache_len_of(cache))
    cache = runner.place_cache(cache)

    sample = _sampler(cfg, scfg.temperature)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    out = [jnp.asarray(tokens)]
    cur = sample(logits, rng)[:, None]
    if cfg.n_codebooks and cur.ndim == 2:
        cur = cur[:, None]
    for i in range(scfg.max_new_tokens):
        out.append(cur)
        rng, k = jax.random.split(rng)
        lg, cache = runner.step(cache, runner.place_tokens(cur),
                                jnp.asarray(s + i))
        cur = sample(lg[:, -1], k)[:, None]
        if cfg.n_codebooks and cur.ndim == 2:
            cur = cur[:, None]
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeResult:
    """Outcome of a continuous-batching run."""

    tokens: dict[int, list[int]]      # rid -> generated token ids
    stats: dict                       # scheduler stats + throughput
    wall_s: float

    @property
    def occupancy(self) -> float:
        return self.stats["occupancy"]

    @property
    def tokens_per_sec(self) -> float:
        return self.stats["tokens_per_sec"]


def _gather_ctx(cache: PyTree, pages) -> PyTree:
    """Pull the shared-prefix pages out of the live paged cache as a
    contiguous per-layer context for the partial prefill. ``pages`` is a
    host array of physical page ids (scratch-padded to a pow2 count, so
    compiled partial-prefill variants stay O(log max_pages)); each time
    leaf (L, N, P, ...) gathers to (L, 1, len(pages) * P, ...)."""
    idx = jnp.asarray(pages, jnp.int32)

    def one(path, leaf):
        keys = [getattr(k, "key", "") for k in path]
        assert keys and keys[-1] in _TIME_KEYS, \
            "prefix sharing needs an all-pool cache (attn/mla)"
        g = leaf[:, idx]
        return g.reshape((g.shape[0], 1, g.shape[1] * g.shape[2])
                         + g.shape[3:])

    return jax.tree_util.tree_map_with_path(one, cache)


def serve_continuous(params, cfg: ModelConfig, requests: list[Request],
                     config: EngineConfig | None = None, *,
                     mesh=None, policy=None,
                     rng: jax.Array | None = None) -> ServeResult:
    """Serve ``requests`` (mixed prompt lengths, arriving over time)
    through ``config.n_slots`` continuously-batched decode slots.

    All engine knobs ride on one :class:`EngineConfig` (serve + paging
    + kernel + prefix + speculative fields, cross-validated at
    construction); loose kwargs raise ``TypeError`` (the one-release
    migration shim is gone).

    The decode step compiles once for the (n_slots, cache_len) shapes
    and runs every step with per-slot positions; admission prefills each
    arrived prompt and writes its cache into the freed slot. Greedy
    decoding (``temperature=0``) matches ``generate`` token-for-token,
    sharded or not, paged or not.

    ``paged=True`` swaps the contiguous per-slot cache for a shared
    pool of ``pool_pages`` fixed-size token pages (``page_size`` each;
    default pool = full contiguous capacity). Slots map logical
    positions to physical pages through a dense page table
    (``serve.paging``); admission goes **by free pages, not free
    slots**, each request reserving only its own worst case — a
    mixed-length trace packs more concurrent requests into the same
    token budget than contiguous slots allow (pass a smaller
    ``pool_pages`` to cap the budget). Pages free mid-decode the moment
    a request finishes.

    ``use_kernel=True`` (paged only) routes decode attention through the
    Pallas paged-attention kernel — the page-table walk happens inside
    the kernel instead of a materialized ``(B, max_pages*P)`` gather;
    sampled tokens are unchanged.

    ``bucket_prompts`` (default: on when paged) right-pads each prompt
    to a pow2 **bucket** before prefill, so a trace of arbitrary
    lengths compiles O(log max_len) prefill executables instead of one
    per distinct length. Causal attention makes right padding invisible
    to real positions, so sampled tokens are unchanged; SSD/hybrid
    mixers scan pad tokens into their recurrent state, so bucketing
    auto-disables there.

    ``prefix_cache=True`` (paged only) retains prompt pages in a
    refcounted radix trie after their request finishes and shares them
    across requests: an admission whose prompt prefix matches pages
    already in the pool maps them instead of recomputing (prefill runs
    only from the divergence point — ``models.lm.prefill_partial``), and
    the first write into a partially-shared page goes through
    copy-on-write. Sampled tokens are identical to ``prefix_cache=False``
    (the partial prefill mirrors the full prefill bit-for-bit at serve
    scales); ``stats["prefix_hits"]``/``stats["shared_pages"]`` count the
    sharing and ``stats["prefill_tokens"]`` the prefill work actually
    done. Auto-disables for SSD/hybrid (their recurrent state has no
    per-position cache to share), like bucketing.

    Throughput accounting: ``stats["tokens_per_sec"]`` divides by the
    FULL wall clock — including the trace+compile of every first-called
    prefill bucket and decode-step variant — and is kept for
    compatibility. ``stats["compile_time_s"]`` isolates that first-call
    (compile-inclusive) time and ``stats["steady_tokens_per_sec"]`` is
    the decode throughput over warm steps only (0.0 when every step was
    cold), so a cold-cache run no longer under-reports the engine.

    With :mod:`repro.obs` enabled the run also emits per-request
    lifecycle spans (queue wait -> prefill -> TTFT -> decode), per-step
    spans and pool/occupancy gauge timelines — see
    docs/observability.md. Disabled (the default), the instrumentation
    is a few branch-on-None checks and never touches the gated
    per-token path.
    """
    if cfg.n_codebooks:
        raise NotImplementedError(
            "serve_continuous drives single-stream token ids; codebook "
            "models go through generate()")
    # invalid combinations (prefix_cache without paged, ...) raise
    # ValueError inside EngineConfig.__post_init__
    config = resolve_config(config, caller="serve_continuous")
    if config.speculative:
        from .speculative import serve_continuous_speculative
        return serve_continuous_speculative(params, cfg, requests, config,
                                            mesh=mesh, policy=policy,
                                            rng=rng)
    n_slots, temperature = config.n_slots, config.temperature
    cache_len, paged = config.cache_len, config.paged
    page_size, pool_pages = config.page_size, config.pool_pages
    use_kernel = config.use_kernel
    bucket = (config.bucket_prompts if config.bucket_prompts is not None
              else paged)
    prefix_cache = config.prefix_cache
    bucket = bucket and cfg.mixer in ("attn", "mla")
    prefix = prefix_cache and cfg.mixer in ("attn", "mla")
    if not requests:
        stats = SlotScheduler(n_slots).stats()
        stats.update(cache_len=0, tokens_per_sec=0.0, paged=paged,
                     bucketed_prefill=bucket, prefix_cache=prefix,
                     prefill_tokens=0, compile_time_s=0.0,
                     steady_tokens_per_sec=0.0,
                     sharded=_resolve_mesh(mesh) is not None)
        if paged:
            stats["paging"] = PagePool(
                page_size, 1 if pool_pages is None else pool_pages,
                n_slots, 1).summary()
            stats["page_stalls"] = 0
        return ServeResult({}, stats, 0.0)
    cache_len = cache_len or max(
        r.prompt_len + r.max_new_tokens for r in requests)
    short = [r for r in requests
             if r.prompt_len + r.max_new_tokens > cache_len]
    if short:
        raise ValueError(
            f"cache_len={cache_len} cannot hold request(s) "
            f"{[r.rid for r in short]}")

    runner = _Runner(params, cfg, mesh, policy)
    sample = _sampler(cfg, temperature)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    pool = None
    if paged:
        max_pages = pages_for(cache_len, page_size)
        # explicit pool_pages=0 must reject (PagePool raises), not
        # silently fall back to the full contiguous footprint
        n_pool = (n_slots * max_pages if pool_pages is None
                  else pool_pages)
        pool = PagePool(page_size, n_pool, n_slots, max_pages,
                        prefix_cache=prefix)
    sched = SlotScheduler(n_slots, pool=pool)
    for r in requests:
        sched.submit(r)

    if paged:
        cache = runner.place_cache(
            LM.init_paged_cache(cfg, pool.n_pages, page_size, n_slots,
                                jnp.dtype(cfg.dtype)), paged=True)
    else:
        cache = runner.place_cache(
            LM.init_cache(cfg, n_slots, cache_len, jnp.dtype(cfg.dtype)))
    cur = jnp.zeros((n_slots, 1), jnp.int32)
    # device-placed page table, refreshed only when the pool remaps a
    # page (device_table() returns a cached identical object when
    # clean, so identity is the dirty signal) — keeps the redundant
    # host->device put off the gated per-token path
    table_host = table_placed = None

    def _admissions():
        # Under the prefix cache, admit one request at a time: each
        # prompt registers right after its own prefill (below), so the
        # NEXT admission's trie match — even in the same step — can
        # already share it. Without the cache, one batched admit() call
        # keeps the original page_stall accounting.
        if not prefix:
            yield from sched.admit()
            return
        while True:
            batch = sched.admit(limit=1)
            if not batch:
                return
            yield batch[0]

    prefill_tokens = 0
    # observability handles, fetched once per run: ``tr``/``reg`` are
    # None when obs is off and every emit below branches on that —
    # the cold/steady split (compile_ns/steady_*) is ALWAYS accounted,
    # it only costs perf_counter_ns calls around already-blocking work
    tr = obs_trace.get()
    reg = obs_metrics.get()
    obs_on = tr is not None or reg is not None
    req_clock: dict[int, dict] = {}    # rid -> lifecycle timestamps (ns)
    compile_ns = 0
    steady_ns = 0
    steady_tokens = 0

    def _mark_eligible():
        # stamp the wall time each queued request first became
        # admissible (its arrival step reached) — queue wait and TTFT
        # are measured from here, not from engine start
        now_ns = time.perf_counter_ns()
        for rid in sched.arrived_pending():
            req_clock.setdefault(rid, {})["eligible"] = now_ns

    def _finish_req(rid: int, t_fin: int):
        rc = req_clock.get(rid, {})
        t_first = rc.get("first")
        if t_first is None:
            return
        n_dec = len(sched.results.get(rid, ())) - 1
        if tr is not None:
            tr.complete("serve/req/decode", t_first, t_fin - t_first,
                        track=f"req {rid}",
                        args={"rid": rid, "decode_tokens": n_dec})
            tr.instant("serve/req/finish", track=f"req {rid}",
                       args={"rid": rid})
        if reg is not None and n_dec > 0:
            reg.histogram("serve/req/decode_per_token_us").observe(
                (t_fin - t_first) / 1e3 / n_dec)

    t0 = time.perf_counter()
    while sched.has_work():
        if obs_on:
            _mark_eligible()
        for slot, req in _admissions():
            rng, k = jax.random.split(rng)
            tokens = np.asarray(req.tokens)
            plen = req.prompt_len
            if obs_on:
                t_adm = time.perf_counter_ns()
                rc = req_clock.setdefault(req.rid, {})
                t_el = rc.get("eligible", t_adm)
                rc["admit"] = t_adm
                if tr is not None:
                    tr.complete("serve/req/queue_wait", t_el,
                                t_adm - t_el, track=f"req {req.rid}",
                                args={"rid": req.rid, "slot": slot})
                if reg is not None:
                    reg.histogram("serve/req/queue_wait_us").observe(
                        (t_adm - t_el) / 1e3)
            t_pf = time.perf_counter_ns()
            info = pool.shared_info(slot) if prefix else None
            shared = info is not None and info.shared_pages > 0
            if shared:
                # prefix-cache hit: gather the matched pages out of the
                # live pool and prefill only the suffix against them
                sstart = info.suffix_start
                s_real = plen - sstart
                suffix = tokens[sstart:]
                if bucket:
                    suffix = np.pad(
                        suffix, [(0, bucket_len(s_real) - s_real)])
                sp = info.shared_pages
                n_pad = 1 << max(sp - 1, 0).bit_length()
                ctx_row = np.concatenate([
                    pool.slot_row(slot)[:sp],
                    np.full(n_pad - sp, pool.scratch_page, np.int32)])
                logits, req_cache = runner.prefill_partial(
                    jnp.asarray(suffix)[None], _gather_ctx(cache, ctx_row),
                    start=sstart, last_pos=s_real - 1)
                prefill_tokens += int(suffix.shape[0])
            elif bucket:
                pad = bucket_len(plen) - plen
                padded = np.pad(tokens, [(0, pad)] + [(0, 0)] * (
                    tokens.ndim - 1))
                logits, req_cache = runner.prefill(
                    jnp.asarray(padded)[None], last_pos=plen - 1)
                prefill_tokens += int(padded.shape[0])
            else:
                logits, req_cache = runner.prefill(jnp.asarray(tokens)[None])
                prefill_tokens += plen
            first = int(np.asarray(sample(logits, k)).reshape(-1)[0])
            t_ft = time.perf_counter_ns()
            if runner.last_cold:
                compile_ns += t_ft - t_pf
            if obs_on:
                rc = req_clock.setdefault(req.rid, {})
                rc["first"] = t_ft
                t_el = rc.get("eligible", t_pf)
                if tr is not None:
                    track = f"req {req.rid}"
                    tr.complete("serve/req/prefill", t_pf, t_ft - t_pf,
                                track=track,
                                args={"rid": req.rid, "tokens": plen,
                                      "shared": shared,
                                      "cold": runner.last_cold})
                    tr.complete("serve/req/ttft", t_el, t_ft - t_el,
                                track=track, args={"rid": req.rid})
                if reg is not None:
                    reg.histogram("serve/req/prefill_us").observe(
                        (t_ft - t_pf) / 1e3)
                    reg.histogram("serve/req/ttft_us").observe(
                        (t_ft - t_el) / 1e3)
            if sched.started(slot, first):
                if paged:
                    if shared:
                        # divergence inside a shared page: give the slot
                        # a private copy BEFORE the suffix write lands
                        cow = pool.cow_if_needed(slot)
                        if cow is not None:
                            cache = copy_page_cache(cache, *cow)
                        pool.ensure(slot, plen)
                        cache = insert_paged_span(
                            cache, runner.place_slot_cache(req_cache),
                            pool.slot_row(slot), sstart, plen - sstart,
                            slot)
                    else:
                        pool.ensure(slot, plen)
                        phys = list(pool.slot_pages(slot))
                        # pad the page list to a pow2 count with the
                        # scratch page so the jitted insert compiles
                        # O(log max_pages) variants, not one per distinct
                        # prompt page count (scratch swallows the surplus
                        # pad pages harmlessly)
                        n_pad = 1 << max(len(phys) - 1, 0).bit_length()
                        phys += [pool.scratch_page] * (n_pad - len(phys))
                        req_cache = fit_cache_len(
                            req_cache, len(phys) * page_size)
                        cache = insert_paged_cache(
                            cache, runner.place_slot_cache(req_cache),
                            phys, slot)
                    if prefix:
                        # future admissions may now share this prompt
                        pool.register_prefix(slot, tokens)
                else:
                    if bucket:
                        # drop pad positions; decode overwrites each
                        # position before the mask ever exposes it
                        req_cache = fit_cache_len(req_cache, plen)
                    cache = insert_slot_cache(
                        cache, runner.place_slot_cache(req_cache), slot)
                cur = cur.at[slot, 0].set(first)
            elif obs_on:
                # max_new_tokens == 1: finished off the prefill alone;
                # the slot never enters the decode batch
                _finish_req(req.rid, time.perf_counter_ns())
            # (nothing to insert for a prefill-only request)
        active = sched.active_mask()
        if not active.any():
            sched.idle_tick()
            continue
        rng, k = jax.random.split(rng)
        pos_host = sched.positions()
        n_active = int(active.sum())
        rid_by_slot = sched.slot_rids() if obs_on else None
        t_st = time.perf_counter_ns()
        pos = runner.place_pos(jnp.asarray(pos_host))
        if paged:
            # alloc-on-grow: map the page each live slot writes this step
            for i in np.flatnonzero(active):
                pool.ensure(int(i), int(pos_host[i]) + 1)
            pool.tick()
            fresh = pool.device_table()
            if fresh is not table_host:
                table_host = fresh
                table_placed = runner.place_table(fresh)
            lg, cache = runner.step_paged(cache, runner.place_tokens(cur),
                                          pos, table_placed,
                                          use_kernel=use_kernel)
        else:
            lg, cache = runner.step(cache, runner.place_tokens(cur), pos)
        nxt = sample(lg[:, -1], k)
        # the host pull below blocks on the step, so the wall time
        # around it is the true per-step latency (the engine is
        # host-synchronous per token by construction)
        nxt_host = np.asarray(nxt)
        t_en = time.perf_counter_ns()
        if runner.last_cold:
            compile_ns += t_en - t_st
        else:
            steady_ns += t_en - t_st
            steady_tokens += n_active
        if tr is not None:
            tr.complete("serve/decode_step", t_st, t_en - t_st,
                        track="engine",
                        args={"active": n_active,
                              "cold": runner.last_cold})
        if reg is not None:
            reg.histogram("serve/step/wall_us").observe(
                (t_en - t_st) / 1e3)
            reg.gauge("serve/slots/active").set(n_active)
        for slot in sched.advance(nxt_host):
            # pages went back to the allocator inside the scheduler;
            # per-slot SSM/conv state still needs the device-side zero
            cache = (evict_slot_state(cache, slot) if paged
                     else evict_slot(cache, slot))
            if obs_on:
                _finish_req(rid_by_slot[slot], time.perf_counter_ns())
        cur = nxt[:, None].astype(jnp.int32)
    jax.block_until_ready(cache)
    wall = time.perf_counter() - t0

    stats = sched.stats()
    stats["cache_len"] = cache_len
    stats["paged"] = paged
    stats["bucketed_prefill"] = bucket
    stats["prefix_cache"] = prefix
    stats["prefill_tokens"] = prefill_tokens
    # compatibility: tokens_per_sec keeps dividing by the FULL wall
    # clock (compile included); the honest split rides alongside
    stats["tokens_per_sec"] = round(
        stats["generated_tokens"] / wall, 3) if wall > 0 else 0.0
    stats["compile_time_s"] = round(compile_ns / 1e9, 6)
    stats["steady_tokens_per_sec"] = round(
        steady_tokens / (steady_ns / 1e9), 3) if steady_ns > 0 else 0.0
    stats["sharded"] = runner.mesh is not None
    return ServeResult(sched.results, stats, wall)


# ---------------------------------------------------------------------------
# frame-by-frame RNN serving (the paper's workload)
# ---------------------------------------------------------------------------

def shard_cell_params(params: dict, mesh, axis_name: str = "model") -> dict:
    """Cycle-balance every ``PaddedCSB`` cell weight over
    ``mesh[axis_name]`` (``dist.csb_partition``'s greedy planner) and
    place the whole tree with ``csb_shard_specs`` — after this,
    ``cell_apply`` under ``use_rules`` routes each MVM through
    ``csb_matvec_sharded``."""
    from repro.core.csb_format import PaddedCSB
    from repro.dist.csb_partition import partition_padded

    n_dev = mesh.shape[axis_name]
    out = {k: (partition_padded(w, n_dev)[1]
               if isinstance(w, PaddedCSB) else w)
           for k, w in params.items()}
    specs = csb_shard_specs(out, mesh, axis=axis_name)
    return jax.tree.map(
        lambda leaf, sp: jax.device_put(leaf, NamedSharding(mesh, sp)),
        out, specs)


def make_frame_step(graph: CellGraph):
    """The jitted one-frame step ``(params, state, x_t) -> (y, state)``
    of ``graph``; its program is ``jit_frame_step`` in a profile."""
    def frame_step(p, st, x):
        return cell_apply(graph, p, x, st)

    return jax.jit(frame_step)


# The frame server's jitted steps, one per key, least recently used
# first. jit caches by function identity, so a step built anew each call
# would trace and lower anew each call; a few dozen keys cover a process
# that serves a handful of cells (the test suite builds hundreds).
_STEP_CACHE_SIZE = 32
_step_cache: collections.OrderedDict = collections.OrderedDict()


def _frame_step_for(graph: CellGraph) -> tuple[Any, set]:
    """The cached jitted step of ``graph`` and the argument signatures
    it has run with, built by :func:`make_frame_step` on a miss.

    The key is what the step's trace reads besides its arguments: the
    graph's structure, the ``cell_apply`` it calls (a module global,
    looked up when it traces), the active model mesh (``ShardedCSB``
    weights route by it) and the kernel's interpret mode. jit's own
    cache keys the rest (shapes, dtypes, shardings)."""
    from repro.core.csb_linear import _active_model_mesh
    from repro.kernels import ops

    key = (graph.key, cell_apply, _active_model_mesh(),
           ops.default_interpret())
    entry, hit = cached_program(key, lambda: make_frame_step(graph))
    reg = obs_metrics.get()
    if reg is not None:
        reg.counter("serve/frames/step_cache/"
                    + ("hit" if hit else "miss")).inc()
    return entry


def cached_program(key, build) -> tuple[tuple[Any, set], bool]:
    """The step cache's entry for ``key`` — the program ``build()``
    made on a miss and the argument signatures it has run with — and
    whether it was a hit."""
    entry = _step_cache.get(key)
    hit = entry is not None
    if hit:
        _step_cache.move_to_end(key)
    else:
        entry = _step_cache[key] = (build(), set())
        if len(_step_cache) > _STEP_CACHE_SIZE:
            _step_cache.popitem(last=False)
    return entry, hit


def _arg_signature(tree: PyTree) -> tuple:
    """Tree structure plus each leaf's shape, dtype and placement: the
    arguments jit would compile anew for."""
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, tuple(
        (type(x), np.shape(x), getattr(x, "dtype", None),
         getattr(x, "weak_type", None), getattr(x, "sharding", None),
         getattr(x, "committed", None)) for x in leaves)


def rnn_serve_frames(graph: CellGraph, params: PyTree, frames,
                     state: PyTree | None = None,
                     warmup: int | None = None,
                     *, config: EngineConfig | None = None, mesh=None,
                     axis_name: str = "model",
                     collect_frame_times: bool | None = None):
    """frames: (T, B, in_dim). Weights may be dense, PaddedCSB, or (with
    a mesh) ShardedCSB.

    ``config.frame_warmup`` / ``config.collect_frame_times`` are the
    :class:`EngineConfig` homes of the two knobs; the positional
    ``warmup`` and ``collect_frame_times`` arguments override them when
    given explicitly (both default to the config).

    The jitted step is built once per graph structure, model mesh and
    kernel interpret mode and reused across calls (a small LRU). The
    ``warmup`` steps run before timing only when the step is fresh to
    these arguments: its first call with this tree structure and these
    leaf shapes, dtypes and shardings of ``(params, state, frames)``,
    i.e. when the step would trace, lower and compile. On later calls
    nothing compiles and no warm-up step runs.

    With ``mesh=`` (or an active Rules mesh with a non-trivial "model"
    axis) the CSB weights are partitioned over the model axis and the
    frame batch sharded over the data axes, so the per-frame latency is
    measured on the sharded mesh — the paper's faster-than-realtime
    number at multi-chip scale. Returns (outputs (T,B,H), final state,
    us_per_frame).

    ``collect_frame_times=True`` appends a 4th element: a ``(T,)``
    numpy array of per-frame wall microseconds, each frame blocked to
    completion before the next starts. Blocking serializes the device
    pipeline, so the MEAN of these is pessimistic — the un-blocked
    ``us_per_frame`` stays the throughput number; the per-frame vector
    is for tail latency (p99) reporting, where realtime audio cares
    about the worst frame, not the average.

    Each call opens a ``serve/frames/call`` span and one span per phase
    inside it (``prepare``, ``warmup``, ``dispatch``, ``sync``,
    ``stack``) through :func:`repro.obs.trace.span`: no-ops unless the
    ring tracer is on or a JAX profiler session is active."""
    with obs_trace.span("serve/frames/call"):
        with obs_trace.span("serve/frames/prepare"):
            fcfg = resolve_config(config, caller="rnn_serve_frames")
            if warmup is None:
                warmup = fcfg.frame_warmup
            if collect_frame_times is None:
                collect_frame_times = fcfg.collect_frame_times
            mesh = _resolve_mesh(mesh)
            rules = current_rules()
            if mesh is not None:
                if axis_name in tuple(mesh.axis_names) \
                        and mesh.shape[axis_name] > 1:
                    params = shard_cell_params(params, mesh, axis_name)
                frames = jnp.asarray(frames)      # (T, B, in): B=dp
                frames = jax.device_put(frames, NamedSharding(
                    mesh, _dp_spec(mesh, frames.shape, batch_axis=1)))
                if rules is None or rules.mesh is not mesh:
                    rules = Rules({}, mesh=mesh)
            if rules is None:
                rules = Rules({})

            if state is None:
                state = init_state(graph, frames.shape[1:-1], jnp.float32)

            with use_rules(rules):
                step, seen = _frame_step_for(graph)
            sig = _arg_signature((params, state, frames))
            fresh = sig not in seen

        with use_rules(rules):
            with obs_trace.span("serve/frames/warmup"):
                # a fresh step's trace, lowering and compile; empty when
                # the step has run with these arguments before
                if fresh and warmup:
                    for _ in range(warmup):
                        y, _ = step(params, state, frames[0])
                    y.block_until_ready()
                seen.add(sig)

            outs = []
            t0 = time.perf_counter()
            st = state
            with obs_trace.span("serve/frames/dispatch"):
                for t in range(frames.shape[0]):
                    y, st = step(params, st, frames[t])
                    outs.append(y)
            with obs_trace.span("serve/frames/sync"):
                jax.block_until_ready(outs[-1])
            dt = time.perf_counter() - t0

            frame_us = None
            if collect_frame_times:
                # separate per-frame-blocking pass so the throughput
                # number above is untouched by the serialization;
                # per-frame spans and the realtime histogram
                # (serve/frames/wall_us — the distribution the 500us
                # budget judges) come from HERE, measured times
                # recorded after the fact so tracing adds zero overhead
                # inside the timed region
                tr = obs_trace.get()
                reg = obs_metrics.get()
                times = np.empty(frames.shape[0])
                st2 = state
                for t in range(frames.shape[0]):
                    f0 = time.perf_counter_ns()
                    y2, st2 = step(params, st2, frames[t])
                    jax.block_until_ready((y2, st2))
                    dur = time.perf_counter_ns() - f0
                    times[t] = dur / 1e3
                    if tr is not None:
                        tr.complete("serve/frame", f0, dur, track="frames",
                                    args={"frame": t})
                    if reg is not None:
                        reg.histogram("serve/frames/wall_us").observe(
                            dur / 1e3)
                frame_us = times
        with obs_trace.span("serve/frames/stack"):
            ys = jnp.stack(outs)
    us_per_frame = dt / frames.shape[0] * 1e6
    if collect_frame_times:
        return ys, st, us_per_frame, frame_us
    return ys, st, us_per_frame
