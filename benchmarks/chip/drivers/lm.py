"""Offline batches through ``repro.serve.serve_continuous`` (paged
cache, continuous batching), calls back to back.

Traffic (``traffic/<mix>.json``): each call serves
``requests_per_call`` requests, all queued at its start, over
``n_slots`` slots. Prompt lengths come from ``prompt_lens`` in equal
shares, in an order drawn from the seed, with token ids drawn from the
seed; each request asks for ``new_tokens`` greedy tokens. Every seed
serves the same lengths, so every seed does the same work.

After the window, ``check_requests`` finished requests are drawn from
the seed, in equal shares of each prompt length (so the longest are
among them), and the configuration's plain reference is run over each
prompt with its served tokens. The number compared is the widest gap by
which a served token's reference logit lies below the reference's best
at that position.
"""
from __future__ import annotations

import collections
import traceback

import jax
import jax.numpy as jnp
import numpy as np


class State:
    pass


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def requests(cell, seed: int, call: int, new_tokens: int | None = None,
             stream: int = 1):
    """The ``call``-th batch of requests of this seed (``stream`` 1; the
    warm-up draws from another)."""
    from repro.serve import Request

    t = cell.traffic
    vocab = cell.config["vocab_size"]
    lens = np.repeat(t["prompt_lens"],
                     t["requests_per_call"] // len(t["prompt_lens"]))
    rng = _rng(seed, stream, call)
    rng.shuffle(lens)
    return [Request(rid=i, tokens=rng.integers(0, vocab, size=int(n),
                                               dtype=np.int32),
                    max_new_tokens=new_tokens or t["new_tokens"])
            for i, n in enumerate(lens)]


def setup(cell, seed: int) -> State:
    from harness import seed_key
    from repro.serve import EngineConfig

    t, mod = cell.traffic, cell.config_mod
    s = State()
    s.cell, s.seed = cell, seed
    s.k_weights = seed_key(seed)
    s.mcfg = mod.program_config(cell.config)
    s.params = jax.block_until_ready(
        mod.program_params(cell.config, s.k_weights))
    # the cache length the window's calls have (the default: longest
    # prompt + new tokens), so warm-up and window share every shape
    s.ecfg = EngineConfig(paged=True, n_slots=t["n_slots"],
                          cache_len=max(t["prompt_lens"]) + t["new_tokens"])
    # warm-up: every prompt length, inserts, the decode step, evictions
    by_len = {}
    for r in requests(cell, seed, 0, new_tokens=3, stream=3):
        by_len.setdefault(r.prompt_len, []).append(r)
    _serve(s, [r for rs in by_len.values() for r in rs[:2]])
    return s


def _serve(s: State, reqs):
    from repro.serve import serve_continuous
    return serve_continuous(s.params, s.mcfg, reqs, s.ecfg)


def window(s: State, seconds: float, span) -> dict:
    """Whole calls until ``seconds`` have passed."""
    s.done = []               # (request, served tokens)
    out = {"attempted": 0, "failed": 0, "generated_tokens": 0,
           "prompt_tokens": 0, "completed": 0, "decode_steps": 0,
           "occupied_steps": 0.0, "units": []}
    call = 0
    while True:
        reqs = requests(s.cell, s.seed, call)
        out["attempted"] += len(reqs)
        try:
            with span("serve_continuous"):
                res = _serve(s, reqs)
        except Exception:  # a call that fails ends the window
            traceback.print_exc()
            out["failed"] += len(reqs)
            break
        st = res.stats
        unit = {"decode_steps": st["decode_steps"], "prompt_tokens": 0,
                "occupied_steps": st["occupancy"] * st["decode_steps"],
                "generated_tokens": 0, "completed": 0,
                "prefills_by_len": collections.Counter(
                    r.prompt_len for r in reqs)}
        for r in reqs:
            got = res.tokens.get(r.rid, [])
            if len(got) != r.max_new_tokens:
                out["failed"] += 1
                continue
            unit["completed"] += 1
            unit["generated_tokens"] += len(got)
            unit["prompt_tokens"] += r.prompt_len
            s.done.append((r, list(got)))
        for k, v in unit.items():
            if k != "prefills_by_len":
                out[k] += v
        out["units"].append(unit)
        span.unit_done()
        call += 1
        if span.elapsed() >= seconds:
            break
    return out


def find_programs(runs: dict, decode_steps: int,
                  prefills_by_len: dict) -> tuple[list, list]:
    """The engine's decode-step and prefill programs among a trace's
    ``runs`` ({program: (runs, ns)}). The engine jits partials, which the
    trace names ``jit__unknown(<fingerprint>)``, so they are told apart by
    their run counts: the decode step runs once a decode step (the
    longest of the programs that do), a prefill program once a prompt of
    its length. Returns ([(runs, ns)] of the decode step, of the
    prefills); a list is empty where nothing matches."""
    def longest(count, k, skip=()):
        c = sorted(((ns, name) for name, (n, ns) in runs.items()
                    if n == count and name not in skip), reverse=True)
        return [name for _, name in c[:k]]

    decode = longest(decode_steps, 1) if decode_steps else []
    by_count: dict = {}
    for plen, count in prefills_by_len.items():
        by_count[count] = by_count.get(count, 0) + 1
    prefill = []
    for count, k in by_count.items():
        names = longest(count, k, skip=decode)
        if len(names) < k:
            return [runs[n] for n in decode], []
        prefill += names
    return [runs[n] for n in decode], [runs[n] for n in prefill]


def release(s: State) -> None:
    s.params = None


def _sample(s: State, seed: int):
    """The requests to compare, in equal shares of each prompt length,
    grouped by (prompt length, served length)."""
    n = s.cell.traffic["check_requests"]
    lens = sorted({r.prompt_len for r, _ in s.done})
    rng = _rng(seed, 2)
    groups = {}
    for plen in lens:
        pool = [(r, g) for r, g in s.done if r.prompt_len == plen]
        k = min(len(pool), max(n // len(lens), 1))
        for i in sorted(rng.choice(len(pool), size=k, replace=False)):
            r, g = pool[i]
            groups.setdefault((plen, len(g)), []).append((r, g))
    return groups


def _gaps(s: State, seed: int, modes: tuple[str, ...]) -> dict:
    """Per mode, the widest reference-logit gap of the tokens compared:
    the served tokens for "served", else the tokens that the reference
    computed in that mode puts first."""
    mod, cfg = s.cell.config_mod, s.cell.config
    if not s.done:
        return {m: float("nan") for m in modes}
    params = mod.reference_params(cfg, s.k_weights)
    worst = {m: 0.0 for m in modes}
    for (plen, n), items in sorted(_sample(s, seed).items()):
        seqs = np.stack([np.concatenate([r.tokens,
                                         np.asarray(g[:-1], np.int32)])
                         for r, g in items])
        served = jnp.asarray(np.stack([g for _, g in items]))
        ref = mod.reference_logits(cfg, params, seqs, plen - 1)
        best = ref.max(-1)
        for m in modes:
            if m == "served":
                tok = served
            else:
                tok = jnp.argmax(mod.reference_logits(
                    cfg, params, seqs, plen - 1, mode=m), -1)
            gap = float((best - jnp.take_along_axis(
                ref, tok[..., None], -1)[..., 0]).max())
            worst[m] = gap if gap != gap else max(worst[m], gap)
    return worst


def check(s: State, out: dict, seed: int) -> list[dict]:
    return [{"name": "lm_max_logit_gap",
             "value": _gaps(s, seed, ("served",))["served"],
             "limit": s.cell.config["limits"]["lm_max_logit_gap"]}]


def control(s: State, out: dict, seed: int) -> dict:
    """The compared number of the program and of the control: the
    tokens that the reference computed one precision step below the
    configuration's puts first."""
    m = s.cell.config_mod.CONTROL_MODE
    g = _gaps(s, seed, ("served", m))
    return {"lm_max_logit_gap": (g["served"], g[m])}
