"""From a JAX profiler trace to the numbers the per-layer metrics read.

``load`` reads the newest ``*.xplane.pb`` under a directory into plain
lists of ``(name, start_ns, duration_ns)``: per device plane its "XLA Ops"
and "XLA Modules" lines, and the host's thread lines. Everything else
here works on those lists, so a test can hand it a small recorded trace
(``to_json`` / ``from_json``).

The window is the span of the harness's own annotations (``bench/...``,
one around each entry call). Busy time is the union of the intervals in
which an operation ran on a device, clipped to the window and averaged
over the devices; idle time is the rest. Each idle gap is labelled with
what the host was doing at its midpoint: the innermost host event that
covers it.
"""
from __future__ import annotations

import collections
import functools
import heapq
import json
from pathlib import Path

SPAN_PREFIX = "bench/"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Trace:
    def __init__(self, device_ops: dict, device_modules: dict,
                 host: dict):
        # plane -> [(name, start_ns, dur_ns)], sorted by start
        self.device_ops = {k: sorted(v, key=lambda e: e[1])
                           for k, v in device_ops.items() if v}
        self.device_modules = {k: sorted(v, key=lambda e: e[1])
                               for k, v in device_modules.items() if v}
        # host thread -> [(name, start_ns, dur_ns)]
        self.host = {k: sorted(v, key=lambda e: e[1])
                     for k, v in host.items() if v}
        spans = [e for evs in self.host.values() for e in evs
                 if e[0].startswith(SPAN_PREFIX)]
        if spans:
            self.window = (min(e[1] for e in spans),
                           max(e[1] + e[2] for e in spans))
        else:
            self.window = None

    # -- window and busy time ------------------------------------------
    @property
    def window_ns(self) -> float:
        return 0.0 if self.window is None else float(
            self.window[1] - self.window[0])

    def busy_intervals(self, plane: str) -> list[tuple[float, float]]:
        """Union of the plane's op intervals, clipped to the window."""
        lo, hi = self.window
        merged: list[list[float]] = []
        for _, s, d in self.device_ops.get(plane, ()):
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return [(a, b) for a, b in merged]

    def busy_ns(self) -> float:
        """Busy time averaged over the device planes that ran any op."""
        if self.window is None or not self.device_ops:
            return 0.0
        per = [sum(b - a for a, b in self.busy_intervals(p))
               for p in self.device_ops]
        return sum(per) / len(per)

    def idle_share(self) -> float | None:
        """1 - busy / window, or None where nothing can be read."""
        if not self.window_ns or not self.device_ops:
            return None
        return 1.0 - self.busy_ns() / self.window_ns

    # -- device time by name ---------------------------------------------
    def op_ns(self, match) -> float:
        """Summed device time of ops whose name satisfies ``match``,
        inside the window, averaged over planes (one chip: its own)."""
        if not self.device_ops or self.window is None:
            return 0.0
        lo, hi = self.window
        tot = sum(max(0.0, min(s + d, hi) - max(s, lo))
                  for evs in self.device_ops.values() for n, s, d in evs
                  if match(n))
        return tot / len(self.device_ops)

    def module_runs(self) -> dict[str, tuple[int, float]]:
        """Per compiled program (by its full name): its runs inside the
        window and their summed device time in ns."""
        lo, hi = self.window or (float("-inf"), float("inf"))
        runs: dict = {}
        for evs in self.device_modules.values():
            for name, s, d in evs:
                if lo <= s and s + d <= hi:
                    n, ns = runs.get(name, (0, 0.0))
                    runs[name] = (n + 1, ns + d)
        return runs

    # -- breakdown ------------------------------------------------------------
    def top_ops(self, n: int = 10) -> list[list]:
        acc: collections.Counter = collections.Counter()
        for evs in self.device_ops.values():
            for name, _, d in evs:
                acc[name] += d
        k = max(len(self.device_ops), 1)
        return [[name, ns / k / 1e9] for name, ns in acc.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle time by the host activity at each gap's midpoint, the
        ``n`` largest totals, first plane only (one chip)."""
        if self.window is None or not self.device_ops:
            return []
        plane = next(iter(self.device_ops))
        lo, hi = self.window
        edges = [lo]
        for a, b in self.busy_intervals(plane):
            edges += [a, b]
        edges.append(hi)
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        labels = innermost_host_events(self.host, [(a + b) / 2
                                                   for a, b in gaps])
        acc: collections.Counter = collections.Counter()
        for (a, b), label in zip(gaps, labels):
            acc[label] += b - a
        return [[name, ns / 1e9] for name, ns in acc.most_common(n)]


def innermost_host_events(host: dict, times: list) -> list[str]:
    """For each of the ascending ``times``, the name of the shortest host
    event (over all threads) that covers it: a sweep over the events by
    start, with the live ones in a heap by duration."""
    evs = sorted((e for line in host.values() for e in line),
                 key=lambda e: e[1])
    live: list = []
    out = []
    i = 0
    for t in times:
        while i < len(evs) and evs[i][1] <= t:
            name, start, dur = evs[i]
            heapq.heappush(live, (dur, start + dur, name))
            i += 1
        while live and live[0][1] < t:      # ended before t: never again
            heapq.heappop(live)
        out.append(live[0][2] if live else "(no host event)")
    return out


@functools.lru_cache(maxsize=None)
def short_name(name: str) -> str:
    """An op or program as the trace names it, without its HLO text and
    instance number: "%csb_mvm_pallas.16 = f32[...] custom-call(...)" is
    "csb_mvm_pallas", "jit_step(1748...)" is "jit_step"."""
    name = name.split(" = ", 1)[0].lstrip("%")
    name = name.split("(", 1)[0]
    base, _, num = name.rpartition(".")
    return base if base and num.isdigit() else name


def load(trace_dir: str | Path) -> Trace:
    """Read the newest profiler trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    ops: dict = {}
    mods: dict = {}
    host: dict = {}
    for plane in pd.planes:
        pname = plane.name
        if pname.startswith("/device:") and "CPU" not in pname:
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    # ops by their short name; programs keep their
                    # fingerprint, which tells unnamed programs apart
                    name = short_name if line.name == OPS_LINE else str
                    evs = [(name(e.name), e.start_ns, e.duration_ns)
                           for e in line.events]
                    (ops if line.name == OPS_LINE else mods)[pname] = evs
        elif pname.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
                if evs:
                    host[f"{pname}/{line.name}"] = evs
    return Trace(ops, mods, host)


def to_json(tr: Trace) -> str:
    return json.dumps({"device_ops": tr.device_ops,
                       "device_modules": tr.device_modules,
                       "host": tr.host})


def from_json(text: str) -> Trace:
    d = json.loads(text)
    conv = {k: {p: [tuple(e) for e in evs] for p, evs in d[k].items()}
            for k in ("device_ops", "device_modules", "host")}
    return Trace(conv["device_ops"], conv["device_modules"], conv["host"])
