"""The program's own spans in a traced window.

The frame server (``repro.serve.rnn_serve_frames``) writes one
``serve/frames/call`` span a call and, inside it, one span a phase into
the profiler's trace, on the device's clock. These helpers pick them out
of a ``trace_reduce.Trace`` by name, inside the window; the ``metrics/``
readers of the frame server's layer use them. A program that writes no
such span gives no call, and a reader then reads nothing (None), not 0.

As a script, it prints how a traced run's window and device idle time
split by phase:

    python3 benchmarks/chip/spans.py benchmarks/chip/.traces/sr1.stream_b8
"""
from __future__ import annotations

import bisect
import json
import sys

CALL = "serve/frames/call"
PHASES = tuple(f"serve/frames/{p}" for p in
               ("prepare", "warmup", "dispatch", "sync", "stack"))
WARMUP, DISPATCH = PHASES[1], PHASES[2]


def spans(tr, name: str) -> list[tuple[float, float]]:
    """(start, end) in ns of every host span called ``name`` that lies
    wholly inside the window, in order."""
    if tr is None or tr.window is None:
        return []
    lo, hi = tr.window
    return sorted((s, s + d) for evs in tr.host.values()
                  for n, s, d in evs if n == name and lo <= s
                  and s + d <= hi)


def calls(tr) -> int:
    """Frame-server calls in the window."""
    return len(spans(tr, CALL))


def total_ns(tr, name: str) -> float:
    """Summed duration of the spans called ``name`` in the window."""
    return float(sum(b - a for a, b in spans(tr, name)))


def idle_ns(tr, name: str) -> float:
    """Device idle time under the spans called ``name``, averaged over
    the device planes as ``trace_reduce.Trace.busy_ns`` averages."""
    ivs = spans(tr, name)
    if not ivs or not tr.device_ops:
        return 0.0
    idle = 0.0
    for plane in tr.device_ops:
        busy = tr.busy_intervals(plane)
        starts = [a for a, _ in busy]
        before = [0.0]                  # busy time before interval i
        for a, b in busy:
            before.append(before[-1] + b - a)

        def busy_until(t):
            i = bisect.bisect_right(starts, t)
            return before[i] - max(0.0, busy[i - 1][1] - t) if i else 0.0

        idle += sum((b - a) - (busy_until(b) - busy_until(a))
                    for a, b in ivs)
    return idle / len(tr.device_ops)


def phase_split(tr) -> dict:
    """Seconds of the window and of device idle time under each phase,
    under a call but no phase, and under no call at all."""
    window, idle = tr.window_ns, tr.window_ns - tr.busy_ns()
    call, call_idle = total_ns(tr, CALL), idle_ns(tr, CALL)
    rows = {p: (total_ns(tr, p), idle_ns(tr, p)) for p in PHASES}
    rows[CALL + " (no phase)"] = (
        call - sum(w for w, _ in rows.values()),
        call_idle - sum(i for _, i in rows.values()))
    rows["(no program span)"] = (window - call, idle - call_idle)
    rows["window"] = (window, idle)
    return {k: {"window_s": w / 1e9, "idle_s": i / 1e9}
            for k, (w, i) in rows.items()}


if __name__ == "__main__":
    import trace_reduce
    tr = trace_reduce.load(sys.argv[1])
    print(json.dumps({"calls": calls(tr), "split": phase_split(tr)},
                     indent=1))
