"""From a profiler trace to busy time, idle gaps, kernel times and the
breakdown.

Two stages, so that the second can be checked on a small recorded trace
without a chip:

- ``events(xplane_path)`` reads the ``.xplane.pb`` that
  ``jax.profiler`` writes: every operation on each device plane
  (``/device:TPU:<n>``, its "XLA Ops" line) and the benchmark's own host
  spans (``TraceAnnotation``) on the host plane, as [name, start_ns,
  duration_ns] on the trace's one clock.
- ``summarize(events)`` reduces them over the window, the host span
  named ``window``: busy time is the union of the operation intervals
  on a device, averaged over the devices; an idle gap is a stretch of
  the window in which no operation runs, named by the host span that
  overlaps it most.
"""
from __future__ import annotations

import collections
import re

WINDOW = "window"
# host spans the window loop records (run.py)
SPANS = ("batch", "dispatch", "wait")
OPS_LINE = "XLA Ops"


def events(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, float(e.start_ns), float(e.duration_ns)]
                         for e in line.events
                         if e.name in SPANS or e.name == WINDOW]
    return {"device": device, "host": host}


def label(text: str) -> str:
    """An operation's name and result type from the trace's HLO text,
    as in ``fusion.9 u32[452747264]{0:T(1024)}``."""
    head, _, rest = text.partition(" = ")
    return (head.lstrip("%") + " " + rest.split(" ")[0]).strip()[:100]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def summarize(ev: dict, top: int = 10) -> dict:
    windows = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW]
    if not windows:
        raise ValueError("the trace holds no host span named 'window'")
    w0, w1 = windows[0]
    spans = [(n, s, s + d) for n, s, d in ev["host"] if n in SPANS]
    totals = collections.defaultdict(float)
    counts = collections.defaultdict(int)
    busy, gaps = [], []
    for name, evs in sorted(ev["device"].items()):
        clipped = []
        for op, s, d in evs:
            s, e = max(s, w0), min(s + d, w1)
            if e > s:
                clipped.append((s, e))
                totals[label(op)] += (e - s) / 1e9
                counts[label(op)] += 1
        merged = _merge(clipped)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                who = max(spans, default=None,
                          key=lambda sp: _overlap(g0, g1, sp[1], sp[2]))
                doing = (who[0] if who and
                         _overlap(g0, g1, who[1], who[2]) > 0 else "none")
                gaps.append((g1 - g0, doing))
    if not busy:
        raise ValueError("the trace holds no device operations")
    ops = sorted(totals.items(), key=lambda kv: -kv[1])
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (w1 - w0) / 1e9,
        "op_seconds": dict(totals),
        "op_counts": dict(counts),
        "device_ops": [[n, s] for n, s in ops[:top]],
        "idle_gaps": [[doing, g / 1e9] for g, doing in gaps[:top]],
    }


def kernel_time(summary: dict, kernel: str) -> tuple[float, int]:
    """(seconds, calls) of the operations named for ``kernel``: the
    custom call ``<kernel>`` or ``<kernel>.<n>``, not an operation that
    only takes its result, nor a longer word that holds it
    (``quantize`` does not match ``dequantize``)."""
    pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    hits = [n for n in summary["op_seconds"] if pat.match(n.split(" ")[0])]
    return (sum(summary["op_seconds"][n] for n in hits),
            sum(summary["op_counts"][n] for n in hits))
