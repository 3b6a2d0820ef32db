"""Reduce a profiler trace (xplane) to the benchmark's device numbers.

* busy: the union of the intervals in which an operation ran on a device,
  per device, inside the traced window;
* per-operation device time, summed over the window, by HLO instruction
  name (a Pallas kernel keeps its function's name there: `paged_attention`,
  `prefill_attention_paged`, `decode_sample`, `decode` ...).  Operations
  inside a loop are events of their own and overlap the loop's event;
* idle gaps: the intervals of the window in which a device ran nothing,
  each named by the innermost harness span (`SPAN_PREFIX`) open on the
  host at the gap's middle.

The traced window is the harness span named `WINDOW_SPAN`.  Device events
are read from the "XLA Ops" line of each `/device:TPU:<n>` plane; the
profiler puts host and device events on one clock.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def op_name(event_name: str) -> str:
    """'%paged_attention.11 = f32[...] custom-call(...)' -> 'paged_attention.11'"""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def host_spans(pd) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, a, b in _events(line):
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, a, b))
    return spans


def reduce(pd, top: int = 10) -> dict:
    """Busy time, per-op time and named idle gaps of a ProfileData."""
    spans = host_spans(pd)
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = win[0][1], win[0][2]
    inner = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s[1] for s in inner]
    per_dev, op_time = [], defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for name, a, b in _events(line):
                if b <= lo or a >= hi:
                    continue
                a, b = max(a, lo), min(b, hi)
                ivs.append((a, b))
                op_time[op_name(name)] += (b - a) * 1e-9
        busy = union(ivs)
        per_dev.append(sum(b - a for a, b in busy) * 1e-9)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _who(inner, starts, (a + b) / 2)))
    n_dev = len(per_dev)
    if not n_dev:
        raise ValueError("trace holds no TPU device plane")
    by_name = defaultdict(float)
    for dur, who in gaps:
        by_name[who] += dur * 1e-9 / n_dev
    ops = sorted(((k, v / n_dev) for k, v in op_time.items()),
                 key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(per_dev) / n_dev,
        "busy_s_per_device": per_dev,
        "op_seconds": dict(ops),
        "device_ops": [[k, v] for k, v in ops[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(by_name.items(),
                                                key=lambda kv: -kv[1])[:top]],
    }


def _who(spans, starts, t: float) -> str:
    """The innermost span open at t: of the nested spans that hold t, the
    one that started last."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        name, a, b = spans[i]
        if b >= t:
            return name
        i -= 1
    return "bench:outside-engine"


def kernel_seconds(reduced: dict, kernel: str) -> float:
    """Device seconds of every instance of one named kernel."""
    rx = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    return sum(v for k, v in reduced["op_seconds"].items() if rx.match(k))


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def describe(pd, per_line: int = 5) -> Dict[str, list]:
    """Planes, lines and a few events with their stats: for looking at a
    trace by hand before writing readers against it."""
    out = {}
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = []
            for i, e in enumerate(line.events):
                if i >= per_line:
                    break
                evs.append([e.name, e.start_ns, e.duration_ns,
                            [[str(k), str(v)[:200]] for k, v in e.stats]])
            lines.append([line.name, evs])
        out[plane.name] = lines
    return out
