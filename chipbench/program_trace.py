"""Reduce a profiler trace (xplane) by the program's own names.

chipbench/trace.py reads device operations and names idle time by the
harness's `bench:` spans; this module reads the same window by what the
serving program names itself:

* module_seconds / module_runs: device seconds and runs per device
  program, from each TPU plane's "XLA Modules" line, the `(<hash>)`
  stripped (`jit_serve_decode_sample`, `jit_serve_prefill_chunk_batched`
  ...);
* program_idle: device idle seconds of the window, each gap named by the
  innermost `serve.` span open on the host at its middle (its self time),
  `OUTSIDE` where none is open;
* program_idle_inclusive: the same gaps counted for every `serve.` span
  open at their middle, so `["serve.step"]` is all idle time inside
  engine steps.

Seconds and runs are averages over the trace's TPU planes.  The `serve.`
spans are opened by one host thread (src/repro/serve/tracing.py), so
they nest.

    python3 chipbench/program_trace.py <file.xplane.pb[.xz]>

prints the reduction as JSON.
"""
from __future__ import annotations

import json
import lzma
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench.trace import (DEVICE_PLANE, OPS_LINE, WINDOW_SPAN,  # noqa: E402
                             union)

PROGRAM_PREFIX = "serve."
STEP_SPAN = "serve.step"
OUTSIDE = "outside-serve"
MODULES_LINE = "XLA Modules"
_HASH = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """'jit_serve_decode(8695725819138592199)' -> 'jit_serve_decode'"""
    return _HASH.sub("", event_name)


def _spans(pd) -> Tuple[Tuple[float, float], List[Tuple[str, float, float]]]:
    """The traced window and the program's spans, by start."""
    window, spans = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name == WINDOW_SPAN:
                    window = (float(e.start_ns),
                              float(e.start_ns + e.duration_ns))
                elif name.startswith(PROGRAM_PREFIX):
                    spans.append((name, float(e.start_ns),
                                  float(e.start_ns + e.duration_ns)))
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    spans.sort(key=lambda s: (s[1], -s[2]))
    return window, spans


def open_spans(spans, points) -> List[List[str]]:
    """For each point of the ascending `points`, the names of the spans
    (sorted by start, outer first at equal starts, properly nested) open
    at it, outermost first."""
    out, stack, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i][1] <= t:
            while stack and stack[-1][2] < spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        out.append([s[0] for s in stack])
    return out


def reduce_program(pd) -> dict:
    """module_seconds, module_runs, program_idle and
    program_idle_inclusive of a ProfileData (module docstring)."""
    (lo, hi), spans = _spans(pd)
    mod_time: Dict[str, float] = defaultdict(float)
    mod_runs: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[float, float]] = []      # (middle, seconds)
    n_dev = 0
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        n_dev += 1
        ivs = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                a = float(e.start_ns)
                b = a + float(e.duration_ns)
                if b <= lo or a >= hi:
                    continue
                a, b = max(a, lo), min(b, hi)
                if line.name == OPS_LINE:
                    ivs.append((a, b))
                else:
                    name = module_name(e.name)
                    mod_time[name] += (b - a) * 1e-9
                    mod_runs[name] += 1
        edges = [lo] + [x for iv in union(ivs) for x in iv] + [hi]
        gaps += [((a + b) / 2, (b - a) * 1e-9)
                 for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    if not n_dev:
        raise ValueError("trace holds no TPU device plane")
    gaps.sort()
    idle: Dict[str, float] = defaultdict(float)
    inclusive: Dict[str, float] = defaultdict(float)
    for (_, dur), names in zip(gaps, open_spans(spans, [g[0] for g in gaps])):
        idle[names[-1] if names else OUTSIDE] += dur / n_dev
        for name in set(names):
            inclusive[name] += dur / n_dev

    def ranked(d):
        return dict(sorted(d.items(), key=lambda kv: -kv[1]))
    return {
        "module_seconds": ranked({k: v / n_dev for k, v in mod_time.items()}),
        "module_runs": ranked({k: v / n_dev for k, v in mod_runs.items()}),
        "program_idle": ranked(idle),
        "program_idle_inclusive": ranked(inclusive),
    }


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".xz"):
        return ProfileData.from_serialized_xspace(
            lzma.decompress(Path(path).read_bytes()))
    return ProfileData.from_file(path)


if __name__ == "__main__":
    print(json.dumps(reduce_program(load(sys.argv[1])), indent=1))
