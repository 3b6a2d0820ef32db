"""Kernel autotuner: per-(shape-bucket, posit format, backend) tile caches.

Every Pallas kernel in this package ships tile/block constants tuned for the
MXU/VPU geometry (`_BM/_BN/_BK` in posit_matmul.py, `_BLOCK_R/_BLOCK_C` in
posit_codec.py, the query tile of the multi-query paged-attention grid).
Those constants are the *fallback*; this module resolves the actual launch
parameters through a persisted autotune cache at dispatch time (ops.py),
so a sweep run once per host platform (launch/autotune.py) speeds up every
later process without any code change.

Cache JSON schema (version `CACHE_VERSION`)
-------------------------------------------

    {
      "version": 1,
      "backend": "cpu",                     # jax.default_backend() at sweep
      "generated_by": "launch/autotune.py",
      "entries": {
        "<digest>": {
          "kernel": "posit_matmul",         # tunable name (TUNABLES key)
          "key":    {"shape": [256, 512, 256], "fmts": ["P16_2", "P16_2"]},
          "params": {"bm": 128, "bn": 256, "bk": 512},
          "ms":     0.42,                   # best measured wall clock
          "oracle_ms": 0.011                # roofline estimate of the winner
        }, ...
      }
    }

Key digest
----------

`key_digest(kernel, backend, key)` = first 16 hex chars of blake2b over the
canonical (sorted-keys, no-whitespace) JSON of
`{"version", "kernel", "backend", "key"}` — so a cache entry is invalidated
automatically by a schema bump, a backend change, or any change to the key
contents.  The shape component of the key is *bucketed* (`shape_bucket`:
each dim rounded up to the next power of two, min 8) so one sweep covers a
band of problem sizes; kernels clamp/pad internally, which keeps any
bucketed winner correct for every shape in its bucket.

Regenerating the committed cache
--------------------------------

`src/repro/kernels/autotune_cache.json` is the committed cache for the CI
host platform (CPU interpret mode).  Regenerate it with:

    PYTHONPATH=src python -m repro.launch.autotune --commit

which sweeps the serving-representative shape set (see
`launch/autotune.py`), prunes each candidate grid with the roofline cost
oracle (`oracle_cost`, cross-checkable against `launch/hlo_analysis.py`
via `hlo_cost`), wall-clock times the survivors, and rewrites the JSON.
Set `REPRO_AUTOTUNE=off` to disable cache lookups entirely, or
`REPRO_AUTOTUNE_CACHE=/path.json` to point at a different cache file.

Cost oracle
-----------

The sweep is pruned before any timing: `oracle_cost(kernel, shape, params)`
computes the *padded* FLOP and HBM-byte volume a candidate tiling actually
launches (tiles larger than a dim pad it up — real wasted work) and turns
them into a roofline time with the published peaks of the chip the
kernels run on (`device_peaks`), the model `benchmarks/roofline.py`
applies to the dryrun sweeps.
Candidates whose oracle time exceeds `prune_factor` x the best oracle time are never
timed.  `hlo_cost(fn, *args)` lowers + compiles a candidate and runs
`launch.hlo_analysis.analyze_hlo` over the HLO text — the CLI's
`--oracle-check` mode uses it to validate the analytic model against the
compiler's view.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import os
import time

import jax
import jax.numpy as jnp

CACHE_VERSION = 1
DEFAULT_CACHE_PATH = os.path.join(os.path.dirname(__file__),
                                  "autotune_cache.json")

# bytes per element of the posit storage container / f32
_STORE_BYTES = {8: 1, 16: 2, 32: 4}


def _enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "on").lower() not in ("0", "off")


def cache_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_CACHE", DEFAULT_CACHE_PATH)


def shape_bucket(shape) -> tuple:
    """Round each dim up to the next power of two (min 8): the cache key's
    shape component, so one tuned entry covers a band of problem sizes."""
    out = []
    for d in shape:
        b = 8
        while b < d:
            b *= 2
        out.append(b)
    return tuple(out)


def _fmt_name(fmt) -> str:
    if fmt is None:
        return "f32"
    return f"P{fmt.n}_{fmt.es}"


def make_key(shape, fmts=()) -> dict:
    """Canonical cache key contents: bucketed shape + posit format names."""
    return {"shape": list(shape_bucket(shape)),
            "fmts": [_fmt_name(f) for f in fmts]}


def key_digest(kernel: str, backend: str, key: dict) -> str:
    blob = json.dumps({"version": CACHE_VERSION, "kernel": kernel,
                       "backend": backend, "key": key},
                      sort_keys=True, separators=(",", ":"))
    return hashlib.blake2b(blob.encode(), digest_size=8).hexdigest()


# ---------------------------------------------------------------------------
# tunable spaces: kernel name -> candidate parameter grid
# ---------------------------------------------------------------------------

TUNABLES = {
    "posit_codec.decode": {"block_r": (64, 128, 256, 512),
                           "block_c": (128, 256, 512, 1024)},
    "posit_codec.encode": {"block_r": (64, 128, 256, 512),
                           "block_c": (128, 256, 512, 1024)},
    "posit_matmul": {"bm": (128, 256), "bn": (128, 256), "bk": (256, 512)},
    "posit_matmul_grouped": {"bm": (128, 256), "bn": (128, 256),
                             "bk": (256, 512)},
    "paged_attention": {"t_block": (1, 2, 4, 8)},
    # fused prefill: TPU launch knobs — whether the batch grid dim may run
    # as a parallel (multi-core) dimension, and the Mosaic VMEM budget
    # (None = compiler default).  Neither changes the computed values.
    "prefill_attention": {"dimension_semantics": ("parallel", "arbitrary"),
                          "vmem_limit_mb": (None, 64, 128)},
    # fused decode epilogue: vocab tile width of the streamed logits GEMM +
    # sampler.  0 collapses the vocab grid dimension (whole vocab in one
    # step); any tiling is bitwise identical (rows stay whole).
    "decode_sample": {"v_block": (0, 512, 1024, 2048)},
}


def candidates(kernel: str):
    """Full parameter grid for a tunable kernel (pre-pruning)."""
    space = TUNABLES[kernel]
    names = sorted(space)
    for vals in itertools.product(*(space[n] for n in names)):
        yield dict(zip(names, vals))


# ---------------------------------------------------------------------------
# cost oracle: padded-volume roofline (+ HLO cross-check)
# ---------------------------------------------------------------------------

def _pad_up(d, b):
    b = min(b, d)
    return -(-d // b) * b


def device_peaks() -> dict:
    """Published peaks of the chip the kernels run on, looked up by the
    attached TPU's device kind (an unknown kind raises).  In interpret
    mode there is no such chip: the kernels stand in for the TPU v5e they
    are written for."""
    from repro.kernels import ops
    from repro.launch.mesh import PEAKS, V5E, peaks

    if ops.interpret_mode():
        return PEAKS[V5E]
    return peaks(jax.devices()[0].device_kind)


def oracle_cost(kernel: str, shape, params: dict, fmts=()) -> float:
    """Roofline seconds for one launch of `kernel` at `shape` under a
    candidate tiling: padded FLOPs / padded HBM bytes through the chip's
    peaks (`device_peaks`).  Used to prune the sweep — a tile that pads a
    dim 4x does 4x the work, which the oracle sees without timing it."""
    hw = device_peaks()

    def elt_bytes(i):
        f = fmts[i] if i < len(fmts) else None
        return 4 if f is None else _STORE_BYTES[f.storage_bits]

    if kernel in ("posit_codec.decode", "posit_codec.encode"):
        R, C = shape
        rp = _pad_up(R, params["block_r"])
        cp = _pad_up(C, params["block_c"])
        n = rp * cp
        flops = 8 * n  # ~bit-ops per element on the VPU
        in_b = 2 if fmts else 4
        bytes_ = n * (in_b + 4)
    elif kernel in ("posit_matmul", "posit_matmul_grouped"):
        if kernel == "posit_matmul_grouped":
            E, M, K, N = shape
        else:
            E, (M, K, N) = 1, shape
        mp = _pad_up(M, params["bm"])
        kp = _pad_up(K, params["bk"])
        np_ = _pad_up(N, params["bn"])
        flops = 2.0 * E * mp * kp * np_
        n_k = kp // min(params["bk"], K)
        # A tile re-read per N block, B tile re-read per M block, one out
        bytes_ = E * (mp * kp * elt_bytes(0) * (np_ // min(params["bn"], N))
                      + kp * np_ * elt_bytes(1) * (mp // min(params["bm"], M))
                      + mp * np_ * 4)
        del n_k
    elif kernel == "paged_attention":
        B, T, M, ps, F = shape
        tb = min(params["t_block"], T)
        tp = _pad_up(T, tb)
        # every (slot, q-tile) sweep re-reads the slot's pages
        bytes_ = B * (tp // tb) * M * ps * F * elt_bytes(0) * 2
        flops = 4.0 * B * tp * M * ps * F
    elif kernel == "prefill_attention":
        # launch knobs (dimension_semantics / VMEM budget) don't change the
        # computed volume — every candidate shares the roofline estimate and
        # all survive pruning into the wall-clock timing stage.
        B, C, M, ps, F = shape
        S = M * ps + C  # worst case: full history + the chunk itself
        bytes_ = B * (C * F * 4 * 3                  # q/k/v chunk reads
                      + M * ps * F * elt_bytes(0) * 2  # history pages (k+v)
                      + C * F * elt_bytes(0) * 2       # encoded page writes
                      + C * F * 4)                     # attention output
        flops = 4.0 * B * C * S * F
    elif kernel == "decode_sample":
        B, D, V = shape
        vb = params["v_block"] or V  # 0 = whole vocab (collapsed grid)
        vp = _pad_up(V, vb)
        # head weights streamed once; x re-read per vocab tile; noise +
        # logits epilogue at f32
        bytes_ = (D * vp * elt_bytes(0)
                  + (vp // min(vb, V)) * B * D * 4 + 2 * B * vp * 4)
        flops = 2.0 * B * D * vp + 8.0 * B * vp
    else:
        raise KeyError(f"no oracle for kernel '{kernel}'")
    return max(flops / hw["peak_flops_bf16"], bytes_ / hw["hbm_bw"])


def hlo_cost(fn, *args) -> dict:
    """Compile a candidate and account its HLO with launch/hlo_analysis —
    the compiler-side cross-check of `oracle_cost` (CLI --oracle-check)."""
    from repro.launch.hlo_analysis import analyze_hlo
    text = jax.jit(fn).lower(*args).compile().as_text()
    return analyze_hlo(text)


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------

class AutotuneCache:
    """In-memory view of one cache JSON + hit/miss accounting."""

    def __init__(self, backend: str | None = None, entries: dict | None = None):
        self.backend = backend or jax.default_backend()
        self.entries = dict(entries or {})
        self.hits: dict = {}
        self.misses: dict = {}

    # -- persistence ------------------------------------------------------

    @classmethod
    def load(cls, path: str | None = None) -> "AutotuneCache":
        path = path or cache_path()
        backend = jax.default_backend()
        if not os.path.exists(path):
            return cls(backend)
        with open(path) as f:
            raw = json.load(f)
        if raw.get("version") != CACHE_VERSION:
            return cls(backend)  # schema bump invalidates the file wholesale
        return cls(backend, raw.get("entries", {}))

    def save(self, path: str | None = None) -> str:
        path = path or cache_path()
        payload = {"version": CACHE_VERSION, "backend": self.backend,
                   "generated_by": "launch/autotune.py",
                   "entries": self.entries}
        with open(path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        return path

    # -- lookup / insert --------------------------------------------------

    def lookup(self, kernel: str, shape, fmts=()) -> dict | None:
        """Tuned params for (kernel, bucketed shape, formats) or None.
        Records per-kernel hit/miss counts for `hit_report`."""
        key = make_key(shape, fmts)
        ent = self.entries.get(key_digest(kernel, self.backend, key))
        if ent is not None and ent.get("kernel") == kernel:
            self.hits[kernel] = self.hits.get(kernel, 0) + 1
            return dict(ent["params"])
        self.misses[kernel] = self.misses.get(kernel, 0) + 1
        return None

    def put(self, kernel: str, shape, params: dict, fmts=(),
            ms: float | None = None, oracle_ms: float | None = None):
        key = make_key(shape, fmts)
        self.entries[key_digest(kernel, self.backend, key)] = {
            "kernel": kernel, "key": key, "params": dict(params),
            "ms": ms, "oracle_ms": oracle_ms}

    def report(self) -> dict:
        """Per-kernel {hits, misses} since load — what the serving example
        prints so tuned-config coverage is visible at a glance."""
        kernels = sorted(set(self.hits) | set(self.misses))
        return {k: {"hits": self.hits.get(k, 0),
                    "misses": self.misses.get(k, 0)} for k in kernels}


_CACHE: AutotuneCache | None = None


def get_cache() -> AutotuneCache:
    global _CACHE
    if _CACHE is None:
        _CACHE = AutotuneCache.load()
    return _CACHE


def reset_cache(cache: AutotuneCache | None = None):
    """Swap/clear the process-wide cache (tests; CLI after a sweep)."""
    global _CACHE
    _CACHE = cache


def lookup(kernel: str, shape, fmts=()) -> dict | None:
    """Dispatch-time resolution hook (ops.py): tuned params or None.
    Honors REPRO_AUTOTUNE=off."""
    if not _enabled():
        return None
    return get_cache().lookup(kernel, shape, fmts)


def hit_report() -> dict:
    cache = _CACHE
    return cache.report() if cache is not None else {}


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def _time_once(fn, reps: int) -> float:
    jax.block_until_ready(fn())  # warm-up/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e3


def sweep(kernel: str, shape, run, fmts=(), reps: int = 3,
          prune_factor: float = 4.0):
    """Tune one (kernel, shape, formats) point.

    `run(params) -> thunk`: builds a zero-arg callable launching the kernel
    with candidate `params`.  Every candidate is scored by the roofline
    oracle first; only candidates within `prune_factor` x the best oracle
    estimate are wall-clock timed (`reps` reps after a warm-up).  Returns
    (best_params, best_ms, table) with the full candidate table for the
    CLI's report.
    """
    scored = [(oracle_cost(kernel, shape, p, fmts), p)
              for p in candidates(kernel)]
    best_oracle = min(c for c, _ in scored)
    table = []
    best = None
    for cost, params in sorted(scored, key=lambda t: t[0]):
        if cost > prune_factor * best_oracle:
            table.append({"params": params, "oracle_ms": cost * 1e3,
                          "ms": None, "pruned": True})
            continue
        try:
            ms = _time_once(run(params), reps)
        except Exception as e:  # an illegal tiling for this shape
            table.append({"params": params, "oracle_ms": cost * 1e3,
                          "ms": None, "pruned": False, "error": str(e)})
            continue
        table.append({"params": params, "oracle_ms": cost * 1e3,
                      "ms": ms, "pruned": False})
        if best is None or ms < best[1]:
            best = (params, ms, cost * 1e3)
    if best is None:
        raise RuntimeError(f"no timeable candidate for {kernel} @ {shape}")
    return best[0], best[1], table
