"""One generator for every traffic mix: `traffic/<mix>.json` holds only data.

A mix fixes the work and its schedule; the seed fills it in.  For a run
of `seconds`, the number of requests, the prompt and output lengths, the
gaps between arrivals and which requests open with a shared system prompt
come from the mix alone: lengths are the distribution's quantiles at
(i + 1/2)/n, gaps the quantiles of the arrival process, put in an order
drawn once from the mix's `order_seed`.  The run's seed draws the token
ids of every prompt and system prompt (and, elsewhere, the weights and
the correctness sample).  Two seeds therefore ask the same work at the
same moments of different tokens: on the chip, seeds that also reordered
the work spread a run's tails by 10-19% (PERF.md), where two runs of one
seed agree within 2%.

Mix keys:
  arrival   {"kind": "poisson", "rate": r}            open loop, r req/s
  prompt / output
            {"dist": "lognormal", "median": m, "sigma": s, "min": a,
             "max": b}
  order_seed  the order of lengths and gaps, and the prefix assignment
  shared_prefix (optional)
            {"share": f, "count": k, "length": n}     a share f of the
            requests open with a system prompt of n tokens, before their
            own prompt part; each picks one of k system prompts uniformly
            at random (with replacement), so prompts repeat at any load
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent

# independent random streams drawn from one seed
STREAM_ORDER, STREAM_TOKENS, STREAM_CHECK = 1, 2, 3


@dataclasses.dataclass
class Item:
    rid: int
    due: float              # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int
    prefix_id: int = -1     # which system prompt it opens with (-1: none)


def load(mix: str) -> dict:
    return json.loads((HERE / "traffic" / f"{mix}.json").read_text())


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """The n stratified lengths of a length distribution (sorted)."""
    u = _quantiles(n)
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def arrivals(arr: dict, seconds: float, order: np.random.Generator):
    """Due times of one run, sorted, all inside [0, seconds)."""
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    total = arr["rate"] * float(seconds)
    n = max(int(round(total)), 1)
    gaps = -np.log1p(-_quantiles(n))          # unit-rate exponential
    gaps *= total / gaps.sum()
    # the median gap is the one after the last arrival; the rest are put
    # in the mix's order
    inner = order.permutation(np.delete(gaps, n // 2))
    return np.concatenate([[0.0], np.cumsum(inner)]) / arr["rate"]


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             rid0: int = 0) -> List[Item]:
    order = rng(mix["order_seed"], STREAM_ORDER)
    toks = rng(seed, STREAM_TOKENS)
    due = arrivals(mix["arrival"], seconds, order)
    n = len(due)
    plen = order.permutation(lengths(mix["prompt"], n))
    olen = order.permutation(lengths(mix["output"], n))
    prefix_of = np.full(n, -1)
    systems: List[np.ndarray] = []
    sp: Optional[dict] = mix.get("shared_prefix")
    if sp:
        systems = [toks.integers(0, vocab, sp["length"]).astype(np.int32)
                   for _ in range(sp["count"])]
        n_shared = int(round(sp["share"] * n))
        prefix_of[:n_shared] = order.integers(0, sp["count"], n_shared)
        prefix_of = order.permutation(prefix_of)
    items = []
    for i in range(n):
        own = toks.integers(0, vocab, int(plen[i])).astype(np.int32)
        prompt = (np.concatenate([systems[prefix_of[i]], own])
                  if prefix_of[i] >= 0 else own)
        items.append(Item(rid=rid0 + i, due=float(due[i]), prompt=prompt,
                          max_new_tokens=int(olen[i]),
                          prefix_id=int(prefix_of[i])))
    return items


def max_positions(mix: dict) -> int:
    """Positions the longest request of the mix can reach."""
    sp = mix.get("shared_prefix")
    return (mix["prompt"]["max"] + mix["output"]["max"]
            + (sp["length"] if sp else 0))
