"""How `correct` is decided: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and holding the longest one,
is run through the configuration's float32 reference, teacher-forced on
the prompt and the served tokens.  At each served position the gap is
how far the served token's reference logit lies below the reference's
best logit; the number compared is the widest gap over the sample.
Serving is greedy, so a served token is the program's own argmax.

The control puts the reference, with every activation entering a matrix
product rounded to float8_e4m3fn, in the program's place: at the same
positions it takes that forward's argmax and reads the gap of that token.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List

import numpy as np

from chipbench import traffic

HERE = Path(__file__).resolve().parent
CONTROL_ACT = "float8_e4m3fn"


def load_limits(cell: str) -> dict:
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())


def load_reference(config: str):
    path = HERE / "configs" / f"{config}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_" + config.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def chunk_groups(prompt_len: int, buckets) -> np.ndarray:
    """Chunk id of each prompt position under the greedy decomposition
    of the prompt into bucket sizes, largest first (the serving engine's
    documented chunking)."""
    out, gid, n = [], 0, prompt_len
    for b in sorted(buckets, reverse=True):
        while n >= b:
            out += [gid] * b
            gid += 1
            n -= b
    return np.asarray(out, np.int32)


MIN_REQUESTS = 3     # the sample holds at least this many requests


def sample(done: List[traffic.Item], seed: int, tokens: int):
    """The longest finished request, then others in an order drawn from
    the seed, until the sample holds `tokens` served tokens and at least
    MIN_REQUESTS requests (or every finished one)."""
    if not done:
        return []
    order = sorted(done, key=lambda it: -(len(it.prompt)
                                          + it.max_new_tokens))
    rest = order[1:]
    rng = traffic.rng(seed, traffic.STREAM_CHECK)
    picked = [order[0]] + [rest[i] for i in rng.permutation(len(rest))]
    out, total = [], 0
    for it in picked:
        out.append(it)
        total += it.max_new_tokens
        if total >= tokens and len(out) >= MIN_REQUESTS:
            break
    return out


def gaps(ref, weights, conf, item, served, buckets, control=False):
    """Per served position: (gap of the served token, gap of the control's
    token or None)."""
    prompt = np.asarray(item.prompt, np.int32)
    served = np.asarray(served, np.int32)
    P, m = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]])
    groups = np.concatenate([chunk_groups(P, buckets),
                             np.full(m - 1, -1, np.int32)])
    out_pos = np.arange(P - 1, P - 1 + m)
    ref_logits = ref.logits(weights, conf, seq, groups, out_pos)
    best = ref_logits.max(axis=-1)
    g = best - ref_logits[np.arange(m), served]
    gc = None
    if control:
        low = ref.logits(weights, conf, seq, groups, out_pos,
                         act=CONTROL_ACT)
        pick = low.argmax(axis=-1)
        gc = best - ref_logits[np.arange(m), pick]
    return g, gc


def readings(conf, weights, done, served: Dict[int, list], seed: int,
             mix: dict, control: bool = False) -> dict:
    """Widest gaps over the seed's sample of finished requests, the
    program's and, with `control`, the control's."""
    ref = load_reference(conf["name"])
    buckets = mix["engine"]["prefill_buckets"]
    picked = sample(done, seed, mix["check"]["sample_tokens"])
    widest, widest_c, n_tok = 0.0, 0.0, 0
    for it in picked:
        g, gc = gaps(ref, weights, conf, it, served[it.rid], buckets,
                     control)
        widest = max(widest, float(g.max()))
        n_tok += len(g)
        if gc is not None:
            widest_c = max(widest_c, float(gc.max()))
    out = {"max_logit_gap": widest, "sampled_requests": len(picked),
           "sampled_tokens": n_tok}
    if control:
        out["control_max_logit_gap"] = widest_c
    return out
