"""Smoke-size copies of the benchmark's configuration and traffic, for the
CPU tests: the same files with the widths cut, so the harness, the
reference and the check run end to end in seconds (Pallas kernels in
interpret mode)."""
from __future__ import annotations

import copy
import time

import jax

from chipbench import run as harness, traffic

CELL = "internlm2-1.8b.shared"


def dense_conf(d_model=64, layers=2, heads=4, kv_heads=2, d_ff=128,
               vocab=97, dtype="bfloat16"):
    conf = copy.deepcopy(harness.load_config("internlm2-1.8b"))
    conf["published"].update(
        hidden_size=d_model, intermediate_size=d_ff,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=kv_heads, vocab_size=vocab)
    conf["model"].update(
        n_layers=layers, d_model=d_model, n_heads=heads, n_kv_heads=kv_heads,
        head_dim=d_model // heads, d_ff=d_ff, vocab_size=vocab, dtype=dtype)
    return conf


def shared_mix(rate=3.0, sample_tokens=12):
    mix = copy.deepcopy(traffic.load("shared"))
    mix["arrival"]["rate"] = rate
    mix["prompt"].update(median=20, min=4, max=40)
    mix["output"].update(median=4, sigma=0.5, min=2, max=8)
    mix["shared_prefix"].update(count=2, length=16)
    mix["engine"].update(batch_slots=4, max_seq=64,
                         prefill_buckets=[16, 4, 1],
                         prefill_chunks_per_step=1)
    mix["check"]["sample_tokens"] = sample_tokens
    return mix


# At this size (2 layers, d_model 128, vocabulary 256, every finished
# request compared) sound runs read a widest logit gap of 0 to 0.027 and
# the float8 control 0.040 to 0.198 over seeds 1-6 (CPU); SMALL_LIMIT
# lies between them on every seed but 2, whose control reads 0.040.  The
# cell's own limit is set from chip readings at its own size
# (chipbench/limits/).
SMALL_LIMIT = {"max_logit_gap": 0.05}


def small(dtype="bfloat16"):
    """A size at which the control and the faults separate from sound
    runs, with outputs long enough for decode to carry state."""
    conf = dense_conf(d_model=128, layers=2, heads=4, kv_heads=2, d_ff=256,
                      vocab=256, dtype=dtype)
    mix = shared_mix(rate=4.0, sample_tokens=1000)
    mix["output"].update(median=8, min=4, max=16)
    mix["engine"]["max_seq"] = 80
    return conf, mix


def run(seed=2**31 + 77, seconds=2.0, conf=None, mix=None, **kw):
    bench = harness.load_bench()
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    return harness.run_cell(
        bench, cell, seed, seconds, False, jax.devices()[:1],
        t_start=time.perf_counter(), conf=conf or dense_conf(),
        mix=mix or shared_mix(), log=lambda *a: None, **kw)
