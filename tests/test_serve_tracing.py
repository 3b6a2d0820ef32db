"""The serving engine's own spans, counters and program names.

  * counters: `admissions` counts the requests served, `decode_rows` the
    tokens decoded after each first token, `prefill_row_tokens` every
    chunk program's rows x chunk size, and the two waits are sums of
    non-negative intervals;
  * spans: a CPU profiler trace of a tiny run through the front end holds
    every `serve.` span, each engine span inside a `serve.step`; without
    a profiler a span is the shared falsy no-op;
  * names: every jitted program of the engine lowers to `jit_serve_...`.
"""
import asyncio
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program_trace
from repro import configs
from repro.core.formats import P8_2, P16_2
from repro.core.quant import QuantPolicy
from repro.models import api
from repro.serve import AsyncServingFrontend, Request, ServingEngine
from repro.serve import tracing

ENGINE_SPANS = {"serve.admit", "serve.prefill_chunk",
                "serve.first_token_wait", "serve.refresh_meta",
                "serve.decode_dispatch", "serve.token_wait", "serve.retire"}
FRONTEND_SPANS = {"serve.frontend.expire", "serve.frontend.preempt",
                  "serve.frontend.pump"}


def _model():
    if not hasattr(_model, "cache"):
        cfg = configs.get_tiny_serving(
            "command_r_35b", QuantPolicy(weights=P16_2, kv_cache=P8_2))
        _model.cache = (cfg, api.init(jax.random.key(0), cfg))
    return _model.cache


def _engine(**kw):
    cfg, params = _model()
    args = dict(batch_slots=2, max_seq=32, page_size=4, n_pages=24,
                prefill_buckets=(4, 1))
    args.update(kw)
    return ServingEngine(cfg, params, **args)


def _prompts(ns, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 60, n).astype(np.int32) for n in ns]


@pytest.fixture(scope="module")
def eng():
    """One engine for the module, so its programs compile once; each test
    reads the counters it moved."""
    return _engine(prefix_sharing=False)


def _moved(eng, before):
    return {k: v - before[k] for k, v in eng.stats.items()
            if isinstance(v, (int, float))}


def test_counter_identities(eng):
    before = dict(eng.stats)
    sizes = []
    chunk = eng._chunk_batched

    def logged(params, tokens, *rest):
        sizes.append(tokens.shape[1])
        return chunk(params, tokens, *rest)
    eng._chunk_batched = logged
    try:
        prompts = _prompts((5, 9, 7, 3))
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=3 + i))
        done = eng.run()
    finally:
        eng._chunk_batched = chunk
    st = _moved(eng, before)
    assert len(done) == st["admissions"] == st["first_tokens"] == 4
    assert st["decode_rows"] == sum(len(r.out_tokens) - 1 for r in done)
    assert st["prefill_row_tokens"] == eng.B * sum(sizes)
    assert st["prefill_live_tokens"] == sum(len(p) for p in prompts)
    assert st["queue_wait_s"] >= 0 and st["admit_to_first_token_s"] > 0
    eng.done.clear()


def test_summary_means_and_ratios(eng):
    eng.submit(Request(rid=9, prompt=_prompts((6,))[0], max_new_tokens=4))
    eng.run()
    st, s = eng.stats, eng.execution_summary()
    assert s["decode_slot_use"] == pytest.approx(
        st["decode_rows"] / (st["decode_steps"] * eng.B))
    assert s["prefill_row_use"] == pytest.approx(
        st["prefill_live_tokens"] / st["prefill_row_tokens"])
    assert s["queue_wait_mean_ms"] == pytest.approx(
        1e3 * st["queue_wait_s"] / st["admissions"])
    assert s["admit_to_first_token_mean_ms"] == pytest.approx(
        1e3 * st["admit_to_first_token_s"] / st["first_tokens"])
    eng.done.clear()


def test_summary_ratios_none_before_any_event():
    s = _engine().execution_summary()
    assert s["admissions"] == 0
    assert all(s[k] is None for k in (
        "queue_wait_mean_ms", "admit_to_first_token_mean_ms",
        "prefill_row_use", "decode_slot_use"))


def test_preemption_requeues_and_readmits(eng):
    before = dict(eng.stats)
    eng.submit(Request(rid=0, prompt=_prompts((6,))[0], max_new_tokens=6))
    eng.step()
    eng.step()
    eng.preempt(0)
    eng.run()
    # a preempted request waits in the queue and is admitted again
    st = _moved(eng, before)
    assert st["admissions"] == st["first_tokens"] == 2
    eng.done.clear()


def test_span_is_a_falsy_no_op_without_a_profiler():
    sp = tracing.span("serve.step")
    assert sp is tracing._OFF and not sp
    with sp as inner:
        assert inner is sp


def _trace_events(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(glob.glob(f"{path}/**/*.xplane.pb",
                                         recursive=True)[0])
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns),
             dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(tracing.PREFIX)]


def test_spans_present_and_nested_in_a_cpu_trace(eng, tmp_path):
    fe = AsyncServingFrontend(eng)
    tickets = [fe.submit(p, max_new_tokens=3, rid=100 + i)
               for i, p in enumerate(_prompts((5, 9, 7)))]

    async def serve():
        await fe.run()
        return [await t.wait() for t in tickets]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # spans only: a fast trace
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        asyncio.run(serve())
    finally:
        jax.profiler.stop_trace()
    events = _trace_events(tmp_path)
    names = {e[0] for e in events}
    assert ENGINE_SPANS | FRONTEND_SPANS | {"serve.step"} <= names
    steps = [e for e in events if e[0] == "serve.step"]
    for name, a, b, _ in events:
        if name in ENGINE_SPANS:
            assert any(sa <= a and b <= sb for _, sa, sb, _ in steps), name
    chunks = [st for name, _, _, st in events
              if name == "serve.prefill_chunk"]
    assert all(st["size"] in (4, 1) and 1 <= st["rows"] <= 2
               for st in chunks)
    admitted = " ".join(str(st["rid"]) for name, _, _, st in events
                        if name == "serve.admit" and "rid" in st).split()
    assert sorted(map(int, admitted)) == [t.rid for t in tickets]
    # the reduction's sweep finds each engine span inside its step
    spans = sorted(((n, a, b) for n, a, b, _ in events),
                   key=lambda s: (s[1], -s[2]))
    mids = sorted((a + b) / 2 for n, a, b, _ in events
                  if n == "serve.token_wait")
    for stack in program_trace.open_spans(spans, mids):
        assert stack[-2:] == ["serve.step", "serve.token_wait"]


@pytest.mark.parametrize("fused_decode", [True, False],
                         ids=["fused", "decomposed"])
def test_programs_carry_stable_names(fused_decode):
    eng = _engine(fused_decode=fused_decode)
    decode = eng._decode_sample if fused_decode else eng._decode
    assert decode.__name__ == ("serve_decode_sample" if fused_decode
                               else "serve_decode")
    for fn, name in ((eng._chunk, "serve_prefill_chunk"),
                     (eng._chunk_batched, "serve_prefill_chunk_batched"),
                     (eng._fork_fn, "serve_fork_page"),
                     (eng._sampler, "serve_sample"),
                     (eng._prefill, "serve_prefill")):
        assert fn.__name__ == name
    text = eng._fork_fn.lower(eng.cache["k"], 1, 2).as_text()
    assert "module @jit_serve_fork_page" in text
    args = (eng.params, jnp.zeros(eng.B, jnp.int32), eng.cache)
    if fused_decode:
        args += (eng._dummy_keys, jnp.float32(1.0))
    assert f"module @jit_{decode.__name__}" in decode.lower(*args).as_text()


def test_speculative_programs_carry_stable_names():
    eng = _engine(speculate_k=3)
    assert eng._draft_decode.__name__ == "serve_draft_decode"
    assert eng._verify.__name__ == "serve_verify"
