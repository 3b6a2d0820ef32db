"""The readers of the serving program's counters on hand-built runs, and
the reduction by the program's own names (chipbench/program_trace.py) on
the committed traces: `short_tiny` (recorded before the program had
spans or program names) and `short_tiny_spans` (a run of the same shape
recorded with them on one TPU v5e chip:

    python3 chipbench/readings.py --workload internlm2-1.8b.short \
        --seeds 2147485100 --seconds 1 --rates 2 \
        --engine '{"batch_slots": 4}' \
        --mix '{"prompt": {"median": 72, "min": 64, "max": 80},
                "output": {"median": 4, "min": 4, "max": 4}}' \
        --trace --keep-trace <dir> --out <file>

then `xz -9e`)."""
import lzma
from pathlib import Path

import pytest

from chipbench import program_trace, trace
from chipbench import run as harness

DATA = Path(__file__).parent / "data"
OLD = DATA / "short_tiny.xplane.pb.xz"
NEW = DATA / "short_tiny_spans.xplane.pb.xz"
ENGINE = {"batch_slots": 4}


def _run(c0, c1):
    return harness.Run(
        conf={}, mix={"engine": ENGINE}, cell="internlm2-1.8b.short",
        chips=1, seconds=1.0, device_kind="TPU v5 lite", items=[], due={},
        submitted={}, times={}, t0=0.0, t1=1.0, t_end=1.0, setup_s=1.0,
        counters0=c0, counters1=c1, page_size=16, memory_peak_bytes=None)


ZERO = {"admissions": 0, "queue_wait_s": 0.0, "first_tokens": 0,
        "admit_to_first_token_s": 0.0, "decode_steps": 0, "decode_rows": 0,
        "prefill_live_tokens": 0, "prefill_row_tokens": 0}
# 4 admissions waiting 0.6 s in all, their first tokens 2 s after; 10
# decode steps of 4 slots carrying 30 rows; chunk programs of 4 rows
# carrying 160 of 640 tokens (counters of the run, over what was there)
LATER = {"admissions": 5, "queue_wait_s": 0.7, "first_tokens": 6,
         "admit_to_first_token_s": 2.5, "decode_steps": 12,
         "decode_rows": 31, "prefill_live_tokens": 170,
         "prefill_row_tokens": 680}
BEFORE = {"admissions": 1, "queue_wait_s": 0.1, "first_tokens": 2,
          "admit_to_first_token_s": 0.5, "decode_steps": 2,
          "decode_rows": 1, "prefill_live_tokens": 10,
          "prefill_row_tokens": 40}
WANT = {"queue_wait_mean_ms": 150.0, "admit_to_first_token_mean_ms": 500.0,
        "decode_slot_use": 75.0, "prefill_row_use": 25.0}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_counter_reader_by_hand(metric):
    read = harness.load_reader(metric)
    assert read(_run(BEFORE, LATER)) == pytest.approx(WANT[metric])
    # nothing counted in the run: nothing to report
    assert read(_run(ZERO, ZERO)) is None


@pytest.mark.parametrize("metric", sorted(WANT))
def test_counter_reader_silent_on_a_program_without_counters(metric):
    old = {"decode_steps": 3, "pages_shared": 0}
    assert harness.load_reader(metric)(_run(old, dict(old))) is None


def _load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(lzma.decompress(
        path.read_bytes()))


@pytest.fixture(scope="module")
def old():
    pd = _load(OLD)
    return trace.reduce(pd), program_trace.reduce_program(pd)


def test_old_trace_reduced_as_before(old):
    reduced, program = old
    # trace.reduce reads the harness's spans only, as it did
    assert set(reduced) == {"window_s", "busy_s", "busy_s_per_device",
                            "op_seconds", "device_ops", "idle_gaps"}
    assert reduced["window_s"] == pytest.approx(0.642500038)
    assert reduced["busy_s"] == pytest.approx(0.604023247)
    # no program span in it: every idle second lies outside them
    idle = reduced["window_s"] - reduced["busy_s"]
    assert list(program["program_idle"]) == [program_trace.OUTSIDE]
    assert program["program_idle"][program_trace.OUTSIDE] == \
        pytest.approx(idle, rel=1e-6)
    assert program["program_idle_inclusive"] == {}
    # 23 programs ran, the unnamed lambdas among them
    assert sum(program["module_runs"].values()) == 23
    assert program["module_runs"]["jit__lambda"] == 9
    assert program["module_runs"]["jit_convert_element_type"] == 10


def test_module_names_and_nesting():
    assert program_trace.module_name(
        "jit_serve_decode_sample(8695725819138592199)") == \
        "jit_serve_decode_sample"
    spans = [("serve.step", 0, 10), ("serve.admit", 1, 2),
             ("serve.token_wait", 5, 8), ("serve.step", 12, 20)]
    assert program_trace.open_spans(spans, [0.5, 1.5, 3, 6, 11, 13]) == [
        ["serve.step"], ["serve.step", "serve.admit"], ["serve.step"],
        ["serve.step", "serve.token_wait"], [], ["serve.step"]]


@pytest.fixture(scope="module")
def new():
    pd = _load(NEW)
    return trace.reduce(pd), program_trace.reduce_program(pd)


def test_program_idle_sums_to_window_less_busy(new):
    reduced, program = new
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(program["program_idle"].values()) == pytest.approx(idle,
                                                                  rel=1e-6)
    # the harness's naming is untouched by the program's spans
    assert all(name.startswith(trace.SPAN_PREFIX)
               for name, _ in reduced["idle_gaps"])
    assert sum(s for _, s in reduced["idle_gaps"]) == pytest.approx(
        idle, rel=1e-6)


def test_programs_by_name(new, old):
    _, program = new
    secs, runs = program["module_seconds"], program["module_runs"]
    for name in ("jit_serve_prefill_chunk_batched",
                 "jit_serve_decode_sample"):
        assert secs[name] > 0, name
    assert not any("lambda" in name for name in runs)
    # two requests, as in the old trace: 3 chunk programs and 6 decode
    # steps, and as many program runs as before the names
    assert runs["jit_serve_prefill_chunk_batched"] == 3
    assert runs["jit_serve_decode_sample"] == 6
    assert sum(runs.values()) == sum(old[1]["module_runs"].values()) == 23


def test_idle_inside_steps_named_by_their_children(new):
    _, program = new
    inside = program["program_idle_inclusive"][program_trace.STEP_SPAN]
    assert inside > 0
    assert program["program_idle"].get(program_trace.STEP_SPAN, 0.0) \
        <= 0.2 * inside
    children = sum(v for k, v in program["program_idle"].items()
                   if k.startswith("serve.") and k != program_trace.STEP_SPAN
                   and not k.startswith("serve.frontend."))
    assert children == pytest.approx(
        inside - program["program_idle"].get(program_trace.STEP_SPAN, 0.0),
        rel=1e-6)
