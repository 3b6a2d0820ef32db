"""The plain reference against the serving engine at a small size on the
CPU, through the whole harness (traffic, warm-up, async front end,
window, check), and the control that the check must fail
(chipbench_smoke.SMALL_LIMIT says where the readings lie)."""
import chipbench_smoke as smoke

LIMIT = smoke.SMALL_LIMIT["max_logit_gap"]


def test_engine_agrees_with_reference_and_control_does_not():
    conf, mix = smoke.small()
    res = smoke.run(seed=1, seconds=3.0, conf=conf, mix=mix, control=True,
                    limits=smoke.SMALL_LIMIT)
    assert res["correct"] is True
    assert res["check"]["unfinished_requests"]["value"] == 0
    assert res["check"]["malformed_outputs"]["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["check"]["max_logit_gap"]["value"] <= LIMIT
    # the control, judged by the same check, comes out not correct
    ctl = res["control"]
    assert ctl["correct"] is False
    assert ctl["check"]["max_logit_gap"]["value"] > LIMIT
    assert res["compiles_in_window"] == 0


def test_float32_engine_matches_reference_closely():
    # with float32 activations only the KV rounding order separates them
    conf, mix = smoke.small(dtype="float32")
    res = smoke.run(seed=2, seconds=3.0, conf=conf, mix=mix,
                    limits=smoke.SMALL_LIMIT)
    assert res["check"]["max_logit_gap"]["value"] <= 1e-3
    assert res["correct"] is True


def test_chunk_groups_follow_the_engine_decomposition():
    from chipbench import check
    g = check.chunk_groups(85, [64, 16, 4, 1])
    assert list(g[:64]) == [0] * 64 and list(g[64:80]) == [1] * 16
    assert list(g[80:84]) == [2] * 4 and list(g[84:]) == [3]
