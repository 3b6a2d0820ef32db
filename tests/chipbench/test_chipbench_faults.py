"""The check catches a broken timed path: the whole harness runs at a
small size on the CPU (the look for a chip skipped) with a fault planted
under it, and `correct` comes out false under the limit for that size
(chipbench_smoke.SMALL_LIMIT).  A one-chip serving cell can have two of
the faults: a token altered where it is made, and a step that returns
its state unchanged."""
import pytest

import chipbench_smoke as smoke


def _token_altered(engine):
    """Every decoded token replaced by its neighbour where it is made."""
    fn = engine._decode_sample
    V = engine.cfg.vocab_size

    def wrapped(*a):
        toks, cache = fn(*a)
        return (toks + 1) % V, cache
    engine._decode_sample = wrapped


def _state_unchanged(engine):
    """The decode step hands back the cache it was given: no key or value
    of a decoded token is ever stored."""
    fn = engine._decode_sample

    def wrapped(params, tokens, cache, *rest):
        toks, _ = fn(params, tokens, cache, *rest)
        return toks, cache
    engine._decode_sample = wrapped


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_fault_reads_not_correct(fault):
    conf, mix = smoke.small()
    res = smoke.run(seed=5, seconds=2.0, conf=conf, mix=mix, fault=fault,
                    limits=smoke.SMALL_LIMIT)
    assert res["check"]["max_logit_gap"]["value"] > \
        res["check"]["max_logit_gap"]["limit"]
    assert res["correct"] is False


def test_sound_run_reads_correct():
    conf, mix = smoke.small()
    res = smoke.run(seed=5, seconds=2.0, conf=conf, mix=mix,
                    limits=smoke.SMALL_LIMIT)
    assert res["correct"] is True
