"""Substrate tests: optimizers, data determinism, checkpoint atomicity +
elastic restore, fault-tolerance control plane, hw cost model."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core.formats import PDPUConfig, P13_2, P16_2
from repro.core import hwmodel
from repro.data import DataConfig, Pipeline
from repro.models.config import ShapeConfig
from repro.optim import adamw, adafactor, sgdm, cosine_schedule, constant_schedule
from repro.runtime import (HeartbeatConfig, HeartbeatMonitor, NaNGuard,
                           StragglerDetector, plan_rescale)
from repro import configs


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("maker", [
    lambda: adamw(constant_schedule(0.05)),
    lambda: adafactor(constant_schedule(0.5)),
    lambda: sgdm(constant_schedule(0.05)),
], ids=["adamw", "adafactor", "sgdm"])
def test_optimizer_minimizes_quadratic(maker):
    opt = maker()
    params = {"w": jnp.asarray(np.linspace(-2, 2, 12).reshape(3, 4),
                               jnp.float32)}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(jnp.square(p["w"] - 1.0))

    l0 = float(loss(params))
    for _ in range(120):
        g = jax.grad(loss)(params)
        upd, state = opt.update(g, state, params)
        params = jax.tree.map(jnp.add, params, upd)
    assert float(loss(params)) < 0.05 * l0


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup=10, total=100, final_frac=0.1)
    assert float(lr(jnp.asarray(0))) < 0.2
    assert abs(float(lr(jnp.asarray(10))) - 1.0) < 0.15
    assert float(lr(jnp.asarray(99))) < 0.2


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_determinism_and_sharding():
    cfg = configs.get_smoke("minitron_8b")
    shape = ShapeConfig("t", 32, 8, "train")
    p = Pipeline(cfg, shape, DataConfig(seed=3))
    b1, b2 = p.batch_at(7), p.batch_at(7)
    assert (b1["tokens"] == b2["tokens"]).all()
    assert (p.batch_at(8)["tokens"] != b1["tokens"]).any()
    # two hosts each produce their slice; contents differ but shapes halve
    pa = Pipeline(cfg, shape, DataConfig(seed=3, host_index=0, host_count=2))
    pb = Pipeline(cfg, shape, DataConfig(seed=3, host_index=1, host_count=2))
    assert pa.batch_at(0)["tokens"].shape[0] == 4
    assert (pa.batch_at(0)["tokens"] != pb.batch_at(0)["tokens"]).any()


def test_data_prefetch_iterator():
    cfg = configs.get_smoke("minitron_8b")
    p = Pipeline(cfg, ShapeConfig("t", 16, 4, "train"))
    it = p.iterator(start_step=5)
    first = next(it)
    assert (first["tokens"] == p.batch_at(5)["tokens"]).all()


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _tree(seed):
    r = np.random.default_rng(seed)
    return {"a": jnp.asarray(r.normal(size=(4, 8)), jnp.float32),
            "nested": {"b": jnp.asarray(r.integers(0, 9, (3,)), jnp.int32)}}


def test_checkpoint_roundtrip_and_retention():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep_last=2)
        trees = {}
        for s in (1, 2, 3, 4):
            trees[s] = _tree(s)
            mgr.save(s, trees[s])
        assert mgr.all_steps() == [3, 4]  # retention
        got = mgr.restore(4, jax.tree.map(lambda x: x, trees[4]))
        assert all((np.asarray(a) == np.asarray(b)).all()
                   for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(trees[4])))


def test_checkpoint_async_and_atomicity():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep_last=5)
        mgr.save_async(1, _tree(1))
        mgr.wait()
        # a torn write (tmp dir) must be invisible to readers
        os.makedirs(os.path.join(d, "step_000000009.tmp-dead"), exist_ok=True)
        assert mgr.all_steps() == [1]
        assert mgr.latest_step() == 1


def test_checkpoint_structure_mismatch_raises():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, _tree(1))
        bad = {"a": jnp.zeros((4, 8)), "nested": {"c": jnp.zeros(3)}}
        with pytest.raises(ValueError):
            mgr.restore(1, bad)


# ---------------------------------------------------------------------------
# fault tolerance control plane
# ---------------------------------------------------------------------------

def test_heartbeat_death_detection():
    cfg = HeartbeatConfig(interval_s=1.0, miss_budget=2)
    mon = HeartbeatMonitor(["h0", "h1"], cfg)
    now = 100.0
    mon.beat("h0", now)
    mon.beat("h1", now)
    assert mon.dead_hosts(now + 1.0) == []
    mon.beat("h0", now + 5.0)
    assert mon.dead_hosts(now + 5.5) == ["h1"]


def test_straggler_detection():
    det = StragglerDetector(HeartbeatConfig(min_steps_for_stats=5))
    for _ in range(20):
        assert not det.observe(1.0 + np.random.default_rng(0).normal(0, 0.01))
    assert det.observe(3.0)  # 3x median


def test_nan_guard_policy():
    g = NaNGuard(max_consecutive=2)
    assert g.observe(1.0) == "ok"
    assert g.observe(float("nan")) == "skip"
    assert g.observe(float("inf")) == "restore"
    assert g.observe(0.5) == "ok"


def test_elastic_rescale_plan():
    plan = plan_rescale(available_hosts=120, chips_per_host=4,
                        restore_step=1000, model_axis=16)
    assert plan.new_mesh_shape == (30, 16)
    assert plan.restore_step == 1000
    with pytest.raises(RuntimeError):
        plan_rescale(available_hosts=1, chips_per_host=4,
                     restore_step=0, model_axis=16)


# ---------------------------------------------------------------------------
# hardware cost model (Table I calibration)
# ---------------------------------------------------------------------------

def test_hwmodel_matches_table1():
    from repro.core.formats import (
        PDPU_P16_16_N4_W14, PDPU_P13_16_N4_W14, PDPU_P13_16_N8_W14,
        PDPU_P10_16_N8_W14, PDPU_P13_16_N8_W10)
    rows = {
        PDPU_P16_16_N4_W14: (9579.15, 1.62, 4.49),
        PDPU_P13_16_N4_W14: (7694.82, 1.60, 3.66),
        PDPU_P13_16_N8_W14: (13560.37, 1.69, 5.80),
        PDPU_P10_16_N8_W14: (10006.42, 1.70, 4.24),
        PDPU_P13_16_N8_W10: (12157.11, 1.66, 5.06),
    }
    for cfg, (area, delay, power) in rows.items():
        r = hwmodel.report(cfg)
        assert abs(r.area_um2 / area - 1) < 0.12, cfg.name
        assert abs(r.delay_ns / delay - 1) < 0.05, cfg.name
        assert abs(r.power_mw / power - 1) < 0.20, cfg.name


def test_hwmodel_trends():
    """Generator monotonicity: bigger N / wider w_m cost more area."""
    base = PDPUConfig(P13_2, P16_2, N=4, w_m=14)
    assert hwmodel.area_um2(PDPUConfig(P13_2, P16_2, N=8, w_m=14)) > \
        hwmodel.area_um2(base)
    assert hwmodel.area_um2(PDPUConfig(P13_2, P16_2, N=4, w_m=24)) > \
        hwmodel.area_um2(base)
    r = hwmodel.report(base)
    # 6-stage pipeline: balanced-ish stages, >3x throughput vs combinational
    assert r.delay_ns / max(r.stage_delay_ns) > 3.0
    # decoders (S1) dominate area (paper §IV-B)
    assert r.stage_area_um2[0] == max(r.stage_area_um2)


def test_peaks_keyed_by_device_kind():
    """A known chip gets its published peaks; an unknown one raises rather
    than inherit the v5e's."""
    from repro.launch.mesh import V5E, peaks
    hw = peaks(V5E)
    assert hw["peak_flops_bf16"] == 197e12 and hw["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v9 imaginary"])
def test_oracle_prices_the_attached_chip(kind, monkeypatch):
    """On a TPU the autotune oracle takes the attached chip's own peaks,
    by its device kind; a kind without published peaks raises."""
    from repro.kernels import autotune, ops
    from repro.launch.mesh import PEAKS

    class Chip:
        device_kind = kind

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    if kind in PEAKS:
        assert autotune.device_peaks() is PEAKS[kind]
    else:
        with pytest.raises(KeyError, match="no published peaks"):
            autotune.oracle_cost("decode_sample", (4, 64, 512),
                                 {"v_block": 128})


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "default"])
def test_compile_cache_dir(env_set, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes to
    the fixed <repo>/.jax_cache."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if env_set:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
