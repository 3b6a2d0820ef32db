"""BENCHMARK.json against the shape the benchmark's contract requires, every
name found as a file, and the command refusing to run without a TPU."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path


ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_name_is_a_file():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert (ROOT / "chipbench" / "configs" / f"{c['name']}.py").is_file()
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
        assert (ROOT / "chipbench" / "traffic"
                / f"{w['traffic']}.json").is_file()
        assert (ROOT / "chipbench" / "limits" / f"{w['name']}.json").is_file()
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["workloads"]


def test_every_cell_reports_enough():
    sys.path.insert(0, str(ROOT))
    from chipbench import run as harness
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(BENCH, w["name"],
                                                       False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(BENCH, w["name"], True)


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
