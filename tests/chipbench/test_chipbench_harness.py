"""BENCHMARK.json resolves, keeps to its naming rules, and the command
refuses to run without the chips a cell asks for."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import result, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = spec.resolve(name)
    kind = spec.kind_module(cell.traffic["kind"])
    assert callable(kind.run)
    assert set(cell.limits) >= {"loss_gap", "grad_gap", "change_gap",
                                "feed_mismatch"}
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer


def test_names_units_and_files():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        assert all(spec.NAME_RE.match(n) for n in names), group
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert spec.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert all(spec.NAME_RE.match(k) for k in c["reduced"])
        f = ROOT / c["file"]
        assert f.is_file() and json.loads(f.read_text())["name"] == c["name"]
    for w in BENCH["workloads"]:
        assert spec.NAME_RE.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_bounds_and_window():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= BENCH["run_seconds"] <= 51
    n = 24
    assert (2 + 14 * n) * (BENCH["run_seconds"] + 60) + n * 180 + 1200 \
        <= 43200


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_command_exits_nonzero_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in BENCH["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("traced", [False, True])
def test_last_line_keys(traced):
    out = {"correct": True, "attempted": 3, "failed": 0,
           "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 1},
           "checks": {"loss_gap": {"value": 0.0, "limit": 1.0}}}
    if traced:
        out["breakdown"] = {"device_ops": [], "idle_gaps": []}
    keys = list(json.loads(result.last_line(out)))
    want = ["correct", "attempted", "failed", "metrics", "device"]
    assert keys == want + (["breakdown"] if traced else []) + ["checks"]
