"""The harness finds cells, mixes and metrics by name, refuses to run off
the TPU or without the system under test, and ``BENCHMARK.json`` keeps to
the shape the harness reads."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_new_config_mix_and_metric_files_are_found_by_name(tmp_path):
    """A later PR adds a cell by adding files: nothing existing is edited."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "test_*.py"))
    s = spec()
    cfg = json.loads((tmp_path / "bench/configs/dit-xl2-256.json").read_text())
    (tmp_path / "bench/configs/dit-b2-256.json").write_text(json.dumps(
        dict(cfg, hidden_size=768, depth=12, num_heads=12)))
    (tmp_path / "bench/mixes/pairs.json").write_text(json.dumps(
        {"arrivals": "clients", "clients": 2, "rows": [1, 2], "steps": 20,
         "sampler": "ddim", "check_rows": 2, "plan": {"policy": "act"}}))
    (tmp_path / "bench/metrics/rows_served.py").write_text(
        "def read(run):\n    return float(sum(r.rows for r in run.served))\n")
    s["configs"].append({"name": "dit-b2-256", "source": "https://arxiv.org/abs/2212.09748",
                         "file": "bench/configs/dit-b2-256.json", "reduced": [], "why": "x"})
    s["workloads"].append({"name": "b2.pairs", "config": "dit-b2-256", "traffic": "pairs",
                           "chips": 1, "why": "x"})
    s["end_to_end"].append({"name": "rows_served", "unit": "rows", "better": "higher",
                            "bound": 0.01, "source": "host_clock", "workloads": ["b2.pairs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))

    cell = harness.load_cell("b2.pairs", root=str(tmp_path))
    assert cell.config["hidden_size"] == 768 and cell.mix["clients"] == 2
    assert sorted(m["name"] for m in cell.end_to_end) == ["rows_served", "setup_s"]
    assert cell.per_layer == []  # no per-layer metric lists the new cell
    run = type("R", (), {"served": [type("Q", (), {"rows": 3})()]})()
    assert cell.readers["rows_served"](run) == 3.0
    assert callable(cell.family.sample)
    # the cells that were there are read as before
    old = harness.load_cell("xl2-256.batch", root=str(tmp_path))
    assert "rows_served" not in old.readers and "images_per_s" in old.readers


def test_off_the_tpu_main_exits_nonzero_and_prints_no_result(capsys):
    assert harness.main(["--workload", "xl2-256.batch", "--seed", str(2**33 + 1),
                         "--seconds", "1", "--trace", "0"]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "TPU" in out.err


def test_beside_nothing_but_its_own_files_the_command_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    s = spec()
    proc = subprocess.run(
        [sys.executable, *s["command"][1:], "--workload", "xl2-256.batch", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_the_spec_names_only_files_and_readers_that_exist():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert s["paths"] == ["bench"] and s["command"][1].startswith("bench/")
    configs = {c["name"]: c for c in s["configs"]}
    cells = {w["name"]: w for w in s["workloads"]}
    for c in s["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"])) and c["file"].startswith("bench/")
        assert any(w["config"] == c["name"] for w in s["workloads"])
    for w in s["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(BENCH, "mixes", f"{w['traffic']}.json"))
    metrics = s["end_to_end"] + s["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert os.path.isfile(os.path.join(BENCH, "metrics", f"{m['name']}.py")), m["name"]
        assert set(m.get("workloads", cells)) <= set(cells)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert next(m for m in s["end_to_end"] if m["name"] == "setup_s")["bound"] <= 0.25
    for cell in cells:
        c = harness.load_cell(cell)
        e2e = {m["name"] for m in c.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
        assert all(m["moves"] in e2e for m in c.per_layer)
