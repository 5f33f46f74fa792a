"""Every cell of BENCHMARK.json, rehearsed through the harness's CPU
switch for a short window: it runs the timed path, checks it correct and
reports each end-to-end metric the cell declares."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from fdnbench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    CELLS = [w["name"] for w in json.load(_fh)["workloads"]]

SHORT = {"warmup_sim_s": 5.0}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct_on_cpu(cell):
    out = harness.run_cell(cell, 2 ** 31 + 11, 0.5, False, cpu=True,
                           mix_override=SHORT)
    r = out.result
    assert r["correct"] is True, r["checks"]
    assert r["rehearsal"] is True and r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"
    assert r["attempted"] > 0
    assert set(r["metrics"]) == {m["name"] for m in out.cell.end_to_end}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert out.window["compiles_in_window"] == 0


def test_no_chip_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "fdnbench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode == 1
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_gives_no_result(tmp_path):
    # BENCHMARK.json and the benchmark's own directories, without the
    # program under test beside them
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in ("fdnbench", os.path.join("tests", "fdnbench")):
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d)
    p = subprocess.run(
        [sys.executable, os.path.join("fdnbench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
