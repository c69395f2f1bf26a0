"""The harness finds cells by name and refuses to run without a chip."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

from harness import spec


def test_workload_file_found_by_name(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = json.loads((tmp_path / "bench" / "workloads" /
                          "sim.qkd.json").read_text())
    traffic["plan_rounds"] = 77
    (tmp_path / "bench" / "workloads" / "sim.later.json").write_text(
        json.dumps(traffic))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "w50.sim.later",
                               "config": "vqc8x3-walker50",
                               "traffic": "sim.later", "chips": 1,
                               "why": "a cell a later change adds"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("w50.sim.later", tmp_path)
    assert cell.traffic["plan_rounds"] == 77
    assert cell.config["constellation"]["n_sats"] == 50
    assert {m["name"] for m in cell.end_to_end} == {"round_ms", "setup_s"}
    assert spec.load_reader("round_ms", tmp_path)(
        type("C", (), {"window_s": 2.0, "completed": 4})) == 500.0


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "w50.sim.qkd",
         "--seed", "2147483901", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout
