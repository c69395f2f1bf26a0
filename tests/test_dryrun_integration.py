"""Integration: the multi-pod dry-run lowers + compiles real combos in a
subprocess (dryrun.py owns the 512-device XLA flag; this process keeps 1).
Small/fast archs only — the full 78-combo sweep runs via
``python -m repro.launch.dryrun --all`` (results in results/)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=420):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


@pytest.mark.parametrize("arch,shape", [
    ("whisper-tiny", "decode_32k"),
    ("mamba2-130m", "long_500k"),
])
def test_dryrun_single_pod(arch, shape, tmp_path):
    out = tmp_path / "rec.json"
    r = _run(["--arch", arch, "--shape", shape, "--mesh", "single",
              "--out", str(out)])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.loads(out.read_text())["records"][0]
    assert rec["fits_hbm"]
    assert rec["n_chips"] == 256
    assert rec["compute_s"] >= 0 and rec["memory_s"] > 0
    assert rec["dominant"] in ("compute", "memory", "collective")


def test_dryrun_multi_pod(tmp_path):
    out = tmp_path / "rec.json"
    r = _run(["--arch", "whisper-tiny", "--shape", "train_4k",
              "--mesh", "multi", "--out", str(out)])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.loads(out.read_text())["records"][0]
    assert rec["n_chips"] == 512
    assert rec["mesh"] == "multi"
    # the pod axis actually shards: per-chip analytic memory halves vs
    # single would be ideal to assert, but at minimum it must fit + lower
    assert rec["fits_hbm"]


def test_full_sweep_results_if_present():
    """Validate the committed sweep artifact covers every combination."""
    path = os.path.join(REPO, "results", "dryrun_all.json")
    if not os.path.exists(path):
        pytest.skip("sweep artifact not present")
    data = json.load(open(path))
    assert not data["failures"], data["failures"]
    combos = {(r["arch"], r["shape"], r["mesh"]) for r in data["records"]}
    # 10 archs x 4 shapes - whisper long_500k = 39 pairs x 2 meshes
    assert len(combos) == 78
    assert all(r["fits_hbm"] for r in data["records"])
