"""Fixtures of the benchmark's own tests: a tiny copy of the cells on the
CPU, built from the real configuration and traffic files."""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_TRAFFIC = ("sim.qkd", "sim.pshift")
# a mix no cell runs yet; its reference path is kept for the async cell
TINY_ASYNC = {"engine": "host", "why": "test size",
              "fl": {"mode": "async", "security": "qkd",
                     "agg_security": "secagg", "max_staleness": 3},
              "plan_rounds": 16, "steps_per_round": 36, "warmup_rounds": 4,
              "trace_seconds": 1,
              "limits": {"loss": 1e-05, "update1": 0.0002, "change3": 0.0002}}


def write_tiny_root(root, n_sats: int = 20, full_vqc: bool = False):
    """A checkout-shaped directory whose cells are the real traffic mixes
    on a small configuration: a 4-qubit, 1-layer VQC (with ``full_vqc``
    the configuration's own) on ``n_sats`` satellites in 2 planes (20 is
    the fewest at which secondaries have ISLs to mains, so groups merge),
    2 local steps of 8 rows, 16 rounds."""
    bench = root / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "workloads").mkdir()
    (bench / "metrics").symlink_to(os.path.join(BENCH, "metrics"))
    with open(os.path.join(BENCH, "configs", "vqc8x3-walker50.json")) as f:
        c = json.load(f)
    c["name"] = "tiny"
    c["constellation"].update(n_sats=n_sats, n_planes=2)
    if not full_vqc:
        c["model"].update(vqc_qubits=4, vqc_layers=1, n_features=4,
                          n_classes=3)
    c["data"]["n_samples"] = 600
    c["fl"].update(local_steps=2, batch_size=8)
    (bench / "configs" / "tiny.json").write_text(json.dumps(c))
    for t in TINY_TRAFFIC:
        with open(os.path.join(BENCH, "workloads", f"{t}.json")) as f:
            d = json.load(f)
        d.update(plan_rounds=16, trace_seconds=1)
        (bench / "workloads" / f"{t}.json").write_text(json.dumps(d))
    (bench / "workloads" / "async.secagg.json").write_text(
        json.dumps(TINY_ASYNC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"] = [{"name": "tiny", "source": c["source"],
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test size"}]
    b["workloads"] = [{"name": t, "config": "tiny", "traffic": t,
                       "chips": 1, "why": "test size"}
                      for t in TINY_TRAFFIC + ("async.secagg",)]
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return write_tiny_root(tmp_path_factory.mktemp("tiny"))


def run_cell(root, workload: str, seed: int = 2147483901,
             seconds: float = 0.5, trace: int = 0) -> dict:
    """``bench/run.py`` on the CPU, without its look for a chip; the
    result line as a dict."""
    import io
    from contextlib import redirect_stdout

    import run
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      require_tpu=False, use_cache=False, root=str(root))
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])
