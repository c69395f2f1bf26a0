"""The reduction from a profiler trace to busy time, per-program time,
exposed collective time and idle gaps: on made-up intervals, and on a
trace of one ``w50.sim.qkd`` round recorded on one TPU v5e
(``bench/testdata``, xz-compressed)."""
import lzma
import os

import pytest

from conftest import BENCH

from harness import xplane

DEV = "/device:TPU:0"


def made_up() -> xplane.Trace:
    ms = 1_000_000
    tr = xplane.Trace(window=(0, 100 * ms))
    tr.modules[DEV] = [("jit__batched_stage_impl(3)", 10 * ms, 30 * ms),
                       ("jit__secure_stage_impl(4)", 50 * ms, 60 * ms),
                       ("jit__batched_stage_impl(3)", 95 * ms, 120 * ms)]
    tr.ops[DEV] = [("fusion.1", 10 * ms, 20 * ms),
                   ("all-reduce.2", 15 * ms, 28 * ms),
                   ("fusion.3", 50 * ms, 60 * ms),
                   ("fusion.4", 95 * ms, 120 * ms)]
    tr.host = [("bench.window", 0, 100 * ms, 0),
               ("bench.round", 1 * ms, 49 * ms, 1),
               ("evaluate", 31 * ms, 49 * ms, 2)]
    return tr


def test_busy_union_and_window():
    tr = made_up()
    # ops 10-28, 50-60, 95-100 inside the window
    assert xplane.busy_s(tr) == pytest.approx(0.033)
    assert xplane.window_s(tr) == pytest.approx(0.1)


def test_program_seconds_and_executions():
    tr = made_up()
    t = xplane.module_seconds(tr)
    assert t["_batched_stage_impl"] == pytest.approx(0.025)
    assert t["_secure_stage_impl"] == pytest.approx(0.010)
    assert xplane.executions(tr) == 3


def test_exposed_collective():
    # all-reduce 15-28 overlaps fusion.1 on 15-20: 8 ms exposed
    assert xplane.exposed_collective_s(made_up()) == pytest.approx(0.008)


def test_idle_gaps_named_by_host_span():
    gaps = xplane.idle_gaps(made_up())
    assert gaps[0] == ["bench.window > bench.window", pytest.approx(0.035)]
    assert gaps[1] == ["bench.round > evaluate", pytest.approx(0.022)]
    assert sum(g[1] for g in gaps) == pytest.approx(0.1 - 0.033)
    assert len(gaps) == len(xplane.gaps(made_up())) == 3


def test_trace_recorded_on_chip(tmp_path):
    src = os.path.join(BENCH, "testdata", "w50_one_round.xplane.pb.xz")
    path = tmp_path / "one_round.xplane.pb"
    with lzma.open(src) as f:
        path.write_bytes(f.read())
    tr = xplane.load(str(path))
    assert tr.modules, "a TPU device plane with an XLA Modules line"
    busy, window = xplane.busy_s(tr), xplane.window_s(tr)
    assert 0 < busy < window
    progs = xplane.module_seconds(tr)
    # one round: local training holds the device most of its busy time
    assert progs["_batched_stage_impl"] > 0.5 * busy
    assert {"_secure_stage_impl", "_dev_eval_impl"} <= set(progs)
    assert xplane.executions(tr) == 456
    assert xplane.exposed_collective_s(tr) == 0.0
    named = xplane.idle_gaps(tr)
    assert named and all(g[0].startswith("bench.") for g in named)
    assert sum(e - s for s, e in xplane.gaps(tr)) / 1e9 == \
        pytest.approx(window - busy, rel=1e-6)
