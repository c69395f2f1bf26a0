"""A run with the timed path broken underneath comes out not correct, and
so does the control: the reference at the next precision down."""
import io

import jax
import pytest

from conftest import run_cell, write_tiny_root

import control
from harness import check, spec


def test_sound_run_is_correct(tiny_root):
    out = run_cell(tiny_root, "sim.qkd")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def _unchanged(monkeypatch):
    from repro.core.round import SatQFLTrainer
    real = SatQFLTrainer.run_round

    def run_round(self, r):
        before = self.global_params
        m = real(self, r)
        self.global_params = before
        return m
    monkeypatch.setattr(SatQFLTrainer, "run_round", run_round)


def _half_batch(monkeypatch):
    import repro.core.localtrain as lt
    real = lt.sample_local_batches

    def half(data, key, batch_size, n, local_steps):
        b = real(data, key, batch_size, n, local_steps)
        return {k: v[:, :batch_size // 2] for k, v in b.items()}
    monkeypatch.setattr(lt, "sample_local_batches", half)


def _altered_update(monkeypatch):
    from repro.core.round import SatQFLTrainer
    real = SatQFLTrainer._batched_stage_impl

    def stage(self, *a):
        p, o, losses = real(self, *a)
        return jax.tree_util.tree_map(lambda x: x.at[0].add(1e-2), p), o, \
            losses
    monkeypatch.setattr(SatQFLTrainer, "_batched_stage_impl", stage)


@pytest.mark.parametrize("plant", [_unchanged, _half_batch, _altered_update],
                         ids=["state_unchanged", "half_batch",
                              "update_altered"])
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, plant):
    plant(monkeypatch)
    out = run_cell(tiny_root, "sim.qkd", seed=11)
    assert not out["correct"], out["checks"]


def test_control_is_not_correct(tmp_path):
    # the configuration's own 8-qubit, 3-layer VQC: at 4 qubits and one
    # layer three bfloat16 passes lose too little to stand for the cell
    root = write_tiny_root(tmp_path, full_vqc=True)
    rows = control.main(["--workload", "sim.qkd", "--seeds", "3",
                         "--upper", "1"], require_tpu=False,
                        use_cache=False, root=str(root),
                        out=io.StringIO())
    limits = spec.load_cell("sim.qkd", root).traffic["limits"]
    got = {r["reading"]: r for r in rows}
    assert check.verdict(got["program"], limits)
    assert not check.verdict(got["control"], limits)
    assert not check.verdict(got["half_batch"], limits)
