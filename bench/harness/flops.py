"""Model FLOPs of the VQC, counted for the gate-by-gate dense statevector
algorithm from the shapes alone, and the chips' published peaks.

A forward pass of one sample on n qubits (dim = 2^n amplitudes):
  * each single-qubit gate is a 2x2 complex matrix on dim/2 amplitude
    pairs: 4 complex multiplies (6 FLOPs) and 2 complex adds (2 FLOPs)
    per pair, 14 FLOPs per amplitude;
  * each CZ is a diagonal of signs: 2 FLOPs per amplitude;
  * readout: |amplitude|^2 (3 FLOPs), one multiply-add per amplitude and
    class for <Z_c> (2 FLOPs), and the classical head and cross-entropy
    (8 FLOPs per class).
The circuit is min(features, qubits) encoding RY gates, then per layer an
RY and an RZ on every qubit and a ring of one CZ per qubit.

Training counts only the rows that train: autodiff as 3 forward passes a
sample, the parameter-shift rule as 1 + 2P forward passes, P the
circuit's rotation angles (2 per qubit and layer). Evaluation counts one
forward pass a sample. The count does not depend on how the program
implements the circuit, so no implementation can move it.
"""
from __future__ import annotations

#: published peaks of one chip, keyed by ``jax.Device.device_kind``
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_s": 197e12, "hbm_bytes_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e'"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}; add it to bench/harness/flops.py")
    return PEAKS[device_kind]


def forward_flops(model: dict) -> int:
    nq, nl, nc = model["vqc_qubits"], model["vqc_layers"], model["n_classes"]
    dim = 1 << nq
    gates = min(model["n_features"], nq) + 2 * nl * nq
    return (14 * dim * gates + 2 * dim * nl * nq
            + 3 * dim + 2 * dim * nc + 8 * nc)


def train_passes(model: dict, grad_method: str) -> int:
    if grad_method == "autodiff":
        return 3
    if grad_method == "param_shift":
        return 1 + 2 * (2 * model["vqc_layers"] * model["vqc_qubits"])
    raise ValueError(f"unknown grad_method {grad_method!r}")


def round_flops(model: dict, fl: dict, trained: int, eval_rows: int) -> int:
    """FLOPs of one round: ``trained`` satellites each taking
    ``local_steps`` steps on ``batch_size`` rows, plus ``eval_rows``
    evaluation forward passes."""
    f = forward_flops(model)
    rows = trained * fl["local_steps"] * fl["batch_size"]
    return f * (rows * train_passes(model, fl.get("grad_method", "autodiff"))
                + eval_rows)
