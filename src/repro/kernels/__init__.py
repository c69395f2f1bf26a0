"""Pallas TPU kernels for the compute hot spots.

  otp_xor       — fused OTP-XOR + polynomial-MAC partials (bulk AEAD on
                  every model exchange; bandwidth-bound streaming)
  statevec_gate — 1-qubit gate application over a statevector (the QFL
                  workload's inner loop; strided pair updates)
  swa_attention — sliding-window flash attention (what makes dense archs
                  feasible at 500k context)
  ssd_scan      — Mamba-2 SSD chunked scan (mamba2 + hymba branch)

Each subpackage: ``kernel.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit'd public wrapper; ``interpret=None`` interprets only on the CPU
backend — ``backend.resolve_interpret``),
``ref.py`` (pure-jnp oracle; also the backward path where the kernel is
forward-only). Tests sweep shapes/dtypes and assert allclose vs ref.
"""
from repro.kernels.otp_xor.ops import otp_xor_mac, otp_xor_mac_edges
from repro.kernels.statevec_gate.ops import apply_gate, apply_gate_layer
from repro.kernels.swa_attention.ops import swa_attention
from repro.kernels.ssd_scan.ops import ssd_scan

__all__ = ["otp_xor_mac", "otp_xor_mac_edges", "apply_gate",
           "apply_gate_layer", "swa_attention", "ssd_scan"]
