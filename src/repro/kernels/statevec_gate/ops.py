"""Public wrappers for statevec_gate with custom VJPs.

``apply_gate(state_complex, gate_2x2_complex, qubit)`` mirrors
``repro.quantum.statevector.apply_1q`` but runs the Pallas butterfly
kernel. Forward runs the kernel; backward applies the adjoint gate with
the SAME kernel (the butterfly is its own transpose pattern) plus a small
einsum for the gate cotangent — so VQC training can run end-to-end on the
kernel path.

``apply_gate_layer(state_complex, gates (nq, 2, 2))`` is the fused-layer
entry point: it consumes the SAME per-qubit gate tensor the fused
simulator path (``statevector.apply_1q_layer`` / ``vqc.layer_gates``)
builds, and picks a layer plan by state size:

  resident — whole state ≤ MAX_FUSED_DIM amplitudes stays in VMEM, all nq
             stages in one launch;
  tiled    — larger states run the multi-stage tiled variant: butterfly
             stages fused per qubit GROUP, one HBM pass per group (20+
             qubits without falling back to per-gate sweeps);
  per-gate — defensive fallback only (non-power-of-two tiling overrides);
             it is LOGGED and recorded in ``LAYER_DEBUG`` — the silent
             degradation the ROADMAP called out is gone.

``layer_plan(dim)`` exposes the choice; ``LAYER_DEBUG`` records the last
trace's plan so benchmarks report which path actually ran. States may
carry leading batch dims (the constellation-batched engine's client-
stacked states) — every plan handles (..., 2^nq).

Backward re-runs the differentiable per-gate oracle composition under
``jax.vjp`` (one extra reference forward — the layer is short, so
recompute beats stashing nq intermediate states).
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.statevec_gate.kernel import (
    GROUP_QUBITS, GROUP_TILE, LOW_QUBITS, MAX_FUSED_DIM, apply_gate_planes,
    apply_layer_planes, apply_layer_planes_tiled,
)
from repro.kernels.statevec_gate.ref import (
    adjoint_gate8, apply_gate_planes_ref, apply_layer_planes_ref, gate_grad,
)

logger = logging.getLogger(__name__)

#: debug record of the most recent apply_gate_layer trace:
#: {"path": "resident"|"tiled"|"per-gate"|"ref", "dim": int, "batch": tuple}
LAYER_DEBUG: dict = {}


def _pack_gate(gate: jax.Array) -> jax.Array:
    g = gate.astype(jnp.complex64)
    return jnp.stack([
        g[0, 0].real, g[0, 0].imag, g[0, 1].real, g[0, 1].imag,
        g[1, 0].real, g[1, 0].imag, g[1, 1].real, g[1, 1].imag,
    ]).astype(jnp.float32)


def _unpack_gate(g8: jax.Array) -> jax.Array:
    re = jnp.stack([g8[0], g8[2], g8[4], g8[6]]).reshape(2, 2)
    im = jnp.stack([g8[1], g8[3], g8[5], g8[7]]).reshape(2, 2)
    return (re + 1j * im).astype(jnp.complex64)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _apply_planes(state_re, state_im, gate8, qubit, interpret, use_kernel):
    if use_kernel:
        return apply_gate_planes(state_re, state_im, gate8, qubit,
                                 interpret=interpret)
    return apply_gate_planes_ref(state_re, state_im, gate8, qubit)


def _fwd(state_re, state_im, gate8, qubit, interpret, use_kernel):
    out = _apply_planes(state_re, state_im, gate8, qubit, interpret,
                        use_kernel)
    return out, (state_re, state_im, gate8)


def _bwd(qubit, interpret, use_kernel, res, cots):
    state_re, state_im, gate8 = res
    cot_re, cot_im = cots
    adj = adjoint_gate8(gate8)
    if use_kernel:
        ar, ai = apply_gate_planes(cot_re, cot_im, adj, qubit,
                                   interpret=interpret)
    else:
        ar, ai = apply_gate_planes_ref(cot_re, cot_im, adj, qubit)
    g8_bar = gate_grad(state_re, state_im, cot_re, cot_im, qubit)
    return ar, ai, g8_bar


_apply_planes.defvjp(_fwd, _bwd)


def apply_gate(state: jax.Array, gate: jax.Array, qubit: int,
               interpret: bool | None = None,
               use_kernel: bool = True) -> jax.Array:
    """Drop-in for statevector.apply_1q on (..., 2^nq) complex states (the
    kernel path; leading batch dims fold into the grid)."""
    interpret = resolve_interpret(interpret)
    g8 = _pack_gate(gate)
    sr = state.real.astype(jnp.float32)
    si = state.imag.astype(jnp.float32)
    outr, outi = _apply_planes(sr, si, g8, qubit, interpret, use_kernel)
    return (outr + 1j * outi).astype(jnp.complex64)


# ---------------------------------------------------------------------------
# fused layer
# ---------------------------------------------------------------------------

def _pack_gates(gates: jax.Array) -> jax.Array:
    """(nq, 2, 2) complex -> (nq, 8) packed reals."""
    g = gates.astype(jnp.complex64).reshape(gates.shape[0], 4)
    return jnp.stack([g.real, g.imag], axis=-1).reshape(gates.shape[0], 8)


def layer_plan(dim: int, use_kernel: bool = True,
               low_qubits: int = LOW_QUBITS,
               group_tile: int = GROUP_TILE) -> str:
    """Which execution plan ``apply_gate_layer`` takes for a 2^nq state."""
    if not use_kernel:
        return "ref"
    if dim <= MAX_FUSED_DIM and dim.bit_length() - 1 <= low_qubits:
        return "resident"
    # every per-pass extent (2^q0 lanes, min(lo, group_tile) tiles) is a
    # power of two, so the tiled grid covers the state exactly iff the
    # tile override is one too — anything else would leave trailing
    # lanes unwritten, which must fall back LOUDLY instead
    if group_tile > 0 and (group_tile & (group_tile - 1)) == 0:
        return "tiled"
    return "per-gate"


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7))
def _apply_layer_planes(state_re, state_im, gates8, interpret, use_kernel,
                        low_qubits, group_qubits, group_tile):
    plan = layer_plan(state_re.shape[-1], use_kernel, low_qubits, group_tile)
    LAYER_DEBUG.update(path=plan, dim=int(state_re.shape[-1]),
                       batch=tuple(state_re.shape[:-1]))
    if plan == "resident":
        return apply_layer_planes(state_re, state_im, gates8,
                                  interpret=interpret)
    if plan == "tiled":
        return apply_layer_planes_tiled(
            state_re, state_im, gates8, low_qubits=low_qubits,
            group_qubits=group_qubits, group_tile=group_tile,
            interpret=interpret)
    if plan == "per-gate":
        # defensive fallback — loud, never silent (ROADMAP gap)
        logger.warning(
            "apply_gate_layer: tiled fused path unavailable for dim=%d "
            "(low_qubits=%d, group_tile=%d) — degrading to %d per-gate "
            "kernel sweeps", state_re.shape[-1], low_qubits, group_tile,
            gates8.shape[0])
        for q in range(gates8.shape[0]):
            state_re, state_im = apply_gate_planes(
                state_re, state_im, gates8[q], q, interpret=interpret)
        return state_re, state_im
    return apply_layer_planes_ref(state_re, state_im, gates8)


def _layer_fwd(state_re, state_im, gates8, interpret, use_kernel,
               low_qubits, group_qubits, group_tile):
    out = _apply_layer_planes(state_re, state_im, gates8, interpret,
                              use_kernel, low_qubits, group_qubits,
                              group_tile)
    return out, (state_re, state_im, gates8)


def _layer_bwd(interpret, use_kernel, low_qubits, group_qubits, group_tile,
               res, cots):
    state_re, state_im, gates8 = res
    _, vjp = jax.vjp(apply_layer_planes_ref, state_re, state_im, gates8)
    return vjp(cots)


_apply_layer_planes.defvjp(_layer_fwd, _layer_bwd)


def apply_gate_layer(state: jax.Array, gates: jax.Array,
                     interpret: bool | None = None, use_kernel: bool = True,
                     low_qubits: int = LOW_QUBITS,
                     group_qubits: int = GROUP_QUBITS,
                     group_tile: int = GROUP_TILE) -> jax.Array:
    """Apply gate q to qubit q for all nq qubits — fused kernel launches.

    state (..., 2^nq) complex (leading dims = stacked clients/branches);
    gates (nq, 2, 2) complex — the same per-qubit gate tensor
    ``vqc.layer_gates`` emits (one layer's RZ·RY products). States up to
    MAX_FUSED_DIM amplitudes run fully resident; larger states run the
    tiled multi-stage plan (one HBM pass per qubit group). The tiling
    knobs exist for tests; defaults are the production plan.
    """
    interpret = resolve_interpret(interpret)
    g8 = _pack_gates(gates)
    sr = state.real.astype(jnp.float32)
    si = state.imag.astype(jnp.float32)
    outr, outi = _apply_layer_planes(sr, si, g8, interpret, use_kernel,
                                     low_qubits, group_qubits, group_tile)
    return (outr + 1j * outi).astype(jnp.complex64)
