"""JAX statevector simulator.

States are complex64 arrays of shape (..., 2**n) with **little-endian**
qubit order (qubit 0 is the least-significant index bit). Gate application
uses reshape/einsum (contiguous strides — the pattern the Pallas kernel in
``repro.kernels.statevec_gate`` tiles for VMEM); controlled gates use the
partner-index formulation (gather + where), which lowers to vectorized ops.

Everything jits and vmaps; the circuit layer (vqc/qkd/teleport) builds on
these primitives.
"""
from __future__ import annotations

import math
import string
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

CDTYPE = jnp.complex64
# every contraction here runs at full f32: the TPU's default precision
# rounds f32 operands to bf16, which moved a 50-satellite round's server
# loss by ~1e-2 and broke the 1e-6 batched-vs-oracle parity on the chip
HIGHEST = jax.lax.Precision.HIGHEST

# ---------------------------------------------------------------------------
# gates
# ---------------------------------------------------------------------------

H = (1.0 / math.sqrt(2.0)) * jnp.array([[1, 1], [1, -1]], CDTYPE)
X = jnp.array([[0, 1], [1, 0]], CDTYPE)
Y = jnp.array([[0, -1j], [1j, 0]], CDTYPE)
Z = jnp.array([[1, 0], [0, -1]], CDTYPE)


def ry_gate(theta) -> jax.Array:
    t = jnp.asarray(theta, jnp.float32) / 2
    c, s = jnp.cos(t), jnp.sin(t)
    return jnp.stack([jnp.stack([c, -s], -1),
                      jnp.stack([s, c], -1)], -2).astype(CDTYPE)


def rz_gate(phi) -> jax.Array:
    p = jnp.asarray(phi, jnp.float32) / 2
    e_m = jnp.exp(-1j * p.astype(CDTYPE))
    e_p = jnp.exp(1j * p.astype(CDTYPE))
    zero = jnp.zeros_like(e_m)
    return jnp.stack([jnp.stack([e_m, zero], -1),
                      jnp.stack([zero, e_p], -1)], -2)


def u3_gate(theta, phi, lam) -> jax.Array:
    """Standard U(θ, φ, λ) — the paper's parameter-encoding unitary (Alg. 2/4)."""
    t = jnp.asarray(theta, jnp.float32) / 2
    c = jnp.cos(t).astype(CDTYPE)
    s = jnp.sin(t).astype(CDTYPE)
    phi = jnp.asarray(phi, jnp.float32).astype(CDTYPE)
    lam = jnp.asarray(lam, jnp.float32).astype(CDTYPE)
    return jnp.stack([
        jnp.stack([c, -jnp.exp(1j * lam) * s], -1),
        jnp.stack([jnp.exp(1j * phi) * s, jnp.exp(1j * (phi + lam)) * c], -1),
    ], -2)


# ---------------------------------------------------------------------------
# state construction / application
# ---------------------------------------------------------------------------

def init_state(n_qubits: int, batch: tuple = ()) -> jax.Array:
    """|0...0> statevector, optionally batched."""
    dim = 2 ** n_qubits
    state = jnp.zeros(batch + (dim,), CDTYPE)
    return state.at[..., 0].set(1.0)


def apply_1q(state: jax.Array, gate: jax.Array, qubit: int) -> jax.Array:
    """Apply a 2x2 gate to `qubit`. state (..., 2^n); gate (..., 2, 2)
    (broadcast against batch dims)."""
    dim = state.shape[-1]
    n = dim.bit_length() - 1
    lead = state.shape[:-1]
    lo = 2 ** qubit
    hi = dim // (2 * lo)
    st = state.reshape(lead + (hi, 2, lo))
    if gate.ndim == 2:
        out = jnp.einsum("ab,...hbl->...hal", gate, st, precision=HIGHEST)
    else:
        out = jnp.einsum("...ab,...hbl->...hal", gate, st,
                         precision=HIGHEST)
    return out.reshape(lead + (dim,))


def apply_h(state, qubit):
    return apply_1q(state, H, qubit)


def apply_ry(state, theta, qubit):
    return apply_1q(state, ry_gate(theta), qubit)


def apply_rz(state, phi, qubit):
    return apply_1q(state, rz_gate(phi), qubit)


def apply_u3(state, theta, phi, lam, qubit):
    return apply_1q(state, u3_gate(theta, phi, lam), qubit)


def _bit(idx, q):
    return (idx >> q) & 1


def apply_cz(state: jax.Array, q1: int, q2: int) -> jax.Array:
    """Controlled-Z: phase-flip where both bits are 1 (diagonal — no gather)."""
    dim = state.shape[-1]
    idx = jnp.arange(dim)
    sign = jnp.where((_bit(idx, q1) & _bit(idx, q2)) == 1, -1.0, 1.0)
    return state * sign.astype(CDTYPE)


def apply_cnot(state: jax.Array, control: int, target: int) -> jax.Array:
    """CNOT via partner-index gather: swap amplitudes where control=1."""
    dim = state.shape[-1]
    idx = jnp.arange(dim)
    partner = idx ^ (1 << target)
    swapped = jnp.take(state, partner, axis=-1)
    cond = (_bit(idx, control) == 1)
    return jnp.where(cond, swapped, state)


def apply_controlled_1q(state, gate, control: int, target: int) -> jax.Array:
    """General controlled single-qubit gate (used for conditioned corrections)."""
    dim = state.shape[-1]
    idx = jnp.arange(dim)
    partner = idx ^ (1 << target)
    tbit = _bit(idx, target)
    # out[i] = g[t, t] * s[i] + g[t, 1-t] * s[partner]  where control=1
    g_tt = jnp.where(tbit == 0, gate[0, 0], gate[1, 1])
    g_to = jnp.where(tbit == 0, gate[0, 1], gate[1, 0])
    mixed = g_tt * state + g_to * jnp.take(state, partner, axis=-1)
    cond = (_bit(idx, control) == 1)
    return jnp.where(cond, mixed, state)


# ---------------------------------------------------------------------------
# fused layer application (one contraction per qubit *group*, not per gate)
# ---------------------------------------------------------------------------

def group_1q_gates(gates: jax.Array, group: int = 2) -> list:
    """Kron consecutive 1q gates into (2^g, 2^g) group gates.

    gates (..., nq, 2, 2), gate q acting on qubit q. Returns a list, low
    group first, of (..., 2^s, 2^s) arrays where group j covers qubits
    [j·g, j·g + s) and the kron is ordered high-qubit-first (matching the
    little-endian (2,)*nq reshape of a statevector).
    """
    nq = gates.shape[-3]
    out = []
    q = 0
    while q < nq:
        s = min(group, nq - q)
        acc = gates[..., q, :, :]
        for t in range(1, s):
            hi = gates[..., q + t, :, :]
            d = acc.shape[-1]
            # kron(hi, acc): row (i_h, i_a), col (j_h, j_a)
            acc = jnp.einsum("...hk,...ab->...hakb", hi, acc,
                             precision=HIGHEST).reshape(
                acc.shape[:-2] + (2 * d, 2 * d))
        out.append(acc)
        q += s
    return out


def apply_1q_layer(state: jax.Array, gates: jax.Array,
                   group: int = 2) -> jax.Array:
    """Apply gate q to qubit q for ALL qubits in one fused contraction.

    gates (..., nq, 2, 2) broadcasts against the state's batch dims (shared
    ansatz gates are (nq, 2, 2); per-sample encoding gates (B, nq, 2, 2)).
    Consecutive qubits are kron-fused into 2^group-dim gates first — same
    flops, 1/group the passes over the state — then a single multi-operand
    einsum contracts every group gate with its state axis (opt_einsum picks
    the pairwise order; XLA fuses the chain).
    """
    dim = state.shape[-1]
    nq = dim.bit_length() - 1
    assert gates.shape[-3] == nq, (gates.shape, nq)
    grouped = group_1q_gates(gates.astype(state.dtype), group)
    sizes = [g.shape[-1] for g in grouped]           # low group first
    lead = state.shape[:-1]
    # axis order of the reshaped state is high group first (little-endian)
    st = state.reshape(lead + tuple(s for s in reversed(sizes)))
    n_groups = len(sizes)
    in_sub = string.ascii_lowercase[:n_groups]        # state axes, high->low
    out_sub = string.ascii_uppercase[:n_groups]
    gate_terms = []
    for j in range(n_groups):                         # group j = axis n-1-j
        k = n_groups - 1 - j
        gate_terms.append("..." + out_sub[k] + in_sub[k])
    eq = ",".join(gate_terms) + ",..." + in_sub + "->..." + out_sub
    out = jnp.einsum(eq, *grouped, st, precision=HIGHEST)
    return out.reshape(lead + (dim,))


@lru_cache(maxsize=None)
def _ring_cz_signs_np(nq: int) -> np.ndarray:
    idx = np.arange(1 << nq)
    count = np.zeros(idx.shape, np.int64)
    for q in range(nq):
        count += ((idx >> q) & 1) & ((idx >> ((q + 1) % nq)) & 1)
    return np.where(count % 2 == 1, -1.0, 1.0).astype(np.float32)


def ring_cz_signs(nq: int) -> jax.Array:
    """±1 diagonal of the CZ entangler ring ∏_q CZ(q, q+1 mod nq).

    CZs are diagonal and commute, so the whole ring is one static sign
    vector: (-1)^(# adjacent 1-pairs). Computed host-side once per nq
    (cached as numpy so a jit trace never captures another trace's array).
    """
    return jnp.asarray(_ring_cz_signs_np(nq))


@lru_cache(maxsize=None)
def _zexp_signs_np(nq: int, n_obs: int) -> np.ndarray:
    idx = np.arange(1 << nq)
    rows = [np.where((idx >> q) & 1 == 0, 1.0, -1.0) for q in range(n_obs)]
    return np.stack(rows).astype(np.float32)


def zexp_signs(nq: int, n_obs: int) -> jax.Array:
    """(n_obs, 2^nq) ±1 matrix: row q is the ⟨Z_q⟩ sign vector, so the
    stacked readout over the first n_obs qubits is one matmul over probs."""
    return jnp.asarray(_zexp_signs_np(nq, n_obs))


def expect_z_all(state: jax.Array, n_obs: int) -> jax.Array:
    """Stacked ⟨Z_0..Z_{n_obs-1}⟩ via one (dim,) x (n_obs, dim) matmul.
    state (..., 2^n) -> (..., n_obs)."""
    nq = state.shape[-1].bit_length() - 1
    return jnp.matmul(probs(state), zexp_signs(nq, n_obs).T,
                      precision=HIGHEST)


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

def probs(state: jax.Array) -> jax.Array:
    return (state.real ** 2 + state.imag ** 2).astype(jnp.float32)


def expect_z(state: jax.Array, qubit: int) -> jax.Array:
    """⟨Z_qubit⟩ ∈ [-1, 1]."""
    p = probs(state)
    dim = state.shape[-1]
    sign = jnp.where(_bit(jnp.arange(dim), qubit) == 0, 1.0, -1.0)
    return jnp.sum(p * sign, axis=-1)


def sample_measure(key: jax.Array, state: jax.Array, shots: int) -> jax.Array:
    """Sample `shots` computational-basis outcomes. Returns (..., shots) int32."""
    p = probs(state)
    logp = jnp.log(jnp.maximum(p, 1e-30))
    return jax.random.categorical(key, logp, axis=-1,
                                  shape=logp.shape[:-1] + (shots,))


def measure_qubit(key: jax.Array, state: jax.Array, qubit: int):
    """Projective measurement of one qubit: returns (outcome, collapsed state).

    outcome: int32 scalar (or batch); the state is renormalized.
    """
    p = probs(state)
    dim = state.shape[-1]
    mask1 = (_bit(jnp.arange(dim), qubit) == 1)
    p1 = jnp.sum(jnp.where(mask1, p, 0.0), axis=-1)
    u = jax.random.uniform(key, p1.shape)
    outcome = (u < p1).astype(jnp.int32)
    keep = jnp.where(outcome[..., None] == 1, mask1, ~mask1)
    collapsed = jnp.where(keep, state, 0.0)
    norm = jnp.sqrt(jnp.sum(probs(collapsed), axis=-1, keepdims=True))
    return outcome, collapsed / jnp.maximum(norm, 1e-30).astype(CDTYPE)
