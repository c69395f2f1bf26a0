"""Variational quantum classifier — the paper's QFL workload (§IV).

Circuit (matching the Qiskit VQC pattern the paper uses):
  1. angle encoding: RY(x_i) on qubit i for each of n_features inputs
  2. ansatz, L layers: RY(θ_l,i) RZ(φ_l,i) per qubit + ring of CZ entanglers
  3. readout: ⟨Z_i⟩ on the first n_classes qubits -> logits (scaled + biased
     by a tiny classical head, standard hybrid practice)

Evaluation is a fused batched pipeline (the hot path both FL engines train):

  * each ansatz layer's RZ·RY products are precomputed as ONE (L, nq, 2, 2)
    gate tensor, and a whole layer of 1q gates is applied in one fused
    contraction (``sv.apply_1q_layer`` — consecutive qubits kron-grouped)
  * the CZ entangler ring is a single precomputed ±1 diagonal per layer
    (``sv.ring_cz_signs`` — CZs commute, the ring is static)
  * readout is one (n_classes, dim) sign-matrix matmul over the
    probabilities (``sv.expect_z_all``) instead of stacked expect_z calls

The per-gate path (``fused=False`` / an ``apply_1q`` override, e.g. the
Pallas kernel) is kept as the reference; tests assert both agree to 1e-6.

Gradients: exact autodiff through the statevector (fast path) and
parameter-shift (paper-faithful path, what Qiskit QNN computes) — the
shift rule is VECTORIZED: all 2·P shifted parameter tensors are stacked
and the circuit vmapped over the shift axis (``chunk`` bounds memory),
replacing the serial per-parameter ``lax.map`` loop. Tests assert the
vectorized rule, the serial rule, and autodiff all agree.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.quantum import statevector as sv


def vqc_init(cfg: ArchConfig, key) -> dict:
    nq, L = cfg.vqc_qubits, cfg.vqc_layers
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "theta": jax.random.uniform(k1, (L, nq), jnp.float32, 0.0, jnp.pi),
        "phi": jax.random.uniform(k2, (L, nq), jnp.float32, 0.0, jnp.pi),
        "w_out": jnp.ones((cfg.n_classes,), jnp.float32) * 3.0,
        "b_out": jnp.zeros((cfg.n_classes,), jnp.float32),
    }


# ---------------------------------------------------------------------------
# fused batched evaluation (default path)
# ---------------------------------------------------------------------------

def layer_gates(params) -> jax.Array:
    """(L, nq, 2, 2) fused per-qubit ansatz gates: RZ(φ_l,q) · RY(θ_l,q).

    One tensor for the whole ansatz — halves the 1q applications per layer
    and is the unit both the fused simulator contraction and the Pallas
    fused-layer kernel consume.
    """
    ry = sv.ry_gate(params["theta"])          # gate builders broadcast
    rz = sv.rz_gate(params["phi"])
    return jnp.einsum("...ab,...bc->...ac", rz, ry, precision=sv.HIGHEST)


def encoding_gates(cfg: ArchConfig, features: jax.Array) -> jax.Array:
    """(B, nq, 2, 2) per-sample RY(x_q) encoding gates (RY(0)=I padding)."""
    nq = cfg.vqc_qubits
    k = min(cfg.n_features, nq)
    angles = jnp.zeros(features.shape[:-1] + (nq,), jnp.float32)
    angles = angles.at[..., :k].set(features[..., :k])
    return sv.ry_gate(angles)


def _circuit_state_fused(cfg: ArchConfig, params, features, group: int = 2):
    """Batched statevector (B, 2^nq) after encoding + ansatz."""
    nq, L = cfg.vqc_qubits, cfg.vqc_layers
    state = sv.init_state(nq, features.shape[:-1])
    state = sv.apply_1q_layer(state, encoding_gates(cfg, features), group)
    gates = layer_gates(params)
    ring = sv.ring_cz_signs(nq).astype(sv.CDTYPE)
    for l in range(L):
        state = sv.apply_1q_layer(state, gates[l], group)
        state = state * ring
    return state


def _logits_fused(cfg: ArchConfig, params, features):
    state = _circuit_state_fused(cfg, params, features)
    exps = sv.expect_z_all(state, cfg.n_classes)
    return params["w_out"] * exps + params["b_out"]


# ---------------------------------------------------------------------------
# per-gate reference path (numerics oracle; also the kernel-injection hook)
# ---------------------------------------------------------------------------

def _circuit_state(cfg: ArchConfig, params, x, apply_1q=None):
    """Statevector after encoding + ansatz for one sample x (n_features,)."""
    ap = apply_1q or sv.apply_1q
    nq, L = cfg.vqc_qubits, cfg.vqc_layers
    state = sv.init_state(nq)
    for q in range(min(cfg.n_features, nq)):
        state = ap(state, sv.ry_gate(x[q]), q)
    for l in range(L):
        for q in range(nq):
            state = ap(state, sv.ry_gate(params["theta"][l, q]), q)
            state = ap(state, sv.rz_gate(params["phi"][l, q]), q)
        for q in range(nq):
            state = sv.apply_cz(state, q, (q + 1) % nq)
    return state


def _logits_single(cfg: ArchConfig, params, x, apply_1q=None):
    state = _circuit_state(cfg, params, x, apply_1q)
    exps = jnp.stack([sv.expect_z(state, q) for q in range(cfg.n_classes)])
    return params["w_out"] * exps + params["b_out"]


def vqc_logits(cfg: ArchConfig, params, features, apply_1q=None,
               fused: bool = True):
    """features (B, n_features) -> logits (B, n_classes).

    Default is the fused batched pipeline; ``fused=False`` (or an
    ``apply_1q`` override, e.g. the Pallas kernel) takes the per-gate
    vmapped path.
    """
    if fused and apply_1q is None:
        return _logits_fused(cfg, params, features)
    return jax.vmap(lambda x: _logits_single(cfg, params, x, apply_1q))(features)


def vqc_loss(cfg: ArchConfig, params, batch, ctx=None, fused: bool = True):
    logits = vqc_logits(cfg, params, batch["features"], fused=fused)
    labels = batch["labels"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - ll)


def vqc_accuracy(cfg: ArchConfig, params, batch):
    logits = vqc_logits(cfg, params, batch["features"])
    return jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))


# ---------------------------------------------------------------------------
# parameter-shift gradients (paper-faithful: Qiskit QNN's gradient rule)
# ---------------------------------------------------------------------------

def _shift_chain(cfg: ArchConfig, params, batch, fused: bool = True):
    """Shared setup: dL/dlogits for the classical chain rule (the shift
    rule differentiates the circuit *expectations* — linear in ⟨Z⟩ — and
    the CE is chained in classically, exactly what Qiskit's QNN does)."""
    feats, labels = batch["features"], batch["labels"]
    logits0 = vqc_logits(cfg, params, feats, fused=fused)
    p = jax.nn.softmax(logits0, axis=-1)
    dL = (p - jax.nn.one_hot(labels, cfg.n_classes)) / feats.shape[0]
    return feats, dL


def _head_grads(cfg: ArchConfig, params, batch):
    return jax.grad(
        lambda w, b: vqc_loss(cfg, {**params, "w_out": w, "b_out": b}, batch),
        argnums=(0, 1))(params["w_out"], params["b_out"])


def parameter_shift_grad(cfg: ArchConfig, params, batch, chunk: int = 0,
                         group: int = 4, with_loss: bool = False):
    """∂loss/∂(θ, φ) via the ±π/2 rule, VECTORIZED over all shifts.

    Every one of the 4·P shifted circuits (P = L·nq each for θ and φ, ±
    per parameter) is evaluated exactly — but never one at a time. Pauli
    rotations compose, R(θ±π/2) = R(θ)·R(±π/2), so a shifted circuit is
    the BASE circuit with one fixed ±π/2 rotation inserted at the shift
    site (RZ shifts additionally commute through the diagonal CZ ring).
    The evaluation therefore

      1. runs the fused base circuit once, keeping each layer state,
      2. per layer, stacks all 2·nq ±-inserted branch states on a leading
         shift axis — (2, nq, B, dim) — and pushes the whole stack through
         the remaining suffix layers as one batched fused contraction,
      3. reads out every branch against a precomputed chained observable
         M[b, :] = Σ_c dL[b,c]·w_c·zsign_c (one elementwise pass).

    ``chunk > 0`` bounds peak memory by pushing each layer's branch stack
    through its suffix in chunks of that size. ``group`` is the kron-fusion
    width of the suffix contractions (4 measures fastest for the wide
    branch stacks; the plain forward defaults to 2). Returns a grads
    pytree matching ``params`` — or ``(loss, grads)`` with
    ``with_loss=True``, the CE loss falling out of the base sweep's
    logits for free (what the FL engines' grad_fn contract wants).
    """
    nq, L = cfg.vqc_qubits, cfg.vqc_layers
    feats, labels = batch["features"], batch["labels"]
    gates = layer_gates(params)
    ring = sv.ring_cz_signs(nq).astype(sv.CDTYPE)

    # ONE base sweep yields the per-layer branch inputs AND the readout:
    # logits0, dL/dlogits, and the (closed-form) head grads all derive
    # from the final state — no separate forward or reverse pass
    state = sv.init_state(nq, feats.shape[:-1])
    state = sv.apply_1q_layer(state, encoding_gates(cfg, feats), group)
    layer_in = []
    for l in range(L):
        layer_in.append(state)
        state = sv.apply_1q_layer(state, gates[l], group) * ring
    exps = sv.expect_z_all(state, cfg.n_classes)             # (B, C)
    logits0 = params["w_out"] * exps + params["b_out"]
    p = jax.nn.softmax(logits0, axis=-1)
    dL = (p - jax.nn.one_hot(labels, cfg.n_classes)) / feats.shape[0]
    # chained diagonal observable: Σ_shift dL·logits needs only
    # Σ_{b,i} |ψ|²[b,i] · M[b,i] per branch (b_out cancels in the ± diff)
    M = jnp.einsum("bc,ci->bi", dL * params["w_out"],
                   sv.zexp_signs(nq, cfg.n_classes), precision=sv.HIGHEST)

    half = jnp.pi / 2
    ry_pm = jnp.stack([sv.ry_gate(half), sv.ry_gate(-half)])    # (2, 2, 2)
    rz_pm = jnp.stack([sv.rz_gate(half), sv.rz_gate(-half)])

    def branch_vals(stack, l0):
        """(2, nq, B, dim) branch stack -> suffix layers l0.. -> (2, nq)."""
        def suffix(st):
            for l in range(l0, L):
                st = sv.apply_1q_layer(st, gates[l], group) * ring
            return jnp.einsum("...bi,bi->...", sv.probs(st), M,
                              precision=sv.HIGHEST)
        if chunk and chunk > 0:
            flat = stack.reshape((-1,) + stack.shape[2:])
            return jax.lax.map(suffix, flat,
                               batch_size=chunk).reshape(2, nq)
        return suffix(stack)

    g_theta, g_phi = [], []
    for l in range(L):
        # θ_l,q: RY(±π/2) on qubit q BEFORE layer l (RY(θ±s) = RY(θ)RY(±s))
        th_stack = jnp.stack([
            jnp.stack([sv.apply_1q(layer_in[l], ry_pm[s], q)
                       for q in range(nq)]) for s in range(2)])
        vt = branch_vals(th_stack, l)
        g_theta.append(0.5 * (vt[0] - vt[1]))
        # φ_l,q: RZ(±π/2) AFTER layer l (RZ(φ±s) = RZ(±s)RZ(φ), and RZ
        # commutes through the diagonal CZ ring), i.e. before layer l+1
        nxt = layer_in[l + 1] if l + 1 < L else state
        ph_stack = jnp.stack([
            jnp.stack([sv.apply_1q(nxt, rz_pm[s], q)
                       for q in range(nq)]) for s in range(2)])
        vp = branch_vals(ph_stack, l + 1)
        g_phi.append(0.5 * (vp[0] - vp[1]))

    # head grads are closed-form: logits = w ⊙ exps + b
    grads = {"theta": jnp.stack(g_theta), "phi": jnp.stack(g_phi),
             "w_out": jnp.sum(dL * exps, axis=0),
             "b_out": jnp.sum(dL, axis=0)}
    if not with_loss:
        return grads
    lse = jax.scipy.special.logsumexp(logits0, axis=-1)
    ll = jnp.take_along_axis(logits0, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - ll), grads


def parameter_shift_grad_serial(cfg: ArchConfig, params, batch):
    """Pre-vectorization reference: one circuit pair per parameter via
    ``lax.map`` over the per-gate path. Kept as the numerics oracle and the
    benchmark baseline the fused engine is measured against."""
    feats, dL = _shift_chain(cfg, params, batch, fused=False)
    shift = jnp.pi / 2

    def logits_at(theta, phi):
        return vqc_logits(cfg, {**params, "theta": theta, "phi": phi},
                          feats, fused=False)

    base_theta, base_phi = params["theta"], params["phi"]

    def shift_grad(base, is_theta):
        flat = base.reshape(-1)

        def one(i):
            e = jnp.zeros_like(flat).at[i].set(shift).reshape(base.shape)
            if is_theta:
                dlogits = 0.5 * (logits_at(base + e, base_phi)
                                 - logits_at(base - e, base_phi))
            else:
                dlogits = 0.5 * (logits_at(base_theta, base + e)
                                 - logits_at(base_theta, base - e))
            return jnp.sum(dL * dlogits)

        return jax.lax.map(one, jnp.arange(flat.shape[0])).reshape(base.shape)

    g_head = _head_grads(cfg, params, batch)
    return {"theta": shift_grad(base_theta, True),
            "phi": shift_grad(base_phi, False),
            "w_out": g_head[0], "b_out": g_head[1]}


# ---------------------------------------------------------------------------
# ModelApi adapter (so a satellite's local model can be the VQC)
# ---------------------------------------------------------------------------

def _no_serve(*a, **k):
    raise NotImplementedError("VQC is a classifier — no autoregressive serve")


def vqc_api():
    from repro.models.registry import ModelApi

    def fwd(cfg, params, batch, ctx=None):
        return vqc_logits(cfg, params, batch["features"]), jnp.zeros((), jnp.float32)

    return ModelApi(vqc_init, fwd, vqc_loss, _no_serve, _no_serve,
                    shift_grad=parameter_shift_grad)
