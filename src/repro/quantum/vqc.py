"""Variational quantum classifier — the paper's QFL workload (§IV).

Circuit (matching the Qiskit VQC pattern the paper uses):
  1. angle encoding: RY(x_i) on qubit i for each of n_features inputs
  2. ansatz, L layers: RY(θ_l,i) RZ(φ_l,i) per qubit + ring of CZ entanglers
  3. readout: ⟨Z_i⟩ on the first n_classes qubits -> logits (scaled + biased
     by a tiny classical head, standard hybrid practice)

Evaluation is a fused batched pipeline (the hot path both FL engines train).
How it applies an ansatz layer depends on the statevector's size alone:

  * dense (2^nq ≤ ``DENSE_MAX_DIM``): a layer ⊗_q RZ(φ_q)·RY(θ_q) is D·R,
    R = ⊗_q RY(θ_q) a real orthogonal 2^nq × 2^nq matrix and D = ⊗_q RZ(φ_q)
    a diagonal phase into which the CZ ring's ±1 diagonal folds. The
    encoded state ⊗_q RY(x_q)|0⟩ is a real product vector. The state rides
    as a float32 (re, im) pair and each layer is ONE matmul of both halves
    against Rᵀ, a contraction as wide as the state that the MXU runs
    whole, then one elementwise phase;
  * grouped (larger states, where a dense matrix per layer and client is
    too large): each layer's RZ·RY products are ONE (L, nq, 2, 2) gate
    tensor applied in one kron-grouped contraction
    (``sv.apply_1q_layer``), then the ring as a ±1 diagonal
    (``sv.ring_cz_signs`` — CZs commute, the ring is static);
  * readout is one (n_classes, dim) sign-matrix matmul over the
    probabilities (``sv.zexp_signs``) instead of stacked expect_z calls

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

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

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


def _encoding_angles(cfg: ArchConfig, features: jax.Array) -> jax.Array:
    """(B, nq) RY encoding angles: the features, 0 on qubits beyond them."""
    nq = cfg.vqc_qubits
    k = min(cfg.n_features, nq)
    angles = jnp.zeros(features.shape[:-1] + (nq,), jnp.float32)
    return angles.at[..., :k].set(features[..., :k])


def encoding_gates(cfg: ArchConfig, features: jax.Array) -> jax.Array:
    """(B, nq, 2, 2) per-sample RY(x_q) encoding gates (RY(0)=I padding)."""
    return sv.ry_gate(_encoding_angles(cfg, features))


#: the largest statevector whose ansatz layers run as dense real matmuls
#: (one 256 x 256 float32 matrix per layer and client, 256 KiB); a larger
#: state takes the kron-grouped contraction
DENSE_MAX_DIM = 256


def uses_dense_layers(nq: int) -> bool:
    """Whether an nq-qubit circuit applies its layers as dense matmuls."""
    return (1 << nq) <= DENSE_MAX_DIM


@lru_cache(maxsize=None)
def _basis_bits(nq: int) -> np.ndarray:
    """(nq, 2^nq) int32: bit q of basis index i (little-endian)."""
    idx = np.arange(1 << nq, dtype=np.int32)
    return (idx[None, :] >> np.arange(nq, dtype=np.int32)[:, None]) & 1


def _ry_product(theta) -> jax.Array:
    """(..., n) angles -> (..., 2^n, 2^n) Rᵀ of ⊗_q RY(θ_q).

    Rᵀ[j, i] = ∏_q RY(θ_q)[b_q(i), b_q(j)]: cos θ_q/2 where the bits
    agree, ±sin θ_q/2 where they differ, picked by static bit tables and
    multiplied over the qubit axis.
    """
    bits = _basis_bits(theta.shape[-1])
    b_in, b_out = bits[:, :, None], bits[:, None, :]
    half = theta[..., None, None] / 2
    return jnp.prod(jnp.where(b_in == b_out, jnp.cos(half),
                              (b_out - b_in) * jnp.sin(half)), axis=-3)


def _dense_operators(params):
    """Per-layer Rᵀ (L, D, D) and phase (cos, sin) each (L, D).

    Rᵀ is the outer product of the low and the high half-register's
    matrices, so the full (D, D) table is touched once, and autodiff
    keeps only the two halves. The phase of basis state i is the product
    of each qubit's RZ phase e^{∓iφ_q/2} (by bit q) and the CZ ring's
    sign: a product of unit factors, each rounded once, where cos/sin of
    the summed angle lost up to 9e-7 at 8 qubits.
    """
    with jax.named_scope("vqc.dense_layer"):
        theta, phi = params["theta"], params["phi"]
        nq = theta.shape[-1]
        lo = nq // 2
        a_lo = _ry_product(theta[..., :lo])
        a_hi = _ry_product(theta[..., lo:])
        rt = (a_hi[..., :, None, :, None] * a_lo[..., None, :, None, :]
              ).reshape(theta.shape[:-1] + (1 << nq, 1 << nq))
        hp = phi[..., None] / 2
        cos, sin = jnp.cos(hp), jnp.sin(hp)
        phase = jnp.prod(jnp.where(_basis_bits(nq) == 1,
                                   jax.lax.complex(cos, sin),
                                   jax.lax.complex(cos, -sin)), axis=-2)
        ring = sv.ring_cz_signs(nq)
        return rt, ring * phase.real, ring * phase.imag


def _dense_encode(cfg: ArchConfig, features) -> jax.Array:
    """(..., 2, D) (re, im) pair of the product state ⊗_q RY(x_q)|0⟩."""
    with jax.named_scope("vqc.dense_layer"):
        half = _encoding_angles(cfg, features)[..., None] / 2
        amp = jnp.prod(jnp.where(_basis_bits(cfg.vqc_qubits) == 1,
                                 jnp.sin(half), jnp.cos(half)), axis=-2)
        return jnp.stack([amp, jnp.zeros_like(amp)], axis=-2)


def _dense_layer(x, rt, cos, sin):
    """One ansatz layer on an (..., 2, D) (re, im) pair: both halves in
    one matmul against Rᵀ, then the diagonal phase."""
    with jax.named_scope("vqc.dense_layer"):
        y = jnp.matmul(x, rt, precision=sv.HIGHEST)
        re, im = y[..., 0, :], y[..., 1, :]
        return jnp.stack([re * cos - im * sin, re * sin + im * cos], axis=-2)


def _dense_probs(x):
    return x[..., 0, :] ** 2 + x[..., 1, :] ** 2


def _dense_state(cfg: ArchConfig, params, features):
    rt, cos, sin = _dense_operators(params)
    x = _dense_encode(cfg, features)
    for l in range(cfg.vqc_layers):
        x = _dense_layer(x, rt[l], cos[l], sin[l])
    return x


def _grouped_encode(cfg: ArchConfig, features, group: int):
    state = sv.init_state(cfg.vqc_qubits, features.shape[:-1])
    with jax.named_scope("vqc.grouped_layer"):
        return sv.apply_1q_layer(state, encoding_gates(cfg, features), group)


def _grouped_layer(state, gates, ring, group: int):
    with jax.named_scope("vqc.grouped_layer"):
        return sv.apply_1q_layer(state, gates, group) * ring


def _circuit_state_fused(cfg: ArchConfig, params, features, group: int = 2):
    """Batched statevector (B, 2^nq) after encoding + ansatz. ``group`` is
    the kron width of the grouped path; the dense path ignores it."""
    nq, L = cfg.vqc_qubits, cfg.vqc_layers
    if uses_dense_layers(nq):
        x = _dense_state(cfg, params, features)
        return jax.lax.complex(x[..., 0, :], x[..., 1, :])
    state = _grouped_encode(cfg, features, group)
    gates = layer_gates(params)
    ring = sv.ring_cz_signs(nq).astype(sv.CDTYPE)
    for l in range(L):
        state = _grouped_layer(state, gates[l], ring, group)
    return state


def _logits_fused(cfg: ArchConfig, params, features):
    if uses_dense_layers(cfg.vqc_qubits):
        probs = _dense_probs(_dense_state(cfg, params, features))
    else:
        probs = sv.probs(_circuit_state_fused(cfg, params, features))
    exps = jnp.matmul(probs, sv.zexp_signs(cfg.vqc_qubits, cfg.n_classes).T,
                      precision=sv.HIGHEST)
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


@lru_cache(maxsize=None)
def _shift_inserts_np(nq: int):
    """The shift rule's fixed ±π/2 insertions as dense-path operators.

    Returns ``ry`` (D, 2, nq, D): [:, s, q, :] is Rᵀ of RY(±π/2) on qubit
    q, and ``rz_sin`` (2, nq, D): the sine of RZ(±π/2)'s phase on qubit q
    at basis state i (its cosine is √½ everywhere). RY(±π/2)'s entry off
    the diagonal is that same ±√½·(2b_q(i)−1), so one table gives both.
    """
    dim = 1 << nq
    idx = np.arange(dim)
    r = np.sqrt(0.5)
    z = 2 * _basis_bits(nq) - 1
    rz_sin = np.array([1.0, -1.0])[:, None, None] * r * z
    ry = np.zeros((2, nq, dim, dim))
    for q in range(nq):
        ry[:, q, idx, idx] = r
        ry[:, q, idx ^ (1 << q), idx] = rz_sin[:, q]
    return (ry.transpose(2, 0, 1, 3).astype(np.float32),
            rz_sin.astype(np.float32))


def _dense_shift_branches(nq: int):
    """(θ-insert, φ-insert): (..., 2, D) pair -> (2, nq, ..., 2, D)."""
    ry_np, rz_sin_np = _shift_inserts_np(nq)
    r = np.float32(np.sqrt(0.5))

    def theta(x):
        with jax.named_scope("vqc.dense_layer"):
            return jnp.einsum("...j,jsqi->sq...i", x, jnp.asarray(ry_np),
                              precision=sv.HIGHEST)

    def phi(x):
        with jax.named_scope("vqc.dense_layer"):
            sin = jnp.asarray(rz_sin_np).reshape(
                (2, nq) + (1,) * (x.ndim - 2) + (1 << nq,))
            re, im = x[..., 0, :], x[..., 1, :]
            return jnp.stack([r * re - sin * im, sin * re + r * im], axis=-2)

    return theta, phi


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
         shift axis — (2, nq, B, ...) — and pushes the whole stack through
         the remaining suffix layers as one batched fused contraction,
      3. reads out every branch against a precomputed chained observable
         M[b, :] = Σ_c dL[b,c]·w_c·zsign_c (one elementwise pass).

    On the dense path the θ insertions are one matmul against a fixed
    (D, 2, nq, D) stack and the φ insertions an elementwise phase.
    ``chunk > 0`` bounds peak memory by pushing each layer's branch stack
    through its suffix in chunks of that size. ``group`` is the kron-fusion
    width of the grouped path's contractions (4 measures fastest for the
    wide branch stacks; the plain forward defaults to 2). Returns a grads
    pytree matching ``params`` — or ``(loss, grads)`` with
    ``with_loss=True``, the CE loss falling out of the base sweep's
    logits for free (what the FL engines' grad_fn contract wants).
    """
    nq, L = cfg.vqc_qubits, cfg.vqc_layers
    feats, labels = batch["features"], batch["labels"]

    if uses_dense_layers(nq):
        rt, cos, sin = _dense_operators(params)

        def layer(st, l):
            return _dense_layer(st, rt[l], cos[l], sin[l])

        probs = _dense_probs
        state = _dense_encode(cfg, feats)
        theta_branches, phi_branches = _dense_shift_branches(nq)
    else:
        gates = layer_gates(params)
        ring = sv.ring_cz_signs(nq).astype(sv.CDTYPE)

        def layer(st, l):
            return _grouped_layer(st, gates[l], ring, group)

        probs = sv.probs
        state = _grouped_encode(cfg, feats, group)
        half = jnp.pi / 2
        ry_pm = jnp.stack([sv.ry_gate(half), sv.ry_gate(-half)])
        rz_pm = jnp.stack([sv.rz_gate(half), sv.rz_gate(-half)])

        def inserts(pm):
            return lambda st: jnp.stack([
                jnp.stack([sv.apply_1q(st, pm[s], q) for q in range(nq)])
                for s in range(2)])

        theta_branches, phi_branches = inserts(ry_pm), inserts(rz_pm)

    # ONE base sweep yields the per-layer branch inputs AND the readout:
    # logits0, dL/dlogits, and the (closed-form) head grads all derive
    # from the final state — no separate forward or reverse pass
    layer_in = []
    for l in range(L):
        layer_in.append(state)
        state = layer(state, l)
    zsigns = sv.zexp_signs(nq, cfg.n_classes)
    exps = jnp.matmul(probs(state), zsigns.T, precision=sv.HIGHEST)  # (B, C)
    logits0 = params["w_out"] * exps + params["b_out"]
    p = jax.nn.softmax(logits0, axis=-1)
    dL = (p - jax.nn.one_hot(labels, cfg.n_classes)) / feats.shape[0]
    # chained diagonal observable: Σ_shift dL·logits needs only
    # Σ_{b,i} |ψ|²[b,i] · M[b,i] per branch (b_out cancels in the ± diff)
    M = jnp.einsum("bc,ci->bi", dL * params["w_out"], zsigns,
                   precision=sv.HIGHEST)

    def branch_vals(stack, l0):
        """(2, nq, B, ...) branch stack -> suffix layers l0.. -> (2, nq)."""
        def suffix(st):
            for l in range(l0, L):
                st = layer(st, l)
            return jnp.sum(probs(st) * M, axis=(-2, -1))
        if chunk and chunk > 0:
            flat = stack.reshape((-1,) + stack.shape[2:])
            return jax.lax.map(suffix, flat,
                               batch_size=chunk).reshape(2, nq)
        return suffix(stack)

    g_theta, g_phi = [], []
    for l in range(L):
        # θ_l,q: RY(±π/2) on qubit q BEFORE layer l (RY(θ±s) = RY(θ)RY(±s))
        vt = branch_vals(theta_branches(layer_in[l]), l)
        g_theta.append(0.5 * (vt[0] - vt[1]))
        # φ_l,q: RZ(±π/2) AFTER layer l (RZ(φ±s) = RZ(±s)RZ(φ), and RZ
        # commutes through the diagonal CZ ring), i.e. before layer l+1
        nxt = layer_in[l + 1] if l + 1 < L else state
        vp = branch_vals(phi_branches(nxt), l + 1)
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
