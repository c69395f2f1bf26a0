"""Quantum layer: statevector invariants, VQC gradients, QKD, teleportation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep
from hypothesis import given, settings, strategies as st

from repro.models import get_config
from repro.quantum import (
    apply_cnot, apply_cz, apply_h, apply_ry, apply_rz, apply_u3,
    apply_1q_layer, bb84_keygen, expect_z, init_state, probs, ring_cz_signs,
    sample_measure, teleport_params, teleport_state, vqc_init, vqc_logits,
    vqc_loss, parameter_shift_grad,
)
from repro.quantum import statevector as sv
from repro.quantum.statevector import measure_qubit
from repro.quantum.teleport import decode_state, u3_col, fidelity


def _norm(state):
    return float(jnp.sum(probs(state)))


@given(st.integers(2, 8), st.integers(0, 7),
       st.floats(-3.1, 3.1), st.floats(-3.1, 3.1), st.floats(-3.1, 3.1))
def test_unitarity_preserves_norm(nq, q, t, p, l):
    q = q % nq
    state = init_state(nq)
    state = apply_h(state, q)
    state = apply_u3(state, t, p, l, q)
    state = apply_cz(state, q, (q + 1) % nq)
    state = apply_cnot(state, q, (q + 1) % nq)
    assert abs(_norm(state) - 1.0) < 1e-5


def test_bell_state():
    state = init_state(2)
    state = apply_h(state, 0)
    state = apply_cnot(state, 0, 1)
    p = np.asarray(probs(state))
    assert np.allclose(p, [0.5, 0, 0, 0.5], atol=1e-6)


def test_expect_z_basis_states():
    state = init_state(3)                      # |000>
    assert float(expect_z(state, 0)) == pytest.approx(1.0)
    state = apply_u3(state, np.pi, 0.0, 0.0, 1)   # flip qubit 1
    assert float(expect_z(state, 1)) == pytest.approx(-1.0, abs=1e-6)
    assert float(expect_z(state, 0)) == pytest.approx(1.0, abs=1e-6)


def test_measure_collapse(rng_key):
    state = apply_h(init_state(1), 0)
    out, collapsed = measure_qubit(rng_key, state, 0)
    assert abs(_norm(collapsed) - 1.0) < 1e-5
    p = np.asarray(probs(collapsed))
    assert p[int(out)] == pytest.approx(1.0, abs=1e-5)


def test_sampling_distribution(rng_key):
    state = apply_ry(init_state(1), 2 * np.arccos(np.sqrt(0.75)), 0)
    # P(|0>) = 0.75
    s = sample_measure(rng_key, state, 4000)
    frac0 = float(jnp.mean((s == 0).astype(jnp.float32)))
    assert abs(frac0 - 0.75) < 0.03


# --- fused evaluation engine ------------------------------------------------

@given(st.integers(2, 8), st.integers(1, 3), st.integers(1, 4), st.integers(0, 50))
@settings(max_examples=15)
def test_fused_layer_matches_per_gate(nq, group, b, seed):
    """apply_1q_layer (kron-grouped one-shot contraction) == sequential
    apply_1q, for random per-qubit gates on random batched states."""
    key = jax.random.PRNGKey(seed)
    re, im = jax.random.normal(key, (2, b, 2 ** nq))
    state = (re + 1j * im).astype(jnp.complex64)
    state = state / jnp.linalg.norm(state, axis=-1, keepdims=True)
    angles = jax.random.uniform(jax.random.fold_in(key, 1), (3, nq),
                                minval=-3.0, maxval=3.0)
    gates = sv.u3_gate(angles[0], angles[1], angles[2])     # (nq, 2, 2)
    got = apply_1q_layer(state, gates, group=group)
    want = state
    for q in range(nq):
        want = sv.apply_1q(want, gates[q], q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@given(st.integers(2, 9), st.integers(0, 50))
@settings(max_examples=10)
def test_ring_diagonal_matches_cz_ring(nq, seed):
    key = jax.random.PRNGKey(seed)
    re, im = jax.random.normal(key, (2, 2 ** nq))
    state = (re + 1j * im).astype(jnp.complex64)
    state = state / jnp.linalg.norm(state)
    want = state
    for q in range(nq):
        want = apply_cz(want, q, (q + 1) % nq)
    got = state * ring_cz_signs(nq).astype(jnp.complex64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-7)


# --- VQC --------------------------------------------------------------------

def test_vqc_parameter_shift_matches_autodiff(rng_key):
    cfg = get_config("vqc-satqfl").replace(vqc_qubits=4, vqc_layers=2,
                                           n_features=4)
    params = vqc_init(cfg, rng_key)
    feats = jax.random.uniform(rng_key, (8, 4), maxval=np.pi)
    labels = jax.random.randint(rng_key, (8,), 0, cfg.n_classes)
    batch = {"features": feats, "labels": labels}
    g_auto = jax.grad(lambda p: vqc_loss(cfg, p, batch))(params)
    g_ps = parameter_shift_grad(cfg, params, batch)
    for k in ("theta", "phi"):
        np.testing.assert_allclose(np.asarray(g_auto[k]), np.asarray(g_ps[k]),
                                   atol=2e-5)


def test_vqc_trains(rng_key):
    cfg = get_config("vqc-satqfl").replace(vqc_qubits=4, vqc_layers=2,
                                           n_features=4, n_classes=2)
    params = vqc_init(cfg, rng_key)
    # separable toy task
    f0 = jax.random.uniform(rng_key, (32, 4), minval=0.2, maxval=1.0)
    f1 = jax.random.uniform(rng_key, (32, 4), minval=2.0, maxval=3.0)
    feats = jnp.concatenate([f0, f1])
    labels = jnp.concatenate([jnp.zeros(32, jnp.int32),
                              jnp.ones(32, jnp.int32)])
    batch = {"features": feats, "labels": labels}
    l0 = float(vqc_loss(cfg, params, batch))
    for i in range(30):
        g = jax.grad(lambda p: vqc_loss(cfg, p, batch))(params)
        params = jax.tree_util.tree_map(lambda p, gg: p - 0.3 * gg, params, g)
    l1 = float(vqc_loss(cfg, params, batch))
    assert l1 < l0 - 0.05


# --- QKD ---------------------------------------------------------------------

def test_bb84_clean_channel(rng_key):
    res = bb84_keygen(rng_key, 2048)
    assert float(res.qber) == 0.0
    assert 800 < int(res.key_len) < 1300     # ~half sift


def test_bb84_eavesdropper_detected(rng_key):
    res = bb84_keygen(rng_key, 4096, eavesdrop=True)
    assert 0.18 < float(res.qber) < 0.32     # 25% expected


# --- teleportation -----------------------------------------------------------

@given(st.floats(0.05, 3.0), st.floats(-3.1, 3.1), st.integers(0, 10**6))
def test_teleportation_exact(theta, phi, seed):
    key = jax.random.PRNGKey(seed)
    received, fid, m0, m1 = teleport_state(key, theta, phi)
    assert float(fid) > 1.0 - 1e-5
    td, pd = decode_state(received)
    assert abs(float(td) - theta) < 1e-3
    # phase only defined when sin(theta/2) != 0
    assert abs(((float(pd) - phi + np.pi) % (2 * np.pi)) - np.pi) < 2e-3


def test_teleport_params_batch(rng_key):
    t = jax.random.uniform(rng_key, (64,), minval=0.1, maxval=3.0)
    p = jax.random.uniform(rng_key, (64,), minval=-3.0, maxval=3.0)
    td, pd, fid = teleport_params(rng_key, t, p)
    assert float(fid) > 1.0 - 1e-5
    np.testing.assert_allclose(np.asarray(td), np.asarray(t), atol=1e-3)
