"""Fused VQC evaluation engine vs the per-gate reference path.

These are the PR's acceptance tests, deliberately hypothesis-free so they
run in the tier-1 gate even where the optional dev deps are absent:

  * fused layer/diagonal/readout circuit state == per-gate statevector
    path (atol 1e-6) on random circuits
  * vectorized parameter-shift == serial ``lax.map`` rule == autodiff
  * the Pallas fused-layer kernel == the simulator, including the
    beyond-VMEM fallback and the custom VJP
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import apply_gate_layer, otp_xor_mac
from repro.kernels.otp_xor.ops import DEFAULT_BLOCK_ROWS
from repro.kernels.otp_xor.ref import otp_xor_mac_ref
from repro.models import get_config
from repro.quantum import (
    expect_z, expect_z_all, parameter_shift_grad, parameter_shift_grad_serial,
    vqc_init, vqc_logits, vqc_loss,
)
from repro.quantum import statevector as sv
from repro.quantum.vqc import (
    _circuit_state, _circuit_state_fused, uses_dense_layers,
)


def _rand_state(key, shape):
    re, im = jax.random.normal(key, (2,) + shape)
    state = (re + 1j * im).astype(jnp.complex64)
    return state / jnp.linalg.norm(state, axis=-1, keepdims=True)


# --- fused simulator primitives ---------------------------------------------

@pytest.mark.parametrize("nq,group", [(2, 1), (4, 2), (5, 2), (7, 3), (8, 4)])
def test_fused_layer_matches_sequential_gates(rng_key, nq, group):
    state = _rand_state(jax.random.fold_in(rng_key, nq), (3, 2 ** nq))
    angles = jax.random.uniform(jax.random.fold_in(rng_key, group), (3, nq),
                                minval=-3.0, maxval=3.0)
    gates = sv.u3_gate(angles[0], angles[1], angles[2])
    got = sv.apply_1q_layer(state, gates, group=group)
    want = state
    for q in range(nq):
        want = sv.apply_1q(want, gates[q], q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_fused_layer_batched_gates(rng_key):
    """Per-sample gates (the encoding layer's case) broadcast correctly."""
    nq, B = 5, 4
    state = _rand_state(rng_key, (B, 2 ** nq))
    th = jax.random.uniform(rng_key, (B, nq), maxval=np.pi)
    gates = sv.ry_gate(th)                                   # (B, nq, 2, 2)
    got = sv.apply_1q_layer(state, gates)
    want = state
    for q in range(nq):
        want = sv.apply_1q(want, sv.ry_gate(th[:, q]), q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_ring_diagonal_matches_cz_ring(rng_key):
    for nq in (2, 3, 5, 8):
        state = _rand_state(jax.random.fold_in(rng_key, nq), (2 ** nq,))
        want = state
        for q in range(nq):
            want = sv.apply_cz(want, q, (q + 1) % nq)
        got = state * sv.ring_cz_signs(nq).astype(jnp.complex64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-7)


def test_readout_matrix_matches_expect_z(rng_key):
    nq, n_obs = 6, 4
    state = _rand_state(rng_key, (3, 2 ** nq))
    got = expect_z_all(state, n_obs)
    want = jnp.stack([expect_z(state, q) for q in range(n_obs)], axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# --- fused circuit vs per-gate ----------------------------------------------

@pytest.mark.parametrize("nq,L,nf", [(4, 2, 4), (5, 1, 5), (8, 2, 8),
                                     (3, 3, 2), (8, 3, 8), (9, 1, 8)])
def test_fused_circuit_state_matches_per_gate(rng_key, nq, L, nf):
    """Acceptance: fused circuit state == per-gate path within 1e-6, on
    the dense path (up to 8 qubits, the benchmark's 8 x 3 among them) and
    the grouped one (9)."""
    cfg = get_config("vqc-satqfl").replace(vqc_qubits=nq, vqc_layers=L,
                                           n_features=nf)
    params = vqc_init(cfg, jax.random.fold_in(rng_key, nq))
    feats = jax.random.uniform(rng_key, (5, nf), maxval=np.pi)
    fused = _circuit_state_fused(cfg, params, feats)
    pergate = jax.vmap(lambda x: _circuit_state(cfg, params, x))(feats)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(pergate),
                               atol=1e-6)
    lf = vqc_logits(cfg, params, feats, fused=True)
    lp = vqc_logits(cfg, params, feats, fused=False)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lp), atol=1e-5)


# --- vectorized parameter-shift ---------------------------------------------

@pytest.mark.parametrize("nq,L", [(4, 2), (5, 1), (3, 3), (8, 3), (9, 1)])
def test_vectorized_shift_matches_serial_and_autodiff(rng_key, nq, L):
    """Acceptance: the vectorized branch-stacked rule == the serial lax.map
    rule == autodiff, and the chunked variant == the unchunked one, on the
    dense path (up to 8 qubits) and the grouped one (9)."""
    cfg = get_config("vqc-satqfl").replace(vqc_qubits=nq, vqc_layers=L,
                                           n_features=nq)
    params = vqc_init(cfg, jax.random.fold_in(rng_key, L))
    feats = jax.random.uniform(rng_key, (6, nq), maxval=np.pi)
    labels = jax.random.randint(rng_key, (6,), 0, cfg.n_classes)
    batch = {"features": feats, "labels": labels}
    g_vec = parameter_shift_grad(cfg, params, batch)
    g_ser = parameter_shift_grad_serial(cfg, params, batch)
    g_chk = parameter_shift_grad(cfg, params, batch, chunk=3)
    g_auto = jax.grad(lambda p: vqc_loss(cfg, p, batch))(params)
    for k in ("theta", "phi"):
        np.testing.assert_allclose(np.asarray(g_vec[k]), np.asarray(g_ser[k]),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(g_vec[k]), np.asarray(g_auto[k]),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(g_chk[k]), np.asarray(g_vec[k]),
                                   atol=1e-6)
    for k in ("w_out", "b_out"):    # closed-form head grads
        np.testing.assert_allclose(np.asarray(g_vec[k]), np.asarray(g_auto[k]),
                                   atol=2e-5)


@pytest.mark.parametrize("nq,path", [(3, "dense"), (8, "dense"),
                                     (9, "grouped")])
def test_layer_path_follows_qubit_count(nq, path):
    """Ansatz layers run as dense matmuls up to 8 qubits and kron-grouped
    above, by the qubit count alone: no argument of the forward, the
    shift rule or autodiff changes the path (read from the named scope
    each path's layers carry in the lowered program)."""
    cfg = get_config("vqc-satqfl").replace(vqc_qubits=nq, vqc_layers=1,
                                           n_features=3)
    params = vqc_init(cfg, jax.random.PRNGKey(0))
    feats = jnp.zeros((2, 3), jnp.float32)
    batch = {"features": feats, "labels": jnp.zeros((2,), jnp.int32)}
    programs = [lambda p: vqc_logits(cfg, p, feats),
                lambda p: jax.grad(lambda q: vqc_loss(cfg, q, batch))(p)]
    programs += [lambda p, g=g: _circuit_state_fused(cfg, p, feats, group=g)
                 for g in (1, 2, 3)]
    programs += [lambda p, g=g, c=c: parameter_shift_grad(cfg, p, batch,
                                                          chunk=c, group=g)
                 for g in (2, 4) for c in (0, 3)]
    other = {"dense": "grouped", "grouped": "dense"}[path]
    assert uses_dense_layers(nq) == (path == "dense")
    for program in programs:
        text = jax.jit(program).lower(params).as_text(debug_info=True)
        assert f"vqc.{path}_layer" in text
        assert f"vqc.{other}_layer" not in text


# --- fused-layer Pallas kernel ----------------------------------------------

def test_kernel_fused_layer_matches_sim(rng_key):
    for nq in (3, 6, 10):
        state = _rand_state(jax.random.fold_in(rng_key, nq), (2 ** nq,))
        angles = jax.random.uniform(jax.random.fold_in(rng_key, nq + 50),
                                    (3, nq), minval=-3.0, maxval=3.0)
        gates = sv.u3_gate(angles[0], angles[1], angles[2])
        got = apply_gate_layer(state, gates)
        want = state
        for q in range(nq):
            want = sv.apply_1q(want, gates[q], q)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)


def test_kernel_fused_layer_fallback_beyond_vmem(rng_key):
    """States too large to stay resident take the gate-by-gate kernel
    sweep — same answer."""
    nq = 14                                 # 2^14 > MAX_FUSED_DIM
    state = _rand_state(rng_key, (2 ** nq,))
    angles = jax.random.uniform(rng_key, (3, nq), minval=-2.0, maxval=2.0)
    gates = sv.u3_gate(angles[0], angles[1], angles[2])
    got = apply_gate_layer(state, gates)
    want = state
    for q in range(nq):
        want = sv.apply_1q(want, gates[q], q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_kernel_fused_layer_vjp_matches_sim(rng_key):
    nq = 6
    state = _rand_state(rng_key, (2 ** nq,))

    def gates_of(theta):
        return jnp.stack([sv.ry_gate(theta * (q + 1)) for q in range(nq)])

    def loss_k(theta):
        out = apply_gate_layer(state, gates_of(theta))
        return jnp.sum(jnp.abs(out[: 2 ** (nq - 1)]) ** 2)

    def loss_r(theta):
        out = state
        g = gates_of(theta)
        for q in range(nq):
            out = sv.apply_1q(out, g[q], q)
        return jnp.sum(jnp.abs(out[: 2 ** (nq - 1)]) ** 2)

    gk = jax.grad(loss_k)(0.37)
    gr = jax.grad(loss_r)(0.37)
    assert abs(float(gk) - float(gr)) < 1e-5


# --- retiled otp_xor ---------------------------------------------------------

def test_otp_xor_mac_multiblock_and_tilings_agree():
    """A stream spanning several grid steps, at the default and a narrow
    tiling: ciphertext identical; tags match the ref for EACH padded
    length (the tag covers the padded stream, so the block size is part of
    the wire format). Slow: two fresh kernel+ref jit instantiations."""
    n = DEFAULT_BLOCK_ROWS * 128 + 17
    msg = jax.random.bits(jax.random.key(7), (n,), jnp.uint32)
    pad = jax.random.bits(jax.random.key(8), (n,), jnp.uint32)
    for rows in (64, DEFAULT_BLOCK_ROWS):
        ct, tag = otp_xor_mac(msg, pad, jnp.uint32(9), jnp.uint32(11),
                              block_rows=rows)
        wpb = rows * 128
        nb = (n + wpb - 1) // wpb
        msgp = jnp.zeros((nb * wpb,), jnp.uint32).at[:n].set(msg)
        padp = jnp.zeros((nb * wpb,), jnp.uint32).at[:n].set(pad)
        ct_r, tag_r = otp_xor_mac_ref(msgp, padp, jnp.uint32(9),
                                      jnp.uint32(11))
        assert bool(jnp.all(ct == ct_r[:n]))
        assert int(tag) == int(tag_r)
