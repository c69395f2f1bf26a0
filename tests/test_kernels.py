"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU; TPU target).

Per assignment: sweep shapes/dtypes with hypothesis and assert_allclose
against the ref.py oracle for every kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep
from hypothesis import given, settings, strategies as st

from repro.kernels import (apply_gate, apply_gate_layer, otp_xor_mac,
                           ssd_scan, swa_attention)
from repro.kernels.otp_xor.ops import DEFAULT_BLOCK_ROWS
from repro.kernels.otp_xor.ref import otp_xor_mac_ref
from repro.kernels.swa_attention.ops import _fold, _repeat_kv, _unfold
from repro.kernels.swa_attention.ref import swa_attention_ref
from repro.models.blocks import ssd_ref
from repro.quantum import statevector as sv
from repro.security.mac import poly_mac_u32

# ---------------------------------------------------------------------------
# otp_xor: fused XOR + MAC must be bit-identical to the security layer
# ---------------------------------------------------------------------------

@given(st.integers(1, 5000), st.integers(0, 2**32 - 1), st.integers(0, 2**32 - 1))
@settings(max_examples=15)
def test_otp_xor_mac_matches_ref(n, rk, sk):
    key = jax.random.key(n)
    msg = jax.random.bits(key, (n,), jnp.uint32)
    pad = jax.random.bits(jax.random.fold_in(key, 1), (n,), jnp.uint32)
    ct, tag = otp_xor_mac(msg, pad, jnp.uint32(rk), jnp.uint32(sk))
    wpb = DEFAULT_BLOCK_ROWS * 128
    nb = max((n + wpb - 1) // wpb, 1)
    msgp = jnp.zeros((nb * wpb,), jnp.uint32).at[:n].set(msg)
    padp = jnp.zeros((nb * wpb,), jnp.uint32).at[:n].set(pad)
    ct_r, tag_r = otp_xor_mac_ref(msgp, padp, jnp.uint32(rk), jnp.uint32(sk))
    assert bool(jnp.all(ct == ct_r[:n]))
    assert int(tag) == int(tag_r)


def test_otp_xor_mac_is_decryptable():
    n = 3000
    msg = jax.random.bits(jax.random.key(0), (n,), jnp.uint32)
    pad = jax.random.bits(jax.random.key(1), (n,), jnp.uint32)
    ct, _ = otp_xor_mac(msg, pad, jnp.uint32(1), jnp.uint32(2))
    assert bool(jnp.all((ct ^ pad) == msg))


# ---------------------------------------------------------------------------
# statevec_gate
# ---------------------------------------------------------------------------

@given(st.integers(2, 11), st.integers(0, 10),
       st.floats(0.0, 3.1), st.floats(-3.1, 3.1), st.floats(-3.1, 3.1))
@settings(max_examples=20)
def test_statevec_gate_matches_sim(nq, q, t, p, l):
    q = q % nq
    key = jax.random.PRNGKey(nq * 31 + q)
    re, im = jax.random.normal(key, (2, 2 ** nq))
    state = (re + 1j * im).astype(jnp.complex64)
    state = state / jnp.linalg.norm(state)
    g = sv.u3_gate(t, p, l)
    got = apply_gate(state, g, q)
    want = sv.apply_1q(state, g, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_statevec_gate_vjp_matches_sim():
    nq, q = 6, 3
    key = jax.random.PRNGKey(5)
    re, im = jax.random.normal(key, (2, 2 ** nq))
    state = ((re + 1j * im) / jnp.linalg.norm(re + 1j * im)).astype(jnp.complex64)

    def loss_k(theta):
        out = apply_gate(state, sv.ry_gate(theta), q)
        return jnp.sum(jnp.abs(out[: 2 ** (nq - 1)]) ** 2)

    def loss_r(theta):
        out = sv.apply_1q(state, sv.ry_gate(theta), q)
        return jnp.sum(jnp.abs(out[: 2 ** (nq - 1)]) ** 2)

    gk = jax.grad(loss_k)(0.7)
    gr = jax.grad(loss_r)(0.7)
    assert abs(float(gk) - float(gr)) < 1e-5


@given(st.integers(2, 11), st.integers(0, 30))
@settings(max_examples=12)
def test_statevec_fused_layer_matches_sim(nq, seed):
    """apply_gate_layer (one launch, all qubits) == sequential apply_1q."""
    key = jax.random.PRNGKey(seed)
    re, im = jax.random.normal(key, (2, 2 ** nq))
    state = (re + 1j * im).astype(jnp.complex64)
    state = state / jnp.linalg.norm(state)
    angles = jax.random.uniform(jax.random.fold_in(key, 1), (3, nq),
                                minval=-3.0, maxval=3.0)
    gates = sv.u3_gate(angles[0], angles[1], angles[2])
    got = apply_gate_layer(state, gates)
    want = state
    for q in range(nq):
        want = sv.apply_1q(want, gates[q], q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


# ---------------------------------------------------------------------------
# swa_attention
# ---------------------------------------------------------------------------

@given(st.sampled_from([64, 128, 256]), st.integers(1, 4), st.integers(1, 4),
       st.sampled_from([16, 32, 64]), st.sampled_from([0, 16, 64, 100]),
       st.sampled_from([jnp.float32, jnp.bfloat16]))
@settings(max_examples=12)
def test_swa_matches_ref(S, H, KVd, hd, W, dtype):
    KV = H // KVd if H % KVd == 0 and H // KVd > 0 else H
    B = 2
    key = jax.random.PRNGKey(S + H)
    q = (0.5 * jax.random.normal(key, (B, S, H, hd))).astype(dtype)
    k = (0.5 * jax.random.normal(jax.random.fold_in(key, 1),
                                 (B, S, KV, hd))).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, S, KV, hd)).astype(dtype)
    got = swa_attention(q, k, v, window=W)
    want = _unfold(swa_attention_ref(
        _fold(q), _fold(_repeat_kv(k, H)), _fold(_repeat_kv(v, H)),
        window=W), B, H)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_swa_grads_match_ref():
    B, S, H, hd, W = 1, 128, 2, 32, 32
    key = jax.random.PRNGKey(0)
    q = 0.5 * jax.random.normal(key, (B, S, H, hd))
    k = 0.5 * jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hd))

    g_kernel = jax.grad(lambda q_: jnp.sum(
        swa_attention(q_, k, v, window=W) ** 2))(q)
    g_ref = jax.grad(lambda q_: jnp.sum(_unfold(swa_attention_ref(
        _fold(q_), _fold(k), _fold(v), window=W), B, H) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_ref),
                               atol=1e-4)


def test_swa_window_actually_limits_context():
    """Token far beyond the window must not influence the output."""
    B, S, H, hd, W = 1, 256, 1, 16, 32
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hd))
    o1 = swa_attention(q, k, v, window=W)
    k2 = k.at[:, 0].set(k[:, 0] + 100.0)     # outside every later window
    v2 = v.at[:, 0].set(v[:, 0] - 50.0)
    o2 = swa_attention(q, k2, v2, window=W)
    # positions >= W unaffected
    assert float(jnp.max(jnp.abs(o1[:, W:] - o2[:, W:]))) < 1e-5


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@given(st.sampled_from([64, 128, 256]), st.sampled_from([1, 2, 4]),
       st.sampled_from([2, 4]), st.sampled_from([16, 32]),
       st.sampled_from([16, 64]), st.sampled_from([32, 64, 128]))
@settings(max_examples=12)
def test_ssd_matches_ref(S, G, Hg, P, N, chunk):
    H = G * Hg
    B = 2
    key = jax.random.PRNGKey(S + H + N)
    x = 0.5 * jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (B, S, H)))
    A = -jnp.exp(0.3 * jax.random.normal(jax.random.fold_in(key, 2), (H,)))
    Bv = 0.3 * jax.random.normal(jax.random.fold_in(key, 3), (B, S, G, N))
    Cv = 0.3 * jax.random.normal(jax.random.fold_in(key, 4), (B, S, G, N))
    y_k, st_k = ssd_scan(x, dt, A, Bv, Cv, chunk=chunk)
    y_r, st_r = ssd_ref(x, dt, A, Bv, Cv, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=3e-5)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_r), atol=3e-5)


@pytest.mark.parametrize("q", [32, 128])
def test_ssd_prefix_sum_matmul_matches_cumsum(q):
    """The compiled kernel's prefix sum (a mask matmul: Mosaic has no
    cumsum) is cumsum up to f32 summation order."""
    from repro.kernels.ssd_scan.kernel import prefix_sum_matmul
    key = jax.random.PRNGKey(q)
    dA = -jax.nn.softplus(jax.random.normal(key, (8, q)))
    np.testing.assert_allclose(np.asarray(prefix_sum_matmul(dA)),
                               np.asarray(jnp.cumsum(dA, axis=1)),
                               rtol=1e-6, atol=1e-6)


def test_ssd_chunk_invariance():
    """The chunked algorithm must be exact: different chunk sizes agree."""
    B, S, H, G, P, N = 1, 128, 2, 1, 16, 32
    key = jax.random.PRNGKey(9)
    x = 0.5 * jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, S, H)))
    A = -jnp.exp(0.3 * jax.random.normal(jax.random.fold_in(key, 2), (H,)))
    Bv = 0.3 * jax.random.normal(jax.random.fold_in(key, 3), (B, S, G, N))
    Cv = 0.3 * jax.random.normal(jax.random.fold_in(key, 4), (B, S, G, N))
    y16, _ = ssd_ref(x, dt, A, Bv, Cv, chunk=16)
    y128, _ = ssd_ref(x, dt, A, Bv, Cv, chunk=128)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y128), atol=2e-5)


def test_ssd_matches_naive_recurrence():
    """Chunked SSD vs the literal token-by-token SSM recurrence."""
    B, S, H, G, P, N = 1, 32, 2, 1, 8, 16
    key = jax.random.PRNGKey(11)
    x = 0.5 * jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, S, H)))
    A = -jnp.exp(0.3 * jax.random.normal(jax.random.fold_in(key, 2), (H,)))
    Bv = 0.3 * jax.random.normal(jax.random.fold_in(key, 3), (B, S, G, N))
    Cv = 0.3 * jax.random.normal(jax.random.fold_in(key, 4), (B, S, G, N))

    state = np.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        dA = np.exp(np.asarray(dt[:, t]) * np.asarray(A)[None])   # (B,H)
        Bt = np.asarray(Bv[:, t, 0])                              # (B,N) G=1
        Ct = np.asarray(Cv[:, t, 0])
        xt = np.asarray(x[:, t])                                  # (B,H,P)
        state = state * dA[..., None, None] + \
            (np.asarray(dt[:, t])[..., None] * xt)[..., None] * Bt[:, None, None, :]
        ys.append(np.einsum("bhpn,bn->bhp", state, Ct))
    y_naive = np.stack(ys, axis=1)
    y_k, st_k = ssd_scan(x, dt, A, Bv, Cv, chunk=16)
    np.testing.assert_allclose(np.asarray(y_k), y_naive, atol=3e-5)
    np.testing.assert_allclose(np.asarray(st_k), state, atol=3e-5)


def test_ssd_grads_flow():
    B, S, H, G, P, N = 1, 64, 2, 1, 8, 16
    key = jax.random.PRNGKey(13)
    x = 0.5 * jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, S, H)))
    A = -jnp.exp(0.3 * jax.random.normal(jax.random.fold_in(key, 2), (H,)))
    Bv = 0.3 * jax.random.normal(jax.random.fold_in(key, 3), (B, S, G, N))
    Cv = 0.3 * jax.random.normal(jax.random.fold_in(key, 4), (B, S, G, N))
    gk = jax.grad(lambda x_: jnp.sum(ssd_scan(x_, dt, A, Bv, Cv, chunk=32)[0] ** 2))(x)
    gr = jax.grad(lambda x_: jnp.sum(ssd_ref(x_, dt, A, Bv, Cv, chunk=32)[0] ** 2))(x)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-4)
