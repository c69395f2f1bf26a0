"""Constellation-batched round executor vs the per-client oracle.

The PR's acceptance tests:

  * batched-vs-per-client metric parity ≤ 1e-6 on ALL four scheduling
    modes and BOTH gradient rules,
    with exact comm/participant accounting equality;
  * the security layer stays transparent and bit-identical under the
    batched executor;
  * the vectorized parameter-shift rule is vmap-safe over the stacked
    client axis (grads == per-client autodiff);
  * the tiled multi-stage fused-layer kernel matches the per-gate oracle
    at small forced-tiling sizes and at 20 qubits, on
    single and client-stacked states, and the per-gate fallback is
    FLAGGED, never silent.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.constellation import build_trace
from repro.core import SatQFLConfig, SatQFLTrainer
from repro.data import dirichlet_partition, make_statlog, server_split
from repro.kernels import apply_gate_layer
from repro.kernels.statevec_gate import ops as sv_ops
from repro.kernels.statevec_gate.kernel import apply_layer_planes_tiled
from repro.models import get_config, get_model
from repro.quantum import parameter_shift_grad, vqc_init, vqc_loss
from repro.quantum import statevector as sv


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("vqc-satqfl").replace(vqc_qubits=4, vqc_layers=1,
                                           n_features=4)
    api = get_model(cfg)
    X, y = make_statlog(n_features=4)
    Xc, yc, server = server_split(X, y)
    trace = build_trace(n_sats=12, n_planes=4, duration_s=1800, step_s=60)
    sats = dirichlet_partition(Xc, yc, 12)
    return cfg, api, trace, sats, server


def _parity_run(setup, mode, grad_method):
    cfg, api, trace, sats, server = setup
    fl = SatQFLConfig(n_rounds=2, local_steps=3, batch_size=8, mode=mode,
                      grad_method=grad_method)
    hists = {}
    for batched in (False, True):
        tr = SatQFLTrainer(cfg, api, fl, trace, sats, server,
                           batched=batched)
        assert tr.batched is batched
        hists[batched] = tr.run()
    for m_pc, m_b in zip(hists[False], hists[True]):
        # accounting must be EXACT — the batched path reorders float
        # training math only, never the comm model
        assert m_b.comm_s == m_pc.comm_s
        assert m_b.security_s == m_pc.security_s
        assert m_b.participants == m_pc.participants
        np.testing.assert_allclose(m_b.server_val_loss, m_pc.server_val_loss,
                                   atol=1e-6)
        np.testing.assert_allclose(m_b.server_val_acc, m_pc.server_val_acc,
                                   atol=1e-6)
        np.testing.assert_allclose(m_b.server_test_acc, m_pc.server_test_acc,
                                   atol=1e-6)


@pytest.mark.parametrize("mode", ["qfl", "sim", "seq", "async"])
def test_batched_parity_autodiff(setup, mode):
    """Acceptance: batched-vs-oracle metric parity ≤ 1e-6, all modes."""
    _parity_run(setup, mode, "autodiff")


@pytest.mark.parametrize("mode", ["qfl", "sim", "seq", "async"])
def test_batched_parity_param_shift(setup, mode):
    """Same parity under the hardware-faithful parameter-shift rule."""
    _parity_run(setup, mode, "param_shift")


def test_batched_security_transparent_and_identical(setup):
    """QKD-OTP under the batched executor: Algorithm 2 runs per edge on
    row slices — the aggregated model must equal the per-client one to
    float-accumulation tolerance, and security time exactly."""
    cfg, api, trace, sats, server = setup
    fl = SatQFLConfig(n_rounds=2, local_steps=3, batch_size=8, mode="sim",
                      security="qkd")
    params, sec = {}, {}
    for batched in (False, True):
        tr = SatQFLTrainer(cfg, api, fl, trace, sats, server,
                           batched=batched)
        tr.run()
        params[batched] = tr.global_params
        sec[batched] = tr.log.security_s
    assert sec[True] == sec[False] > 0
    for a, b in zip(jax.tree_util.tree_leaves(params[False]),
                    jax.tree_util.tree_leaves(params[True])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_custom_sampler_forces_per_client(setup):
    """A custom sample_batch has no padded-bound contract: the trainer
    must drop to the per-client oracle (and still run)."""
    cfg, api, trace, sats, server = setup

    def sampler(data, key, batch_size):
        n = next(iter(data.values())).shape[0]
        idx = jax.random.randint(key, (batch_size,), 0, n)
        return {k: v[idx] for k, v in data.items()}

    fl = SatQFLConfig(n_rounds=1, local_steps=2, batch_size=8, mode="sim")
    tr = SatQFLTrainer(cfg, api, fl, trace, sats, server,
                       sample_batch=sampler, batched=True)
    assert tr.batched is False
    m = tr.run_round(0)
    assert np.isfinite(m.server_val_loss)


def test_param_shift_vmaps_over_client_axis(rng_key):
    """The vectorized shift rule under the client vmap (exactly how the
    batched executor runs it) == per-client autodiff."""
    cfg = get_config("vqc-satqfl").replace(vqc_qubits=4, vqc_layers=2,
                                           n_features=4)
    K, B = 3, 6
    keys = jax.random.split(rng_key, K)
    params = jax.vmap(lambda k: vqc_init(cfg, k))(keys)
    feats = jax.random.uniform(jax.random.fold_in(rng_key, 1), (K, B, 4),
                               maxval=np.pi)
    labels = jax.random.randint(jax.random.fold_in(rng_key, 2), (K, B),
                                0, cfg.n_classes)
    batches = {"features": feats, "labels": labels}
    g_shift = jax.vmap(lambda p, b: parameter_shift_grad(cfg, p, b))(
        params, batches)
    for i in range(K):
        p_i = jax.tree_util.tree_map(lambda x: x[i], params)
        b_i = {k: v[i] for k, v in batches.items()}
        g_auto = jax.grad(lambda p: vqc_loss(cfg, p, b_i))(p_i)
        for k in ("theta", "phi", "w_out", "b_out"):
            np.testing.assert_allclose(np.asarray(g_shift[k][i]),
                                       np.asarray(g_auto[k]), atol=2e-5)


# ---------------------------------------------------------------------------
# tiled multi-stage fused layer
# ---------------------------------------------------------------------------

def _rand_state(key, shape):
    re, im = jax.random.normal(key, (2,) + shape)
    state = (re + 1j * im).astype(jnp.complex64)
    return state / jnp.linalg.norm(state, axis=-1, keepdims=True)


def _oracle(state, gates):
    for q in range(gates.shape[0]):
        state = sv.apply_1q(state, gates[q], q)
    return state


@pytest.mark.parametrize("nq,low,gq,gt", [
    (6, 3, 2, 4),      # 3 passes: [0,3) + [3,5) + [5,6)
    (8, 4, 3, 8),      # [0,4) + [4,7) + [7,8)
    (9, 5, 4, 16),     # [0,5) + [5,9)
])
def test_tiled_layer_forced_small_tiles(rng_key, nq, low, gq, gt):
    """The multi-pass tiled kernel == the per-gate oracle when tiny tile
    overrides force several qubit groups (the cheap stand-in for 20q)."""
    state = _rand_state(jax.random.fold_in(rng_key, nq), (2 ** nq,))
    angles = jax.random.uniform(jax.random.fold_in(rng_key, nq + 31),
                                (3, nq), minval=-3.0, maxval=3.0)
    gates = sv.u3_gate(angles[0], angles[1], angles[2])
    got = apply_gate_layer(state, gates, low_qubits=low, group_qubits=gq,
                           group_tile=gt)
    assert sv_ops.LAYER_DEBUG["path"] == "tiled"
    np.testing.assert_allclose(np.asarray(got), np.asarray(_oracle(state,
                                                                   gates)),
                               atol=2e-6)


def test_tiled_layer_batched_states(rng_key):
    """Client-stacked (B, 2^nq) states run the SAME tiled kernel."""
    nq, B = 9, 3
    state = _rand_state(rng_key, (B, 2 ** nq))
    angles = jax.random.uniform(jax.random.fold_in(rng_key, 7), (3, nq),
                                minval=-2.0, maxval=2.0)
    gates = sv.u3_gate(angles[0], angles[1], angles[2])
    got = apply_gate_layer(state, gates, low_qubits=5, group_qubits=3,
                           group_tile=8)
    assert sv_ops.LAYER_DEBUG["path"] == "tiled"
    assert sv_ops.LAYER_DEBUG["batch"] == (B,)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_oracle(state, gates)), atol=2e-6)


def test_tiled_layer_vjp(rng_key):
    nq = 7

    def gates_of(theta):
        return jnp.stack([sv.ry_gate(theta * (q + 1)) for q in range(nq)])

    state = _rand_state(rng_key, (2 ** nq,))

    def loss_k(theta):
        out = apply_gate_layer(state, gates_of(theta), low_qubits=3,
                               group_qubits=2, group_tile=4)
        return jnp.sum(jnp.abs(out[: 2 ** (nq - 1)]) ** 2)

    def loss_r(theta):
        return jnp.sum(jnp.abs(_oracle(state,
                                       gates_of(theta))[: 2 ** (nq - 1)]) ** 2)

    gk = jax.grad(loss_k)(0.41)
    gr = jax.grad(loss_r)(0.41)
    assert abs(float(gk) - float(gr)) < 1e-5


def test_per_gate_fallback_is_flagged(rng_key, caplog):
    """When the tiled plan is unavailable the op must degrade LOUDLY:
    warning log + LAYER_DEBUG record (the ROADMAP's silent-fallback gap)."""
    nq = 14
    state = _rand_state(rng_key, (2 ** nq,))
    angles = jax.random.uniform(rng_key, (3, nq), minval=-2.0, maxval=2.0)
    gates = sv.u3_gate(angles[0], angles[1], angles[2])
    with caplog.at_level(logging.WARNING,
                         logger="repro.kernels.statevec_gate.ops"):
        # a non-power-of-two tile cannot cover the lanes exactly — the op
        # must refuse the tiled plan rather than write a partial state
        got = apply_gate_layer(state, gates, group_tile=3)
    assert sv_ops.LAYER_DEBUG["path"] == "per-gate"
    assert any("per-gate" in rec.message for rec in caplog.records)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_oracle(state, gates)), atol=2e-6)


def test_tiled_layer_20_qubits(rng_key):
    """Acceptance: nq=20 runs the tiled multi-stage plan (no per-gate
    fallback) and matches the per-gate oracle to 1e-6."""
    nq = 20
    state = _rand_state(rng_key, (2 ** nq,))
    angles = jax.random.uniform(jax.random.fold_in(rng_key, 3), (3, nq),
                                minval=-2.0, maxval=2.0)
    gates = sv.u3_gate(angles[0], angles[1], angles[2])
    got = apply_gate_layer(state, gates)
    assert sv_ops.LAYER_DEBUG["path"] == "tiled"
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_oracle(state, gates)), atol=1e-6)
