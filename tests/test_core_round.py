"""Host-level sat-QFL trainer (paper Algorithm 1 + 2) behaviour tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.constellation import build_trace
from repro.core import CommModel, SatQFLConfig, SatQFLTrainer
from repro.data import dirichlet_partition, make_statlog, server_split
from repro.models import get_config, get_model
from repro.quantum import vqc_logits, vqc_loss


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


def _fl(**kw):
    return SatQFLConfig(n_rounds=2, local_steps=3, batch_size=8, **kw)


@pytest.fixture(scope="module")
def run(setup):
    """(trainer, history) of one run per configuration, computed once and
    shared by every test that reads it; no test mutates a shared run."""
    cfg, api, trace, sats, server = setup
    runs = {}

    def get(**kw):
        fl = _fl(**kw)
        if fl not in runs:
            tr = SatQFLTrainer(cfg, api, fl, trace, sats, server)
            runs[fl] = (tr, tr.run())
        return runs[fl]

    return get


@pytest.mark.parametrize("mode", ["qfl", "sim", "seq", "async"])
def test_modes_run_and_evaluate(run, mode):
    tr, hist = run(mode=mode)
    assert len(hist) == 2
    for m in hist:
        assert np.isfinite(m.server_val_loss)
        assert 0.0 <= m.server_val_acc <= 1.0
        assert m.comm_s > 0


@pytest.mark.parametrize("mode", ["qfl", "sim", "seq", "async"])
def test_fused_engine_metrics_match_per_gate(setup, run, mode):
    """Acceptance: trainer metrics on every mode are unchanged (within
    float tolerance) when the VQC evaluates on the fused pipeline instead
    of the per-gate path it replaced."""
    cfg, api, trace, sats, server = setup

    def fwd_pg(c, p, b, ctx=None):
        return (vqc_logits(c, p, b["features"], fused=False),
                jnp.zeros((), jnp.float32))

    def loss_pg(c, p, b, ctx=None):
        return vqc_loss(c, p, b, ctx, fused=False)

    api_pg = api._replace(forward=fwd_pg, loss=loss_pg)
    tr = SatQFLTrainer(cfg, api_pg, _fl(mode=mode), trace, sats, server)
    hists = [run(mode=mode)[1], tr.run()]
    for m_fused, m_pg in zip(*hists):
        np.testing.assert_allclose(m_fused.server_val_loss,
                                   m_pg.server_val_loss, atol=1e-4)
        np.testing.assert_allclose(m_fused.server_val_acc,
                                   m_pg.server_val_acc, atol=1e-3)
        np.testing.assert_allclose(m_fused.server_test_acc,
                                   m_pg.server_test_acc, atol=1e-3)
        assert m_fused.comm_s == m_pg.comm_s


def test_param_shift_trainer_matches_autodiff(setup):
    """grad_method='param_shift' trains the same global model (the shift
    rule is exact for the Pauli-rotation ansatz)."""
    cfg, api, trace, sats, server = setup
    runs = {}
    for gm in ("autodiff", "param_shift"):
        fl = SatQFLConfig(n_rounds=1, local_steps=2, batch_size=8,
                          mode="sim", grad_method=gm)
        tr = SatQFLTrainer(cfg, api, fl, trace, sats, server)
        tr.run()
        runs[gm] = tr.global_params
    for a, b in zip(jax.tree_util.tree_leaves(runs["autodiff"]),
                    jax.tree_util.tree_leaves(runs["param_shift"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_encryption_transparent(run):
    t1, _ = run(mode="sim", security="none")
    t2, _ = run(mode="sim", security="qkd")
    for a, b in zip(jax.tree_util.tree_leaves(t1.global_params),
                    jax.tree_util.tree_leaves(t2.global_params)):
        assert bool(jnp.all(a == b))


def test_security_adds_overhead(run):
    t1, h1 = run(mode="sim", security="none")
    t2, h2 = run(mode="sim", security="qkd")
    assert t2.log.security_s > t1.log.security_s


def test_teleport_fidelity_reported(run):
    _, hist = run(mode="sim", security="teleport")
    assert hist[-1].teleport_fidelity > 0.999


def test_qfl_baseline_fastest_comm(run):
    """Paper Fig.12: flat QFL beats the hierarchical schedules on comm time
    (it ignores constellation constraints)."""
    _, h_qfl = run(mode="qfl")
    _, h_seq = run(mode="seq")
    _, h_sim = run(mode="sim")
    c = lambda h: sum(m.comm_s for m in h)
    assert c(h_qfl) < c(h_seq)
    assert c(h_qfl) < c(h_sim)


def test_async_staleness_buffer(setup):
    cfg, api, trace, sats, server = setup
    fl = SatQFLConfig(mode="async", n_rounds=3, local_steps=2, batch_size=8,
                      max_staleness=0)
    tr = SatQFLTrainer(cfg, api, fl, trace, sats, server)
    hist = tr.run()
    assert all(np.isfinite(m.server_val_loss) for m in hist)


def test_compromised_edge_aborts(setup):
    cfg, api, trace, sats, server = setup
    fl = SatQFLConfig(mode="sim", n_rounds=1, local_steps=2, batch_size=8,
                      security="qkd")
    # eavesdrop on every ISL edge: exchanges must abort
    eav = frozenset((s, m) for s in range(12) for m in range(12))
    tr = SatQFLTrainer(cfg, api, fl, trace, sats, server,
                       eavesdrop_edges=eav)
    with pytest.raises(ConnectionAbortedError):
        tr.run_round(0)
