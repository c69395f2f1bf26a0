"""Fault-injection & recovery plane (PR 8 acceptance suite).

The compiled :class:`~repro.core.plan.FaultSchedule` is the single
source of fault truth: the per-client oracle and the batched executor
must drop / retry / raise on EXACTLY the same (round, edge/sat) sites,
report identical per-round :class:`~repro.core.round.FaultReport`
counts, and keep the repo's established parity contracts (exact comm
accounting, ≤1e-6 params) while degrading. Round-granularity
checkpointing must make a kill-at-round-r + resume run bit-identical
to the uninterrupted one, and async retransmissions must never expose
an OTP pad twice (a flapped attempt drops the link BEFORE ciphertext
moves, so each (edge, born) pad reaches the wire at most once).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_async_buffer as tab
from repro.core import SatQFLConfig, SatQFLTrainer
from repro.core.plan import compile_round_plan, fault_site_u32
from repro.security.errors import (CorruptionError, FaultError,
                                   LinkFlapError, RetryExhaustedError,
                                   SatCrashError)

model = tab.model          # module-scoped (cfg, api) fixture

FAULTS = dict(link_flap_rate=0.3, crash_rate=0.2, straggler_rate=0.3,
              corrupt_rate=0.3, fault_seed=11)


def _fl(**kw):
    base = dict(mode="sim", n_rounds=4, local_steps=2, batch_size=4,
                eval_every=10 ** 6)
    base.update(kw)
    return SatQFLConfig(**base)


def _dense(N=5, R=4):
    """Every secondary sees main 0 at every step (no degenerate groups —
    every round has fault sites to hit)."""
    sg = np.zeros((N, R), bool)
    sg[0, :] = True
    sg[N - 1, :] = True
    ss = np.zeros((N, N, R), bool)
    ss[1:, 0, :] = True
    return sg, ss


@pytest.fixture(scope="module")
def full_run(model):
    """The uninterrupted run of one (configuration, executor) on the dense
    trace of ``fl.n_rounds`` steps, computed once and shared by the
    parity and resume tests; none of them mutates it."""
    cfg, api = model
    runs = {}

    def get(fl, batched):
        if (fl, batched) not in runs:
            sg, ss = _dense(R=fl.n_rounds)
            trace = tab.make_trace(sg, ss)
            sats, server = tab.make_data(trace.n_sats, 0)
            tr = SatQFLTrainer(cfg, api, fl, trace, sats, server,
                               batched=batched)
            tr.run()
            runs[(fl, batched)] = tr
        return runs[(fl, batched)]

    return get


def _pair(full_run, fl):
    return {batched: full_run(fl, batched) for batched in (False, True)}


# ---------------------------------------------------------------------------
# config validation (PR 4/5 knobs + the fault plane's)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(mode="simultaneous"),
    dict(security="otp"),
    dict(on_qber_abort="ignore"),
    dict(agg_security="masking"),
    dict(agg_security="secagg", mode="sim"),
    dict(max_staleness=-1),
    dict(n_rounds=0),
    dict(local_steps=0),
    dict(batch_size=0),
    dict(link_flap_rate=1.5),
    dict(crash_rate=-0.1),
    dict(straggler_extra_s=-1.0),
    dict(on_fault="retry"),
    dict(max_retries=-1),
    dict(retry_backoff_steps=0),
    dict(max_retries=2, mode="sim"),
    dict(corrupt_rate=0.5, security="none"),
    dict(corrupt_rate=0.5, security="qkd", verify_mac=False),
])
def test_config_validation_raises(kw):
    with pytest.raises(ValueError):
        _fl(**kw)


def test_config_fault_knobs_accepted():
    fl = _fl(mode="async", security="qkd", max_retries=3,
             retry_backoff_steps=2, **FAULTS)
    assert fl.max_retries == 3


# ---------------------------------------------------------------------------
# the compiled schedule is the tabulated pointwise hash
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["sim", "async"])
def test_fault_schedule_matches_pointwise_hash(model, mode):
    sg, ss = _dense(R=4)
    fl = _fl(mode=mode, security="qkd",
             max_retries=(1 if mode == "async" else 0), **FAULTS)
    plan = compile_round_plan(tab.make_trace(sg, ss), fl)
    f = plan.faults
    assert f is not None
    for r in range(plan.n_rounds):
        for s in range(plan.n_sats):
            u = fault_site_u32(fl.fault_seed, "crash", r, s)
            hit = int(u) < int(fl.crash_rate * 4294967296.0)
            assert bool(f.crash[r, s]) == hit
        hi = int(plan.edges.ptr[r, int(plan.edges.n_stages[r])])
        for j in range(hi):
            b = int(plan.edges.born[r, j]) if mode == "async" else r
            edge = (int(plan.edges.src[r, j]), int(plan.edges.dst[r, j]))
            att = int(f.attempt[r, j])
            if not (mode == "async" and int(plan.edges.link[r, j]) == 0):
                assert bool(f.link_flap[r, j]) == f.flap_of(b, edge, att)
            tv = int(f.tamper[r, j])
            assert tv == f.tamper_of(b, edge)
            if tv:
                assert tv & 1       # never a zero-XOR no-op


def test_zero_rates_compile_no_schedule(model):
    sg, ss = _dense()
    plan = compile_round_plan(tab.make_trace(sg, ss), _fl(security="qkd"))
    assert plan.faults is None


# ---------------------------------------------------------------------------
# engine parity under faults: oracle vs batched, all four modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["qfl", "sim", "seq", "async"])
def test_fault_parity_oracle_vs_batched(full_run, mode):
    fl = _fl(mode=mode, security="qkd",
             max_retries=(2 if mode == "async" else 0), **FAULTS)
    out = _pair(full_run, fl)
    to, tb = out[False], out[True]
    assert to.log.round_details == tb.log.round_details
    assert to.fault_reports == tb.fault_reports
    assert sum(f.crashes + f.link_flaps + f.corruptions
               for f in to.fault_reports) > 0, "degenerate: no fault hit"
    for a, b in zip(to.history, tb.history):
        assert a.participants == b.participants
        assert a.comm_s == b.comm_s and a.security_s == b.security_s
    for x, y in zip(jax.tree_util.tree_leaves(to.global_params),
                    jax.tree_util.tree_leaves(tb.global_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


def test_fault_free_round_details_carry_no_fault_key(full_run):
    out = _pair(full_run, _fl(security="qkd"))
    for tr in out.values():
        assert tr.plan.faults is None and tr.fault_reports == []
        assert all("faults" not in d for d in tr.log.round_details)


# ---------------------------------------------------------------------------
# on_fault='raise' surfaces the typed FaultError family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,err", [
    (dict(crash_rate=1.0), SatCrashError),
    (dict(link_flap_rate=1.0), LinkFlapError),
    (dict(corrupt_rate=1.0, security="qkd"), CorruptionError),
])
def test_on_fault_raise(model, kw, err):
    cfg, api = model
    sg, ss = _dense()
    sats, server = tab.make_data(5, 0)
    fl = _fl(on_fault="raise", fault_seed=11, **kw)
    tr = SatQFLTrainer(cfg, api, fl, tab.make_trace(sg, ss), sats, server)
    with pytest.raises(err) as ei:
        tr.run()
    assert isinstance(ei.value, FaultError) and ei.value.sites


def test_retry_exhaustion_raises(model):
    """A round whose retransmit budget ran dry surfaces
    RetryExhaustedError (it outranks the round's plain flaps)."""
    cfg, api = model
    N, R = 5, 6
    sg, ss = _dense(N, R)
    sats, server = tab.make_data(N, 0)
    fl = _fl(mode="async", n_rounds=R, link_flap_rate=1.0, max_retries=1,
             fault_seed=11)
    tr = SatQFLTrainer(cfg, api, fl, tab.make_trace(sg, ss), sats, server)
    tr.run()
    lossy = [f.round for f in tr.fault_reports if f.lost > 0]
    assert lossy, "degenerate: flap_rate=1.0 lost nothing"
    with pytest.raises(RetryExhaustedError):
        tr._raise_round_faults(lossy[0])


# ---------------------------------------------------------------------------
# async retransmit: recovery happens AND no OTP pad is ever reused
# ---------------------------------------------------------------------------

def test_async_retransmit_recovers_without_pad_reuse(model, monkeypatch):
    import repro.core.round as round_mod
    cfg, api = model
    N, R = 5, 6
    sg, ss = _dense(N, R)
    sats, server = tab.make_data(N, 0)
    fl = _fl(mode="async", n_rounds=R, security="qkd",
             link_flap_rate=0.4, fault_seed=3, max_retries=2)
    tr = SatQFLTrainer(cfg, api, fl, tab.make_trace(sg, ss), sats, server,
                       batched=False)
    used = []
    real = round_mod.encrypt_tree

    def spy(params, seed):
        used.append(int(seed))
        return real(params, seed)

    monkeypatch.setattr(round_mod, "encrypt_tree", spy)
    tr.run()
    rep = {k: sum(getattr(f, k) for f in tr.fault_reports)
           for k in ("retries", "recovered", "lost", "link_flaps")}
    assert rep["retries"] > 0, "degenerate: no retransmission exercised"
    assert rep["recovered"] > 0, "retransmit never recovered a delivery"
    # one pad per (edge, born): a flapped attempt dropped the link before
    # ciphertext moved, so the retransmission is the pad's FIRST exposure
    assert len(used) == len(set(used)), "OTP pad exposed twice on the wire"
    # and the batched path agrees fault-for-fault
    tb = SatQFLTrainer(cfg, api, fl, tab.make_trace(sg, ss), sats, server,
                       batched=True)
    tb.run()
    assert tb.fault_reports == tr.fault_reports
    assert tb.log.round_details == tr.log.round_details


# ---------------------------------------------------------------------------
# crash-resume: kill at round r, restore, bit-identical end state
# ---------------------------------------------------------------------------

def _resume_check(model, full_run, fl, batched, tmp_path, kill_at=2):
    cfg, api = model
    sg, ss = _dense(R=fl.n_rounds)
    trace = tab.make_trace(sg, ss)
    sats, server = tab.make_data(5, 0)
    trA = full_run(fl, batched)
    trB = SatQFLTrainer(cfg, api, fl, trace, sats, server, batched=batched)
    for r in range(kill_at):
        trB.run_round(r)
    trB.save_round_checkpoint(str(tmp_path))
    trC = SatQFLTrainer(cfg, api, fl, trace, sats, server, batched=batched)
    assert trC.restore_round_checkpoint(str(tmp_path)) == kill_at
    for r in range(kill_at, fl.n_rounds):
        trC.run_round(r)
    for x, y in zip(jax.tree_util.tree_leaves(trA.global_params),
                    jax.tree_util.tree_leaves(trC.global_params)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            "resumed params are not bit-identical"
    assert trA.log.round_details == trC.log.round_details
    assert trA.fault_reports == trC.fault_reports
    assert trA.aborted_edges == trC.aborted_edges


@pytest.mark.parametrize("batched", [False, True])
def test_crash_resume_bit_identical_sim_faults(model, full_run, batched,
                                               tmp_path):
    _resume_check(model, full_run, _fl(security="qkd", **FAULTS), batched,
                  tmp_path)


@pytest.mark.parametrize("batched", [False, True])
def test_crash_resume_bit_identical_async_retry(model, full_run, batched,
                                                tmp_path):
    fl = _fl(mode="async", security="qkd", max_retries=2, **FAULTS)
    _resume_check(model, full_run, fl, batched, tmp_path)


RESUME_EXTENDED = [
    dict(mode="qfl", security="qkd", **FAULTS),
    dict(mode="seq", security="qkd", **FAULTS),
    dict(mode="async", agg_security="secagg", crash_rate=0.2,
         link_flap_rate=0.2, max_retries=1, fault_seed=5),
    dict(mode="sim", security="teleport"),
    dict(mode="async", security="qkd_fernet"),
]


# a batched case with a QKD exchange compiles the secure stage, which
# takes a minute or more on XLA:CPU alone: those cases are slow
@pytest.mark.parametrize("kw,batched", [
    pytest.param(kw, batched, id=f"kw{i}-{batched}", marks=(
        pytest.mark.slow,) if batched and kw.get("security") in (
            "qkd", "qkd_fernet") else ())
    for i, kw in enumerate(RESUME_EXTENDED) for batched in (False, True)])
def test_crash_resume_bit_identical_extended(model, full_run, kw, batched,
                                             tmp_path):
    _resume_check(model, full_run, _fl(**kw), batched, tmp_path)


def test_resume_rejects_config_mismatch(model, tmp_path):
    cfg, api = model
    sg, ss = _dense()
    trace = tab.make_trace(sg, ss)
    sats, server = tab.make_data(5, 0)
    tr = SatQFLTrainer(cfg, api, _fl(), trace, sats, server)
    tr.run_round(0)
    tr.save_round_checkpoint(str(tmp_path))
    other = SatQFLTrainer(cfg, api, _fl(lr=0.01), trace, sats, server)
    with pytest.raises(ValueError, match="different SatQFLConfig"):
        other.restore_round_checkpoint(str(tmp_path))
    oracle = SatQFLTrainer(cfg, api, _fl(), trace, sats, server,
                           batched=False)
    with pytest.raises(ValueError, match="fingerprint"):
        oracle.restore_round_checkpoint(str(tmp_path))


def test_run_auto_resumes_from_checkpoint_dir(model, full_run, tmp_path):
    cfg, api = model
    sg, ss = _dense()
    trace = tab.make_trace(sg, ss)
    sats, server = tab.make_data(5, 0)
    fl = _fl(security="qkd", **FAULTS)
    trA = full_run(fl, True)
    trB = SatQFLTrainer(cfg, api, fl, trace, sats, server)
    for r in range(2):
        trB.run_round(r)
    trB.save_round_checkpoint(str(tmp_path))
    trC = SatQFLTrainer(cfg, api, fl, trace, sats, server)
    hist = trC.run(ckpt_dir=str(tmp_path))
    assert len(hist) == fl.n_rounds
    for x, y in zip(jax.tree_util.tree_leaves(trA.global_params),
                    jax.tree_util.tree_leaves(trC.global_params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    from repro.checkpoint.io import latest_step
    assert latest_step(str(tmp_path)) == fl.n_rounds


# ---------------------------------------------------------------------------
# dist engine graceful degradation (fault_mask)
# ---------------------------------------------------------------------------

def test_dist_fault_mask_degrades_and_all_ones_is_noop(model):
    from repro.core.dist import fl_init_state, make_fl_round
    from repro.nn.optim import sgd
    cfg, api = model
    N = 4
    opt = sgd(0.05)
    fl = _fl(n_rounds=2)
    rf = jax.jit(make_fl_round(cfg, api, fl, opt, N, security="none"))
    st0 = fl_init_state(cfg, api, opt, N, jax.random.PRNGKey(0))
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    b = {"features": jax.random.uniform(k1, (N, fl.local_steps,
                                             fl.batch_size, 2)),
         "labels": jax.random.randint(k2, (N, fl.local_steps,
                                           fl.batch_size), 0, 7)}
    pm = jnp.ones((N,), jnp.float32)
    seeds = jnp.arange(N, dtype=jnp.uint32)
    w = jnp.asarray([1.0, 2.0, 1.0, 2.0])

    def leaves(t):
        return [np.asarray(x) for x in jax.tree_util.tree_leaves(t)]

    sA, mA = rf(st0, b, pm, seeds, w)
    sB, mB = rf(st0, b, pm, seeds, w, jnp.ones((N,), jnp.float32))
    for x, y in zip(leaves(sA), leaves(sB)):
        assert np.array_equal(x, y)          # all-healthy mask = no mask
    fm = jnp.asarray([1, 0, 1, 1], jnp.float32)
    sC, _ = rf(st0, b, pm, seeds, w, fm)
    # the crashed row's optimizer slot is frozen...
    for x, y in zip(leaves(jax.tree_util.tree_map(lambda v: v[1],
                                                  sC.opt_slots)),
                    leaves(jax.tree_util.tree_map(lambda v: v[1],
                                                  st0.opt_slots))):
        assert np.array_equal(x, y)
    # ...and the crash degrades exactly like a zero FedAvg weight
    sE, _ = rf(st0, b, pm, seeds, w * fm)
    for x, y in zip(leaves(sC.params), leaves(sE.params)):
        assert np.array_equal(x, y)
    # every row crashed -> the model is kept, not zeroed
    sD, _ = rf(st0, b, pm, seeds, w, jnp.zeros((N,), jnp.float32))
    for x, y in zip(leaves(sD.params), leaves(st0.params)):
        assert np.array_equal(x, y)


def test_dist_secagg_rejects_fault_mask(model):
    from repro.core.dist import fl_init_state, make_fl_round
    from repro.nn.optim import sgd
    cfg, api = model
    N = 4
    opt = sgd(0.05)
    fl = _fl(n_rounds=1)
    rf = make_fl_round(cfg, api, fl, opt, N, security="secagg")
    st0 = fl_init_state(cfg, api, opt, N, jax.random.PRNGKey(0))
    b = {"features": jnp.zeros((N, fl.local_steps, fl.batch_size, 2)),
         "labels": jnp.zeros((N, fl.local_steps, fl.batch_size),
                             jnp.int32)}
    with pytest.raises(ValueError, match="secagg"):
        rf(st0, b, jnp.ones((N,)), jnp.zeros((N,), jnp.uint32), None,
           jnp.ones((N,), jnp.float32))


def test_plan_fault_mask_accessor(model):
    sg, ss = _dense()
    trace = tab.make_trace(sg, ss)
    clean = compile_round_plan(trace, _fl())
    assert np.array_equal(np.asarray(clean.fault_mask(0)), np.ones(5))
    plan = compile_round_plan(trace, _fl(crash_rate=0.5, fault_seed=11))
    fm = np.asarray(plan.fault_mask(1))
    assert np.array_equal(fm, 1.0 - plan.faults.crash[1].astype(np.float32))


# ---------------------------------------------------------------------------
# roofline --full on a CPU-only host: recorded skip, nothing clobbered
# ---------------------------------------------------------------------------

def test_roofline_full_skips_on_cpu_host():
    if jax.devices()[0].platform != "cpu":
        pytest.skip("accelerator host: the skip path is not reachable")
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import roofline
    payload, derived = roofline.full()
    assert "skipped" in derived
    assert payload["skipped"]["platform"] == "cpu"
    assert "reason" in payload["skipped"]
