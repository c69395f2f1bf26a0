"""Async v2 bounded-staleness buffer: ring dispatch vs per-main-list oracle.

The PR's acceptance suite. Arbitrary (delivery, window-drop, QBER-abort,
staleness) patterns over 3+ rounds — hand-crafted deterministic traces in
tier-1, hypothesis-drawn access matrices on top — must give, between the
compiled ring-buffer path (``batched=True``) and the live per-main-list
oracle (``batched=False``):

  * BIT-equal merged parameters at the buffer boundary (every (round,
    main) merge output — both paths reduce through the same
    ``(N+1)·(Δ_max+1)`` frame, so zero-weight cells are exact no-ops and
    the float sums associate identically);
  * exactly equal delivered counts (RoundMetrics.participants);
  * exactly equal CommLog wait/wall/security accounting, component by
    component per round (``CommLog.round_details``);
  * identical QBER-abort sets;

for both gradient rules. The oracle
replays the batched executor's local training, each input checked to the
bit, so both buffers see identical updates; end-of-round global
parameters are held to the repo's 1e-6 contract.

Plus: the compiled delay/deliver/staleness semantics pinned on crafted
windows, the wait-accounting fix (a windowless sender clamps to the comm
model's mean window wait instead of reporting zero), and exact secagg
dropout recovery through the engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.constellation.topology import ConstellationTrace
from repro.core import SatQFLConfig, SatQFLTrainer
from repro.models import get_config, get_model

N_CLASSES = 7


@pytest.fixture(scope="module")
def model():
    cfg = get_config("vqc-satqfl").replace(vqc_qubits=2, vqc_layers=1,
                                           n_features=2)
    return cfg, get_model(cfg)


def make_trace(sg: np.ndarray, ss: np.ndarray,
               step_s: float = 60.0) -> ConstellationTrace:
    """Synthetic trace from hand-specified access matrices.

    sg (N, T) bool — ground visibility (one station); ss (N, N, T) bool —
    ISL access (symmetrized, zero diagonal). Distinct static positions
    make the nearest-primary assignment deterministic.
    """
    N, T = sg.shape
    ss = (ss | ss.transpose(1, 0, 2))
    ss[np.arange(N), np.arange(N)] = False
    pos = np.zeros((N, T, 3))
    pos[:, :, 0] = (np.arange(N) + 1.0)[:, None] * 1000.0
    return ConstellationTrace(
        times_s=np.arange(T) * step_s,
        sat_pos=pos,
        sg_access=sg[:, None, :],
        ss_access=ss,
        gs_names=["GS0"],
        n_sats=N)


def make_data(n_sats: int, seed: int = 0, equal_sizes: bool = False):
    rng = np.random.default_rng(seed)
    sats = []
    for s in range(n_sats):
        n = 8 if equal_sizes else 6 + 2 * (s % 3)
        sats.append({
            "features": jnp.asarray(
                rng.uniform(0, np.pi, (n, 2)).astype(np.float32)),
            "labels": jnp.asarray(
                rng.integers(0, N_CLASSES, (n,)), jnp.int32),
        })
    batch = {
        "features": jnp.asarray(
            rng.uniform(0, np.pi, (8, 2)).astype(np.float32)),
        "labels": jnp.asarray(rng.integers(0, N_CLASSES, (8,)), jnp.int32),
    }
    return sats, {"val": batch, "test": batch}


def pattern(name: str, R: int = 4):
    """Crafted (sg, ss) access matrices exercising one buffer behavior."""
    N, T = 5, R
    sg = np.zeros((N, T), bool)
    ss = np.zeros((N, N, T), bool)
    sg[0, :] = True                       # sat 0: always-visible main
    if name == "steady":
        # every secondary grouped every round, window open at every step:
        # transmit next step, merge with staleness 1
        ss[1:, 0, :] = True
    elif name == "gappy":
        # sat 1's window only at even steps: trains at even rounds,
        # transmits two rounds later (staleness 2)
        ss[1, 0, 0::2] = True
        ss[2, 0, :] = True
    elif name == "horizon":
        # sat 2's window opens at the last step only: its update can
        # never transmit before the trace ends (window-drop)
        ss[1, 0, :] = True
        ss[2, 0, T - 1:] = True
    elif name == "stale":
        # sat 1 grouped at round 0, window reopens only at the last step:
        # the arrival would exceed Δ_max → too stale, never transmitted
        ss[1, 0, 0] = True
        ss[1, 0, T - 1] = True
        ss[2, 0, :] = True
    elif name == "main_flicker":
        # destination main loses ground visibility after the send round:
        # the delivery lands in its buffer and merges rounds later, when
        # it is primary again — the multi-round ring case
        sg[0, :] = False
        sg[0, 0] = True
        sg[0, T - 1] = True
        sg[4, :] = True                   # keeps every round mains-bearing
        ss[1, 0, :] = True
    else:
        raise ValueError(name)
    return sg, ss


def _record_training(tr, trained):
    """Record what the batched executor trains, per (round, satellite):
    (input params, trained params, loss)."""
    real = tr._train_group_batched

    def train(sat_ids, params_stacked, r, *args, **kw):
        p, losses = real(sat_ids, params_stacked, r, *args, **kw)
        for j, s in enumerate(sat_ids):
            trained[(r, s)] = tuple(
                jax.tree_util.tree_map(lambda x: np.asarray(x[j]), t)
                for t in (params_stacked, p)) + (float(losses[j]),)
        return p, losses

    tr._train_group_batched = train


def _replay_training(tr, trained):
    """The oracle trains nothing itself: each (round, satellite) gets the
    batched executor's result, after its input is checked to the bit, so
    both paths buffer, merge and aggregate identical inputs. Vmapped and
    per-client training agree only to the 1e-6 contract (not to the bit
    under jax 0.9's XLA:CPU); test_batched_round and test_faults hold the
    two trainings to it."""
    def train(sat, params, r):
        given, out, loss = trained[(r, sat)]
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(given)):
            # unequal inputs: an earlier merge or aggregation differs
            assert np.array_equal(np.asarray(a), b), ("input", r, sat)
        return jax.tree_util.tree_map(jnp.asarray, out), loss

    tr._train_sat = train


def run_pair(model, fl, sg, ss, *, eav=frozenset(), step_s=60.0,
             equal_sizes=False, seed=0):
    cfg, api = model
    trace = make_trace(sg, ss, step_s)
    sats, server = make_data(trace.n_sats, seed, equal_sizes)
    out, trained = {}, {}
    for batched in (True, False):
        tr = SatQFLTrainer(cfg, api, fl, trace, sats, server,
                           batched=batched, eavesdrop_edges=eav)
        tr.async_debug = True
        (_record_training if batched else _replay_training)(tr, trained)
        hist = tr.run()
        out[batched] = (tr, hist)
    return out


def assert_paths_agree(out):
    (to, ho), (tb, hb) = out[False], out[True]
    # delivered counts + per-round accounting: EXACT
    for a, b in zip(ho, hb):
        assert a.participants == b.participants
        assert a.comm_s == b.comm_s
        assert a.security_s == b.security_s
    assert to.log.round_details == tb.log.round_details
    assert to.log.wait_s == tb.log.wait_s
    assert to.log.bytes_moved == tb.log.bytes_moved
    assert to.log.n_transfers == tb.log.n_transfers
    assert to.aborted_edges == tb.aborted_edges
    # buffer-boundary merges: BIT-equal trees at every (round, main)
    mo = {(r, m): t for r, m, t in to.async_merge_log}
    mb = {(r, m): t for r, m, t in tb.async_merge_log}
    assert set(mo) == set(mb) and mo
    for k in mo:
        for a, b in zip(jax.tree_util.tree_leaves(mo[k]),
                        jax.tree_util.tree_leaves(mb[k])):
            assert np.array_equal(a, b), k
    # end-of-round params: the repo-wide vmap-vs-loop contract
    for a, b in zip(jax.tree_util.tree_leaves(to.global_params),
                    jax.tree_util.tree_leaves(tb.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    return to, tb


PATTERNS = ["steady", "gappy", "horizon", "stale", "main_flicker"]


def _fl(**kw):
    base = dict(mode="async", n_rounds=4, local_steps=2, batch_size=4,
                eval_every=10 ** 6)
    base.update(kw)
    return SatQFLConfig(**base)


@pytest.mark.parametrize("name", PATTERNS)
def test_patterns_autodiff(model, name):
    sg, ss = pattern(name)
    assert_paths_agree(run_pair(model, _fl(), sg, ss))


@pytest.mark.parametrize("name", PATTERNS)
def test_patterns_param_shift(model, name):
    sg, ss = pattern(name)
    assert_paths_agree(run_pair(model, _fl(grad_method="param_shift"),
                                sg, ss))


@pytest.mark.parametrize("security,agg", [
    ("qkd", "none"), ("qkd_fernet", "none"),
    ("none", "secagg"), ("qkd", "secagg"),
])
def test_steady_secure_modes(model, security, agg):
    sg, ss = pattern("steady")
    assert_paths_agree(run_pair(
        model, _fl(security=security, agg_security=agg), sg, ss))


def test_qber_abort_drop_pattern(model):
    """An eavesdropped sender aborts at delivery in BOTH paths: identical
    abort sets, its update exactly absent from every merge."""
    sg, ss = pattern("steady")
    eav = frozenset({(0, 1)})
    out = run_pair(model, _fl(security="qkd", on_qber_abort="drop"),
                   sg, ss, eav=eav)
    to, tb = assert_paths_agree(out)
    assert to.aborted_edges == {(0, 1)}
    # satellite 1 delivered nothing: no merged cell carries it
    st = to.plan.stale
    assert not (st.merge_w[:, :, 1, :] > 0).any()
    # the clean satellites still merged
    assert (st.merge_born >= 0).any()


def test_staleness_semantics_compiled(model):
    """Pin the compiled delay/deliver/staleness numbers on crafted
    windows (Δ_max = 2, 5 rounds, stride-1 steps)."""
    cfg, api = model
    R = 5
    sg, ss = pattern("gappy", R)
    trace = make_trace(sg, ss)
    fl = _fl(n_rounds=R, max_staleness=2)
    sats, server = make_data(trace.n_sats)
    tr = SatQFLTrainer(cfg, api, fl, trace, sats, server)
    st = tr.plan.stale
    # sat 2: window open every step -> transmit next step, staleness 1
    for r in range(R - 1):
        assert st.delay_rounds[r, 2] == 1
        assert st.deliver_round[r, 2] == r + 1
    # last round's update cannot transmit inside the trace
    assert st.deliver_round[R - 1, 2] == -1
    assert np.isinf(st.tx_wait_s[R - 1, 2])
    # sat 1: grouped at even rounds, window reopens two steps later
    assert st.delay_rounds[0, 1] == 2 and st.deliver_round[0, 1] == 2
    assert st.delay_rounds[1, 1] == -1          # not grouped at odd rounds
    # merged staleness never exceeds Delta_max, and equals deliver - born
    for r in range(R):
        for g in range(st.n_mains_max):
            borns = st.merge_born[r, g][st.merge_born[r, g] >= 0]
            assert all(0 < r - b <= fl.max_staleness for b in borns)
    # the stale pattern drops the too-old arrival entirely
    sg2, ss2 = pattern("stale", R)
    tr2 = SatQFLTrainer(cfg, api, _fl(n_rounds=R, max_staleness=1), trace
                        .__class__(times_s=trace.times_s,
                                   sat_pos=trace.sat_pos,
                                   sg_access=sg2[:, None, :],
                                   ss_access=(ss2 | ss2.transpose(1, 0, 2)),
                                   gs_names=["GS0"], n_sats=5),
                        sats, server)
    assert tr2.plan.stale.deliver_round[0, 1] == -1      # d=4 > Delta=1
    assert np.isfinite(tr2.plan.stale.tx_wait_s[0, 1])   # but it DID wait


def test_wait_accounting_windowless_vs_open(model):
    """The wait-accounting fix: a sender whose window never reopens
    clamps to the comm model's mean window wait (18 s) — distinguishable
    from an all-open round (one step, 5 s) and from an idle round (0) —
    and BOTH paths record the identical number."""
    cfg, api = model
    R = 4
    # sat 1: always-open window (tx next step = 5 s); sat 2: grouped at
    # round 0 only, never reopens (windowless sender)
    sg = np.zeros((4, R), bool)
    sg[0, :] = True
    ss = np.zeros((4, 4, R), bool)
    ss[1, 0, :] = True
    ss[2, 0, 0] = True
    out = run_pair(model, _fl(n_rounds=R), sg, ss, step_s=5.0)
    to, tb = assert_paths_agree(out)
    waits = [d["wait_s"] for d in to.log.round_details]
    # round 0 blocks on the windowless sender: the 18 s clamp, not 0
    assert waits[0] == 18.0
    # middle rounds only hold the open-window sender: one 5 s step
    assert all(w == 5.0 for w in waits[1:-1])
    # the final round's sender has no next trace step to transmit in —
    # windowless by horizon, so it clamps as well
    assert waits[-1] == 18.0
    assert [d["wait_s"] for d in tb.log.round_details] == waits


def test_secagg_dropout_recovery_engine(model):
    """Acceptance: with secagg, an aborted satellite's pairwise masks are
    cancelled exactly — the secure aggregate equals the same scenario's
    unmasked quantized aggregate (weights equal, so the only difference
    vs the float path is fixed-point rounding)."""
    sg, ss = pattern("steady")
    eav = frozenset({(0, 1)})
    kw = dict(sg=sg, ss=ss, eav=eav, equal_sizes=True)
    out_s = run_pair(model, _fl(security="qkd", on_qber_abort="drop",
                                agg_security="secagg"), **kw)
    to, tb = assert_paths_agree(out_s)
    assert to.aborted_edges == {(0, 1)}
    out_f = run_pair(model, _fl(security="qkd", on_qber_abort="drop"), **kw)
    tf = out_f[0][0]
    # identical delivery/abort behavior, merge values within quantization
    assert [m.participants for m in out_s[0][1]] \
        == [m.participants for m in out_f[0][1]]
    ms = {(r, m): t for r, m, t in to.async_merge_log}
    mf = {(r, m): t for r, m, t in tf.async_merge_log}
    assert set(ms) == set(mf)
    for k in ms:
        for a, b in zip(jax.tree_util.tree_leaves(ms[k]),
                        jax.tree_util.tree_leaves(mf[k])):
            np.testing.assert_allclose(a, b, atol=2e-4)


# ---------------------------------------------------------------------------
# hypothesis: arbitrary access patterns (the deterministic patterns above
# run regardless; this section needs the optional dev dependency)
# ---------------------------------------------------------------------------

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # pragma: no cover - optional dep
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @st.composite
    def scenarios(draw):
        n_sats = draw(st.integers(3, 5))
        n_rounds = draw(st.integers(3, 5))
        delta = draw(st.integers(0, 3))
        sg = np.asarray(draw(st.lists(
            st.lists(st.booleans(), min_size=n_rounds, max_size=n_rounds),
            min_size=n_sats, max_size=n_sats)), bool)
        ss = np.zeros((n_sats, n_sats, n_rounds), bool)
        for i in range(n_sats):
            for j in range(i + 1, n_sats):
                col = draw(st.lists(st.booleans(), min_size=n_rounds,
                                    max_size=n_rounds))
                ss[i, j, :] = col
        security = draw(st.sampled_from(["none", "qkd"]))
        secagg = draw(st.booleans())
        eav = frozenset()
        if security == "qkd" and draw(st.booleans()):
            eav = frozenset({(draw(st.integers(0, n_sats - 1)),
                              draw(st.integers(0, n_sats - 1)))})
        return n_rounds, delta, sg, ss, security, secagg, eav

    def _property_body(model, sc, grad_method):
        n_rounds, delta, sg, ss, security, secagg, eav = sc
        fl = _fl(n_rounds=n_rounds, max_staleness=delta, security=security,
                 on_qber_abort="drop", grad_method=grad_method,
                 agg_security="secagg" if secagg else "none")
        out = run_pair(model, fl, sg, ss, eav=eav)
        # degenerate all-dark traces have no mains and no merges to
        # compare — everything else must agree exactly
        if out[False][0].async_merge_log:
            assert_paths_agree(out)
        else:
            assert not out[True][0].async_merge_log
            assert out[False][0].log.round_details \
                == out[True][0].log.round_details

    def _bits(rows):
        return np.asarray([[c == "1" for c in row] for row in rows], bool)

    # Found by the search below, kept as an explicit example so that every
    # run shows it: main 2 merges three updates (weights 0.3, 0.4, 0.3) at
    # round 2, and on identical inputs the ring dispatch
    # (_async_merge_impl) and the oracle's frame merge (_amerge_frame_impl)
    # sum the (1, 2)-shaped leaves to different last bits on jax 0.9's
    # XLA:CPU: a defect of the program, not of this test.
    MERGE3 = (4, 1, _bits(["0000", "0001", "1111", "1010", "0100"]),
              np.stack([_bits(["0000", "1010", "1111", "1100", "1000"]),
                        _bits(["0000", "0000", "0111", "1110", "1111"]),
                        _bits(["0000", "0000", "0000", "0111", "0000"]),
                        _bits(["0000"] * 5), _bits(["0000"] * 5)]),
              "none", False, frozenset())

    @settings(max_examples=8, deadline=None)
    @given(sc=scenarios())
    @example(sc=MERGE3)
    def test_property_arbitrary_patterns(model, sc):
        _property_body(model, sc, "autodiff")

    @settings(max_examples=4, deadline=None)
    @given(sc=scenarios())
    @example(sc=MERGE3)
    def test_property_arbitrary_patterns_param_shift(model, sc):
        _property_body(model, sc, "param_shift")
