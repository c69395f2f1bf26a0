"""Edge-batched secure-exchange plane (Algorithm 2) vs the per-edge oracle.

The PR's acceptance tests:

  * vmapped BB84 / batched establishment / stacked OTP+MAC are
    BIT-identical per edge to the per-edge oracle calls;
  * the edge-axis otp_xor kernel entry matches per-edge kernel launches
    (ciphertexts and tags, kernel and ref paths);
  * the trainer's edge-batched plane reproduces the per-edge loop
    exactly: bit-exact global params, exactly equal comm/security
    accounting, identical participant counts;
  * a forced eavesdropper on a subset of edges aborts exactly those
    edges in BOTH paths (drop mode), with identical accounting — and
    still raises (a SecurityError, which is a ConnectionAbortedError)
    in legacy raise mode;
  * MAC verification failures raise SecurityError carrying the edge id
    (no `assert`, which would vanish under python -O);
  * the vmapped device-metric pass equals the sequential evaluate() loop.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.constellation import build_trace
from repro.core import SatQFLConfig, SatQFLTrainer
from repro.core.plan import compile_round_plan
from repro.core.round import evaluate
from repro.data import dirichlet_partition, make_statlog, server_split
from repro.kernels import otp_xor_mac, otp_xor_mac_edges
from repro.models import get_config, get_model
from repro.quantum.qkd import bb84_keygen, bb84_keygen_edges, qber_abort_mask
from repro.security import (
    KeyManager, SecurityError, canonical_edge, encrypt_tree,
    encrypt_tree_rows, mac_verify_rows, poly_mac_rows, poly_mac_u32,
    tree_to_u32, tree_to_u32_rows, u32_to_tree_rows,
)
from repro.security.keys import QBER_ABORT


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


# ---------------------------------------------------------------------------
# primitive parity: BB84 / keys / OTP / MAC / kernel
# ---------------------------------------------------------------------------

def test_bb84_edges_bit_identical(rng_key):
    E, n_bits = 6, 256
    keys = jax.random.split(rng_key, E)
    eav = jnp.asarray([False, True, False, True, True, False])
    batch = bb84_keygen_edges(keys, n_bits, eav)
    for e in range(E):
        one = bb84_keygen(keys[e], n_bits, eavesdrop=bool(eav[e]))
        assert bool(jnp.all(one.sifted_key == batch.sifted_key[e]))
        assert int(one.key_len) == int(batch.key_len[e])
        assert float(one.qber) == float(batch.qber[e])
    # vectorized abort mask: attacked edges show ~25% QBER, clean ~0
    aborts = np.asarray(qber_abort_mask(batch, QBER_ABORT))
    assert aborts.tolist() == [bool(x) for x in np.asarray(eav)]


def test_establish_edges_matches_per_edge(rng_key):
    eav = frozenset({(1, 2), (0, "gs")})
    edges = [(0, 3), (2, 1), ("gs", 0), (5, "gs"), (2, 7), (0, 3)]
    km_loop = KeyManager(rng_key, eavesdrop_edges=eav)
    km_batch = KeyManager(rng_key, eavesdrop_edges=eav)
    eks_loop = [km_loop.establish(e) for e in edges]
    eks_batch = km_batch.establish_edges(edges)
    for a, b in zip(eks_loop, eks_batch):
        assert a.edge == b.edge
        assert a.seed == b.seed
        assert a.qber == b.qber
        assert a.compromised == b.compromised
    assert eks_batch[1].compromised          # (2, 1) ≡ (1, 2): eavesdropped
    # per-round mixes agree too (shared helpers)
    for r in (0, 3):
        assert int(eks_loop[0].round_seed(r)) == int(eks_batch[0].round_seed(r))


def test_stacked_otp_mac_bit_identical(rng_key):
    E = 5
    tree = {
        "a": jax.random.normal(rng_key, (E, 33), jnp.float32),
        "b": jax.random.normal(jax.random.fold_in(rng_key, 1),
                               (E, 5, 7)).astype(jnp.bfloat16),
    }
    seeds = jnp.asarray([11, 22, 33, 44, 55], jnp.uint32)
    rks = jnp.asarray([3, 1, 4, 1, 5], jnp.uint32)
    sks = jnp.asarray([9, 2, 6, 5, 3], jnp.uint32)
    ct_rows = encrypt_tree_rows(tree, seeds)
    streams = tree_to_u32_rows(ct_rows)
    tags = poly_mac_rows(streams, rks, sks)
    assert bool(jnp.all(mac_verify_rows(streams, tags, rks, sks)))
    for e in range(E):
        row = jax.tree_util.tree_map(lambda x: x[e], tree)
        ct_one = encrypt_tree(row, seeds[e])
        # compare ciphertexts in the u32 wire domain: XOR-ed floats can
        # hold NaN bit patterns, where float == is False for equal bits
        stream_one = tree_to_u32(ct_one)
        assert bool(jnp.all(stream_one == streams[e]))
        assert int(poly_mac_u32(stream_one, rks[e], sks[e])) == int(tags[e])
    # rows round-trip through the stacked wire view (u32-domain compare)
    back = u32_to_tree_rows(streams, ct_rows)
    assert bool(jnp.all(tree_to_u32_rows(back) == streams))
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(ct_rows)):
        assert a.dtype == b.dtype and a.shape == b.shape


@pytest.mark.parametrize("use_kernel", [True, False])
def test_edge_kernel_matches_per_edge(use_kernel):
    rng = np.random.default_rng(3)
    E, n = 4, 700                      # forces padding + 1 block at R=8
    msgs = jnp.asarray(rng.integers(0, 2**32, (E, n), dtype=np.uint32))
    pads = jnp.asarray(rng.integers(0, 2**32, (E, n), dtype=np.uint32))
    rk = jnp.asarray(rng.integers(0, 2**32, (E,), dtype=np.uint32))
    sk = jnp.asarray(rng.integers(0, 2**32, (E,), dtype=np.uint32))
    cts, tags = otp_xor_mac_edges(msgs, pads, rk, sk, block_rows=8,
                                  use_kernel=use_kernel)
    for e in range(E):
        ct1, tag1 = otp_xor_mac(msgs[e], pads[e], rk[e], sk[e], block_rows=8)
        assert bool(jnp.all(ct1 == cts[e]))
        assert int(tag1) == int(tags[e])


# ---------------------------------------------------------------------------
# plan: edge schedule consistency
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["qfl", "sim", "seq", "async"])
def test_plan_edge_schedule_matches_groups(setup, mode):
    cfg, api, trace, sats, server = setup
    fl = SatQFLConfig(n_rounds=3, mode=mode, security="qkd")
    km = KeyManager(jax.random.PRNGKey(7))
    plan = compile_round_plan(trace, fl, keymgr=km, with_seeds=False)
    es = plan.edges
    assert es.with_keys
    seen = set()
    for r in range(plan.n_rounds):
        g = plan.groups(r)
        # last stage is always the feeder uplink of the round
        lo, hi = es.stage_bounds(r, int(es.n_stages[r]) - 1)
        feeders = [es.edge_tuple(r, j) for j in range(lo, hi)]
        expect = ([canonical_edge((s, "gs")) for s in range(trace.n_sats)]
                  if mode == "qfl"
                  else [canonical_edge((m, "gs")) for m in g])
        assert feeders == expect
        for j in range(int(es.ptr[r, -1])):
            e = es.edge_tuple(r, j)
            # first-contact marks exactly the first planned use
            assert bool(es.first[r, j]) == (e not in seen)
            seen.add(e)
            # key material matches the registry's fold-in schedule; pad
            # seeds fold in the BORN round (= r everywhere except async
            # deferred deliveries, whose payload trained rounds earlier)
            born = int(es.born[r, j])
            if mode != "async":
                assert born == r
            else:
                assert 0 <= born <= r
            ek = km.get(e)
            assert int(es.seed[r, j]) == int(ek.round_seed(born))
            assert bool(es.abort[r, j]) == ek.compromised


# ---------------------------------------------------------------------------
# trainer: edge-batched plane == per-edge oracle
# ---------------------------------------------------------------------------

def _run_pair(setup, mode, security, **kw):
    cfg, api, trace, sats, server = setup
    fl = SatQFLConfig(n_rounds=2, local_steps=3, batch_size=8, mode=mode,
                      security=security, **kw)
    out = {}
    for eb in (True, False):
        tr = SatQFLTrainer(cfg, api, fl, trace, sats, server,
                           edge_batched=eb)
        assert tr.edge_batched is eb
        out[eb] = (tr, tr.run())
    return out


@pytest.mark.parametrize("mode,security", [
    ("sim", "qkd"), ("qfl", "qkd"), ("sim", "qkd_fernet"),
])
def test_edge_batched_plane_exact(setup, mode, security):
    """Acceptance: one dispatch per stage == E host calls, to the bit."""
    out = _run_pair(setup, mode, security)
    (tb, hb), (to, ho) = out[True], out[False]
    assert tb.log.security_s == to.log.security_s > 0
    assert tb.log.bytes_moved == to.log.bytes_moved
    assert tb.log.n_transfers == to.log.n_transfers
    for a, b in zip(hb, ho):
        assert a.comm_s == b.comm_s
        assert a.security_s == b.security_s
        assert a.participants == b.participants
    # the exchange is transparent on both paths → bit-exact global model
    for a, b in zip(jax.tree_util.tree_leaves(tb.global_params),
                    jax.tree_util.tree_leaves(to.global_params)):
        assert bool(jnp.all(a == b))


@pytest.mark.slow
@pytest.mark.parametrize("mode,security", [
    ("seq", "qkd"), ("async", "qkd"), ("qfl", "qkd_fernet"),
    ("seq", "qkd_fernet"), ("async", "qkd_fernet"),
])
def test_edge_batched_plane_exact_slow(setup, mode, security):
    test_edge_batched_plane_exact(setup, mode, security)


# ---------------------------------------------------------------------------
# QBER aborts, per edge
# ---------------------------------------------------------------------------

def _eav_subset():
    """Eavesdrop every edge touching satellites 0-2 (ISL and feeder)."""
    ends = list(range(12)) + ["gs"]
    return frozenset(canonical_edge((a, b)) for a in range(3) for b in ends
                     if a != b)


@pytest.mark.parametrize("mode", ["sim", "qfl"])
def test_qber_abort_subset_drop(setup, mode):
    """Acceptance: forced eavesdropper on a subset of edges aborts exactly
    those edges in the oracle AND the batched plane, identical accounting."""
    cfg, api, trace, sats, server = setup
    eav = _eav_subset()
    fl = SatQFLConfig(n_rounds=2, local_steps=2, batch_size=8, mode=mode,
                      security="qkd", on_qber_abort="drop")
    runs = {}
    for eb, b in ((True, True), (False, True), (False, False)):
        tr = SatQFLTrainer(cfg, api, fl, trace, sats, server,
                           eavesdrop_edges=eav, batched=b, edge_batched=eb)
        runs[(eb, b)] = (tr, tr.run())
    (tb, hb) = runs[(True, True)]
    (to, ho) = runs[(False, True)]
    (tp, hp) = runs[(False, False)]
    # aborted exactly the same (nonempty) edge subset, all eavesdropped
    assert tb.aborted_edges == to.aborted_edges == tp.aborted_edges
    assert len(tb.aborted_edges) > 0
    assert tb.aborted_edges <= eav
    for a, b, c in zip(hb, ho, hp):
        assert a.comm_s == b.comm_s == c.comm_s
        assert a.security_s == b.security_s == c.security_s
        assert a.participants == b.participants == c.participants
    for a, b in zip(jax.tree_util.tree_leaves(tb.global_params),
                    jax.tree_util.tree_leaves(to.global_params)):
        assert bool(jnp.all(a == b))
    for a, c in zip(jax.tree_util.tree_leaves(tb.global_params),
                    jax.tree_util.tree_leaves(tp.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-6)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["seq", "async"])
def test_qber_abort_subset_drop_slow(setup, mode):
    test_qber_abort_subset_drop(setup, mode)


@pytest.mark.parametrize("edge_batched", [True, False])
def test_qber_abort_raise_mode(setup, edge_batched):
    """Legacy behavior: raise mode kills the round with a SecurityError
    (still a ConnectionAbortedError) naming the edge."""
    cfg, api, trace, sats, server = setup
    fl = SatQFLConfig(mode="sim", n_rounds=1, local_steps=2, batch_size=8,
                      security="qkd")
    eav = frozenset((s, m) for s in range(12) for m in range(12))
    tr = SatQFLTrainer(cfg, api, fl, trace, sats, server,
                       eavesdrop_edges=eav, edge_batched=edge_batched)
    with pytest.raises(SecurityError) as ei:
        tr.run_round(0)
    assert isinstance(ei.value, ConnectionAbortedError)
    assert len(ei.value.edges) == 1 and ei.value.edges[0] in tr.aborted_edges


# ---------------------------------------------------------------------------
# every trainer shares one compiled secure stage
# ---------------------------------------------------------------------------

def test_secure_stage_compiles_once_for_two_trainers(setup):
    """A second trainer of the same shapes and settings reuses the first
    one's compiled ``_secure_stage_impl``: binding the stage to a trainer
    would compile it again for every instance (tens of seconds each on
    XLA:CPU)."""
    import repro.core.round as round_mod
    cfg, api, trace, sats, server = setup
    fl = SatQFLConfig(mode="sim", n_rounds=1, local_steps=2, batch_size=8,
                      security="qkd")
    compiles = []

    def on_event(event, duration, **kw):
        if (event == "/jax/core/compile/backend_compile_duration"
                and "_secure_stage_impl" in kw.get("fun_name", "")):
            compiles.append(kw["fun_name"])

    jax.monitoring.register_event_duration_secs_listener(on_event)
    counts, sizes = [], []
    try:
        for _ in range(2):
            tr = SatQFLTrainer(cfg, api, fl, trace, sats, server)
            tr.run_round(0)
            counts.append(len(compiles))
            sizes.append(round_mod._secure_stage_impl._cache_size())
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    # the first trainer compiles the stage unless an earlier test of this
    # process did; the second compiles nothing and adds no cache entry
    assert counts[0] <= 1 and counts[1] == counts[0], compiles
    assert sizes[0] >= 1 and sizes[1] == sizes[0], sizes


# ---------------------------------------------------------------------------
# MAC failures raise (never assert)
# ---------------------------------------------------------------------------

def test_mac_failure_raises_security_error(setup, monkeypatch, request):
    cfg, api, trace, sats, server = setup
    fl = SatQFLConfig(mode="sim", n_rounds=1, local_steps=2, batch_size=8,
                      security="qkd")
    # batched plane: tamper the receiver-side stage verify. It is read when
    # the shared stage is traced, so drop the stage's compiled programs
    # before the patch, and again after, so no later test runs this one
    import repro.core.round as round_mod
    round_mod._secure_stage_impl.clear_cache()
    request.addfinalizer(round_mod._secure_stage_impl.clear_cache)
    monkeypatch.setattr(
        round_mod, "_mac_rows_verify",
        lambda streams, tags, r, s: jnp.zeros(tags.shape, bool))
    tr = SatQFLTrainer(cfg, api, fl, trace, sats, server, edge_batched=True)
    with pytest.raises(SecurityError) as ei:
        tr.run_round(0)
    assert ei.value.edges             # failing edges are named
    # per-edge oracle: tamper the scalar verify
    monkeypatch.setattr(round_mod, "mac_verify",
                        lambda *a, **k: jnp.asarray(False))
    tr2 = SatQFLTrainer(cfg, api, fl, trace, sats, server,
                        edge_batched=False)
    with pytest.raises(SecurityError) as ei2:
        tr2.run_round(0)
    assert ei2.value.edges


# ---------------------------------------------------------------------------
# batched evaluate()
# ---------------------------------------------------------------------------

def test_dev_eval_vmap_matches_loop(setup):
    """The vmapped device-metric pass == the sequential evaluate() loop
    it replaced (masked padded rows carry exact zero weight)."""
    cfg, api, trace, sats, server = setup
    fl = SatQFLConfig(n_rounds=1, local_steps=2, batch_size=8, mode="sim")
    tr = SatQFLTrainer(cfg, api, fl, trace, sats, server)
    S = min(tr.n_sats, 8)
    losses, accs = tr._jit_dev_eval(
        tr.global_params,
        {k: v[:S] for k, v in tr._data_stacked.items()},
        tr._n_samples[:S])
    for s in range(S):
        l_ref, a_ref = evaluate(api, cfg, tr.global_params,
                                {k: v[:64] for k, v in sats[s].items()})
        np.testing.assert_allclose(float(losses[s]), l_ref, atol=1e-5)
        np.testing.assert_allclose(float(accs[s]), a_ref, atol=1e-5)
