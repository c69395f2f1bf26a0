"""Host-orchestrated sat-QFL rounds — paper Algorithm 1 + Algorithm 2.

This is the *paper-scale* engine: tens of satellites, each with a private
dataset and a local model (the VQC for the paper's experiments; any
ModelApi works). Roles (main/secondary), assignments, and access windows
come from the constellation trace; exchanges are optionally secured with
QKD-keyed OTP (+MAC), Fernet-lite control tokens, or teleportation of
(θ, φ) pairs; the communication-time model accounts every transfer.

**Constellation-batched execution (default).** Local training is the hot
path, and with the per-client loop a round costs one jitted dispatch per
satellite — wall-clock linear in constellation size even though each
client is fast. ``batched=True`` stacks every participating client's
parameters, optimizer slots, and (padded) data along a leading client
axis and runs local training as ONE vmapped-and-jitted program per group
stage (``repro.core.localtrain`` — the same program ``repro.core.dist``
vmaps at mesh scale): a 32-satellite round is one compiled dispatch, not
32. Aggregation is a weighted reduction over the stacked axis; the
communication/security accounting is unchanged (and bit-identical) —
security modes run Algorithm 2 per edge exactly as before.

``batched=False`` keeps the per-client loop as the numerics oracle; both
paths draw per-(round, satellite) keys from the same fold-in schedule and
sample through the same bounded sampler, so they see identical data and
agree to float-accumulation tolerance (tests enforce ≤ 1e-6 on metrics,
exact equality on comm accounting). A custom ``sample_batch`` (whose
signature has no padding bound) forces the per-client path.

**Async v2: the compiled bounded-staleness buffer.** The asynchronous
schedule no longer blocks on access windows: an update trained at round
``b`` transmits when its (sat, main) ISL window actually opens, arrives
at a later round, and waits in its main's buffer until that main is
primary again — merged if its staleness is still within Δ_max, discarded
otherwise. The whole lifecycle is a pure function of the trace, so
``core/plan.py`` compiles it into a :class:`~repro.core.plan.
StalenessSchedule` (a fixed ``(n_mains, N+1, Δ_max+1)`` ring frame of
validity/born/weight masks) and the batched executor runs queue append,
staleness filter, weighted aggregation, and delivery counting as ONE
scatter-into-ring + masked-tensordot dispatch per round — no per-main
Python lists, no per-row tree slicing. The ``batched=False`` path keeps
live per-main lists (append / filter / discard at runtime) and merges
through the *same* frame-shaped reduction, so the two paths agree
bit-for-bit on merged parameters and exactly on accounting.

**Dropout-tolerant secure aggregation** (``fl.agg_security='secagg'``,
async only): cohort members additively mask their quantized updates with
signed pairwise pad streams keyed off BB84 shares
(``security.otp.secagg_mask_stream``); masks of partners merged in the
same batch cancel by construction, and a partner that QBER-aborts or
misses its window has its pads cancelled EXACTLY from the surviving rows
(``KeyManager.recover_masks`` / the plan's compiled correction tables) —
mod-2^32 arithmetic, so the list oracle and the ring dispatch are
bit-identical.

**Edge-batched secure exchange (default).** With ``security`` in
{``qkd``, ``qkd_fernet``} the per-edge Algorithm-2 loop — BB84
establishment, pad expansion, OTP-XOR, MAC — used to dispatch once per
(sender, receiver) edge, making the security plane the round's serial
bottleneck. ``edge_batched=True`` consumes the plan's compiled
:class:`~repro.core.plan.EdgeSchedule` instead: all edge keys are
established in ONE vmapped BB84 at plan compile, and each round stage
encrypts/tags/verifies/decrypts every edge's stream in ONE stacked
dispatch (``encrypt_tree_rows`` + ``poly_mac_rows`` over the fixed
dispatch frame). Ciphertexts and MAC tags are bit-identical per edge to
the per-edge oracle (``edge_batched=False``), comm/security accounting is
exactly equal, and QBER aborts / MAC failures surface per edge
(``SecurityError.edges``; ``fl.on_qber_abort`` picks raise-vs-drop).
"""
from __future__ import annotations

from dataclasses import asdict, dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.constellation.topology import ConstellationTrace
from repro.core.comm import CommLog, CommModel
from repro.core.flconfig import SatQFLConfig
from repro.core.localtrain import (
    make_batched_local_train, make_local_train, sample_batch_bounded,
    sample_local_batches,
)
from repro.core.plan import RoundPlan, compile_round_plan
from repro.nn.optim import get_optimizer, inv_sqrt_schedule, constant_schedule
from repro.nn.pytree import tree_bytes, tree_weighted_sum
from repro.security.errors import (CorruptionError, LinkFlapError,
                                   RetryExhaustedError, SatCrashError,
                                   SecurityError)
from repro.security.fernet_lite import TOKEN_OVERHEAD
from repro.security.keys import KeyManager, canonical_edge
from repro.security.mac import (mac_verify, mac_verify_rows, poly_mac_rows,
                                poly_mac_u32)
from repro.security.otp import (decrypt_tree, decrypt_tree_rows, encrypt_tree,
                                encrypt_tree_rows, q32_to_tree,
                                secagg_mask_stream, sum_signed_pads,
                                tree_to_u32, tree_to_u32_rows)
from repro.quantum.teleport import teleport_params

# FedAvg reductions run in full f32: the TPU's default matmul precision
# rounds f32 operands to bf16, which would quantize the global model
HIGHEST = jax.lax.Precision.HIGHEST


# receiver-side batched MAC check — module-level so tests can simulate a
# tampered stage. NOTE: it is read at TRACE time of _secure_stage_impl,
# whose compiled program every trainer shares, so a patch takes effect
# only between two _secure_stage_impl.clear_cache() calls
_mac_rows_verify = mac_verify_rows


# ----------------------------------------------------------------------
# stages that read nothing of a trainer but static settings: jitted once
# here, so every trainer of the same shapes and settings reuses one
# compiled program (a jit of a bound method compiles again per instance)
# ----------------------------------------------------------------------
@partial(jax.jit, static_argnames="verify_mac")
def _secure_stage_impl(stacked, seeds, mac_r, mac_s, tamper, *,
                       verify_mac: bool):
    """ONE edge-batched Algorithm-2 dispatch over the dispatch frame:
    per-row pad expansion + OTP-XOR (encrypt), stacked wire streams,
    batched MAC tag + verify, decrypt. Rows without an edge carry seed
    0 and pass through bit-identically (XOR is an involution).
    ``tamper`` holds the fault plane's injected wire-corruption word
    per row (0 = clean): it flips the first RECEIVED word before the
    receiver's MAC recompute, so tampered rows genuinely fail
    verification in-dispatch."""
    ct = encrypt_tree_rows(stacked, seeds)
    if verify_mac:
        streams = tree_to_u32_rows(ct)
        tags = poly_mac_rows(streams, mac_r, mac_s)
        # receiver-side recompute over the received streams
        rx = streams.at[:, 0].set(streams[:, 0] ^ tamper)
        ok = _mac_rows_verify(rx, tags, mac_r, mac_s)
    else:
        ok = jnp.ones((seeds.shape[0],), bool)
    return decrypt_tree_rows(ct, seeds), ok


# async v2: the batched executor's ring dispatches, the oracle's masking
# and frame merge
@jax.jit
def _ring_send_impl(ring, rows, sats, slots):
    """Scatter this round's trained updates into their ring slots
    (born mod D); masked rows land on the scratch satellite row."""
    return jax.tree_util.tree_map(
        lambda full, x: full.at[sats, slots].set(x), ring, rows)


@jax.jit
def _ring_send_y_impl(ring_y, rows, sats, slots, wq, seeds, signs):
    """secagg send: quantize + pairwise-mask every row, then scatter —
    one dispatch for the whole cohort."""
    y = jax.vmap(secagg_mask_stream)(rows, wq, seeds, signs)
    return ring_y.at[sats, slots].set(y)


# the oracle masks one satellite's update at a time
_mask_one = jax.jit(secagg_mask_stream)


@jax.jit
def _async_merge_impl(ring, mw, anyv, gparams):
    """The entire async merge as one masked tensordot over the ring
    frame: mw (mp, N+1, D) holds the plan's normalized weights (zero
    = cell not in this round's merge), anyv masks empty mains back to
    the global model."""
    mp = mw.shape[0]
    nd = mw.shape[1] * mw.shape[2]
    w2 = mw.reshape(mp, nd)

    def one(x, g):
        xf = x.reshape((nd,) + x.shape[2:]).astype(jnp.float32)
        xb = jnp.broadcast_to(xf[None], (mp,) + xf.shape)
        out = jnp.einsum('gk,gk...->g...', w2, xb, precision=HIGHEST)
        k = anyv.reshape((-1,) + (1,) * (out.ndim - 1))
        return jnp.where(k, out,
                         g.astype(jnp.float32)[None]).astype(x.dtype)

    return jax.tree_util.tree_map(one, ring, gparams)


@jax.jit
def _amerge_frame_impl(entries, rows, wf):
    """Oracle-side merge: scatter the per-main list into the SAME
    (N+1)·D frame and run the identical einsum — zero-weight cells
    are exact no-ops, so this is bit-equal to the ring dispatch."""
    nd = wf.shape[0]

    def one(x):
        frame = jnp.zeros((nd,) + x.shape[1:], x.dtype).at[rows].set(x)
        return jnp.einsum('k,k...->...', wf, frame.astype(jnp.float32),
                          precision=HIGHEST).astype(x.dtype)

    return jax.tree_util.tree_map(one, entries)


@jax.jit
def _async_merge_y_impl(ring_y, sel, corr_seed, corr_sign, sumw, anyv,
                        gparams):
    """secagg merge: masked mod-2^32 sum over the ring + the plan's
    signed correction streams (absent partners' pads cancelled),
    then dequantize — one dispatch over the stacked main axis."""
    mp = sel.shape[0]
    nd = sel.shape[1] * sel.shape[2]
    yf = ring_y.reshape(nd, -1)
    agg = jnp.sum(sel.reshape(mp, nd)[:, :, None] * yf[None],
                  axis=1, dtype=jnp.uint32)
    corr = jax.vmap(
        lambda sd, sg: sum_signed_pads(sd, sg, yf.shape[-1]))(
        corr_seed, corr_sign)
    merged = q32_to_tree(agg + corr, gparams, sumw)

    def keep(m, g):
        k = anyv.reshape((-1,) + (1,) * (m.ndim - 1))
        return jnp.where(k, m, g[None]).astype(g.dtype)

    return jax.tree_util.tree_map(keep, merged, gparams)


def default_sample_batch(data: dict, key, batch_size: int) -> dict:
    # one sampling implementation repo-wide: the batched/oracle parity
    # contract depends on both paths drawing identical indices
    return sample_batch_bounded(data, key, batch_size,
                                next(iter(data.values())).shape[0])


def evaluate(api, model_cfg, params, batch) -> tuple[float, float]:
    """(loss, accuracy). Accuracy = argmax match over the label field."""
    logits, _ = api.forward(model_cfg, params, batch)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - ll)
    acc = jnp.mean((jnp.argmax(lf, -1) == labels).astype(jnp.float32))
    return float(loss), float(acc)


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


@dataclass
class FaultReport:
    """Per-round ledger of the injected-fault plane (plan-derived, so the
    per-client oracle and the batched executor report IDENTICAL counts by
    construction; the parity suites verify the engines' *behavior* —
    drops, merges, accounting — matches these numbers site for site)."""
    round: int
    crashes: int = 0        # satellites whose payload computer was down
    stragglers: int = 0     # satellites paying straggler_extra_s
    link_flaps: int = 0     # transmissions dropped before data moved
    corruptions: int = 0    # payloads MAC-rejected at the receiver
    retries: int = 0        # async retransmissions launched
    lost: int = 0           # async updates lost after max_retries
    recovered: int = 0      # async deliveries that needed ≥ 1 retry


@dataclass
class RoundMetrics:
    round: int
    server_val_loss: float = float("nan")
    server_val_acc: float = float("nan")
    server_test_acc: float = float("nan")
    dev_train_acc: float = float("nan")
    dev_test_acc: float = float("nan")
    dev_val_loss: float = float("nan")
    comm_s: float = 0.0
    security_s: float = 0.0
    participants: int = 0
    teleport_fidelity: float = float("nan")


class SatQFLTrainer:
    """Hierarchical QFL over a constellation trace (paper Algorithm 1)."""

    def __init__(self, model_cfg, api, fl: SatQFLConfig,
                 trace: ConstellationTrace, sat_data: list,
                 server_data: dict, comm: CommModel | None = None,
                 sample_batch=default_sample_batch,
                 eavesdrop_edges: frozenset = frozenset(),
                 batched: bool = True, edge_batched: bool = True):
        self.model_cfg = model_cfg
        self.api = api
        self.fl = fl
        self.trace = trace
        self.sat_data = sat_data
        self.server_data = server_data
        self.comm = comm or CommModel()
        self.sample_batch = sample_batch
        self.n_sats = trace.n_sats
        assert len(sat_data) == self.n_sats
        self._custom_sampler = sample_batch is not default_sample_batch
        # the batched executor samples through the bounded default sampler;
        # a custom sampler has no padding contract -> per-client oracle
        self.batched = batched and not self._custom_sampler
        # every batched dispatch is padded to ONE fixed frame so each mode
        # compiles exactly one stage program, however the trace reshuffles
        # groups round to round (pad rows train throwaway copies and
        # scatter into the scratch slot row)
        self._frame = _next_pow2(self.n_sats)

        key = jax.random.PRNGKey(fl.seed)
        self.key, init_key = jax.random.split(key)
        # local-training randomness is a pure function of (round, satellite)
        # so the batched executor and the per-client oracle draw IDENTICAL
        # batch streams regardless of dispatch order
        self._train_key = jax.random.fold_in(jax.random.PRNGKey(fl.seed),
                                             0x5A7)
        self.global_params = api.init(model_cfg, init_key)
        self._row_nbytes = tree_bytes(self.global_params)

        sched = (inv_sqrt_schedule(fl.lr, warmup=0)
                 if fl.lr_schedule == "inv_sqrt" else constant_schedule(fl.lr))
        self.opt = get_optimizer(fl.optimizer, sched)
        self.opt_states = [self.opt.init(self.global_params)
                           for _ in range(self.n_sats)]
        # batched path keeps optimizer slots stacked (row i = satellite i);
        # row n_sats is a scratch row that absorbs the writes of padding /
        # masked-out dispatch rows, so the in-graph scatter needs no
        # host-side row selection
        self._opt_stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (self.n_sats + 1,) + x.shape),
            self.opt.init(self.global_params))

        # every client padded to one shared length (single compile for all
        # satellites on BOTH paths); the true length rides along so the
        # bounded sampler draws exactly the unpadded indices
        counts = [len(next(iter(d.values()))) for d in sat_data]
        max_n = max(counts)
        self._n_samples = jnp.asarray(counts, jnp.int32)
        self._data_stacked = {
            k: jnp.stack([
                jnp.concatenate([d[k], jnp.zeros((max_n - c,) + d[k].shape[1:],
                                                 d[k].dtype)])
                if c < max_n else d[k]
                for d, c in zip(sat_data, counts)])
            for k in sat_data[0]}

        self.keymgr = KeyManager(jax.random.PRNGKey(fl.seed + 7),
                                 n_qkd_bits=fl.qkd_bits,
                                 eavesdrop_edges=eavesdrop_edges)
        self._qkd_established: set = set()
        self.aborted_edges: set = set()         # QBER aborts, per edge
        # async oracle state: live per-main buffer lists and the deferred
        # in-flight sends, keyed by their compiled delivery round
        self.pending: dict[int, list] = {}      # main -> [(payload, sat, born)]
        self._outbox: dict[int, list] = {}      # deliver_round -> sends
        # test hook: when True, every (round, main) buffer-merge output is
        # recorded as a host tree — the async property suite compares the
        # ring path against the list oracle at this boundary, bit by bit
        self.async_debug = False
        self.async_merge_log: list = []
        self.log = CommLog()
        self.history: list[RoundMetrics] = []
        self.fault_reports: list[FaultReport] = []
        # the edge-batched secure plane covers the OTP(+MAC) modes; the
        # per-edge loop stays as the numerics/accounting oracle
        self.edge_batched = (edge_batched
                             and fl.security in ("qkd", "qkd_fernet"))

        self._local_train = make_local_train(api, model_cfg, fl, self.opt)
        self._jit_local = jax.jit(self._local_train_impl)
        self._batched_train = make_batched_local_train(api, model_cfg, fl,
                                                       self.opt)
        self._jit_stage = jax.jit(self._batched_stage_impl)
        self._jit_dev_eval = jax.jit(self._dev_eval_impl)
        # the whole schedule — roles, assignments, participation, window
        # waits, FedAvg weights, and the secure-exchange EdgeSchedule — is
        # compiled from the trace once up front. For the OTP(+MAC) modes
        # the trainer's KeyManager rides along so every edge key is
        # established in one batched BB84 and the plan's per-(round, edge)
        # seeds/MAC keys/abort masks match the live registry exactly;
        # teleport keeps deriving live in _exchange (sequential RNG).
        self.plan: RoundPlan = compile_round_plan(
            trace, fl,
            sample_counts=counts,
            keymgr=(self.keymgr
                    if (fl.security != "none"
                        or fl.agg_security == "secagg") else None),
            with_seeds=False)

        if fl.mode == "async":
            self._init_async()

    def _init_async(self):
        """Async v2 state: the device-side staleness ring.

        The ring is keyed (satellite, born mod D) — row ``n_sats`` is the
        scratch row absorbing masked scatter writes — so group reshuffles
        never need payload remapping; the compiled
        :class:`~repro.core.plan.StalenessSchedule` masks select directly
        into it.
        """
        fl, st = self.fl, self.plan.stale
        N, D = self.n_sats, st.D
        es = self.plan.edges
        arr_max = max((int(es.ptr[r, 1] - es.ptr[r, 0])
                       for r in range(self.plan.n_rounds)), default=1)
        self._async_exframe = _next_pow2(max(arr_max, 1))
        self._ring = jax.tree_util.tree_map(
            lambda x: jnp.zeros((N + 1, D) + x.shape, x.dtype),
            self.global_params)
        if fl.agg_security == "secagg":
            leaves = jax.tree_util.tree_leaves(self.global_params)
            # user-config validation must RAISE (asserts vanish under -O)
            if not all(jnp.dtype(x.dtype) == jnp.float32 for x in leaves):
                raise ValueError(
                    "agg_security='secagg' quantizes float32 parameters "
                    "only; this model has non-f32 leaves")
            self._q_words = sum(int(np.prod(x.shape)) for x in leaves)
            if 4 * self._q_words != self._row_nbytes:
                raise ValueError(
                    "secagg wire stream size disagrees with the model's "
                    "byte accounting")
            self._ring_y = jnp.zeros((N + 1, D, self._q_words), jnp.uint32)

    # ------------------------------------------------------------------
    # local training
    # ------------------------------------------------------------------
    def _sat_key(self, r: int, sat: int):
        return jax.random.fold_in(jax.random.fold_in(self._train_key, r), sat)

    def _step0(self, r: int):
        # every satellite sits at the same schedule point within a round
        # (the paper's η_t ∝ 1/√t counts ROUNDS of local epochs, not an
        # arbitrary client visiting order)
        return jnp.asarray(r * self.fl.local_steps, jnp.int32)

    def _local_train_impl(self, params, opt_state, data, n, key, step0):
        """Per-client oracle: pre-sample E batches, run the shared program."""
        fl = self.fl
        if self._custom_sampler:
            keys = jax.random.split(key, fl.local_steps)
            batches = jax.vmap(
                lambda k: self.sample_batch(data, k, fl.batch_size))(keys)
        else:
            batches = sample_local_batches(data, key, fl.batch_size, n,
                                           fl.local_steps)
        return self._local_train(params, opt_state, batches, step0)

    def _train_sat(self, sat: int, params, r: int):
        if self._custom_sampler:
            data, n = self.sat_data[sat], jnp.asarray(0, jnp.int32)
        else:
            data = {k: v[sat] for k, v in self._data_stacked.items()}
            n = self._n_samples[sat]
        p, o, loss = self._jit_local(params, self.opt_states[sat], data, n,
                                     self._sat_key(r, sat), self._step0(r))
        self.opt_states[sat] = o
        return p, float(loss)

    # ------------------------------------------------------------------
    # batched local training: one dispatch per client group
    # ------------------------------------------------------------------
    def _broadcast_global(self, k: int):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (k,) + x.shape), self.global_params)

    def _batched_stage_impl(self, params, opt_stacked, data, n_all, ids,
                            scatter_ids, r):
        """One jit-compiled group stage: key derivation, slot/data gather,
        K vmapped local trainings, and the masked optimizer-slot scatter —
        zero host round-trips per stage."""
        fl = self.fl
        rk = jax.random.fold_in(self._train_key, r)
        keys = jax.vmap(lambda s: jax.random.fold_in(rk, s))(ids)
        slots = jax.tree_util.tree_map(lambda x: x[ids], opt_stacked)
        data_k = {kk: v[ids] for kk, v in data.items()}
        n = n_all[ids]
        step0 = (r * fl.local_steps).astype(jnp.int32)
        p, o, losses = self._batched_train(params, slots, data_k, n, keys,
                                           step0)
        # masked rows scatter into the scratch row (index n_sats) — real
        # rows have distinct ids, so the scatter is conflict-free
        new_opt = jax.tree_util.tree_map(
            lambda full, new: full.at[scatter_ids].set(new), opt_stacked, o)
        return p, new_opt, losses

    def _train_group_batched(self, sat_ids: list[int], params_stacked, r: int,
                             update_opt=None, pad_to: int | None = None):
        """Train ``sat_ids`` in ONE vmapped dispatch.

        params_stacked: leaves (K or Kp, ...) — row j holds sat_ids[j]'s
        input model. Returns (params (Kp, ...), losses (Kp,)) — PADDED to
        ``pad_to`` (default: next power of two), so every downstream
        reduction sees bucket-stable shapes and the op/jit caches hold
        O(log n_sats) entries across a whole trace instead of recompiling
        per round. Rows where ``update_opt`` is False (seq-mode chain
        padding) and pad rows leave their optimizer slots untouched.
        """
        k = len(sat_ids)
        kp = pad_to or self._frame
        ids = np.asarray(list(sat_ids) + [sat_ids[0]] * (kp - k))
        upd = np.asarray(([True] * k if update_opt is None
                          else list(update_opt)) + [False] * (kp - k))
        params = jax.tree_util.tree_map(
            lambda x: jnp.concatenate(
                [x, jnp.broadcast_to(x[:1], (kp - x.shape[0],)
                                     + x.shape[1:])])
            if x.shape[0] < kp else x, params_stacked)
        p, self._opt_stacked, losses = self._jit_stage(
            params, self._opt_stacked, self._data_stacked, self._n_samples,
            jnp.asarray(ids), jnp.asarray(np.where(upd, ids, self.n_sats)),
            jnp.asarray(r, jnp.int32))
        return p, losses

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------
    def _dev_eval_impl(self, params, data, n):
        """Batched device-metric pass: masked per-client (loss, acc) over
        the first ≤64 (padded) samples — the padded tail carries exact
        zero weight, so each row equals the unpadded per-client metric."""
        m_cap = min(64, next(iter(data.values())).shape[1])

        def one(d, nn):
            b = {k: v[:m_cap] for k, v in d.items()}
            logits, _ = self.api.forward(self.model_cfg, params, b)
            labels = b["labels"]
            lf = logits.astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(lf, axis=-1)
            ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
            valid = (jnp.arange(m_cap) < jnp.minimum(nn, m_cap)).astype(
                jnp.float32)
            cnt = jnp.maximum(jnp.sum(valid), 1.0)
            loss = jnp.sum((lse - ll) * valid) / cnt
            acc = jnp.sum((jnp.argmax(lf, -1) == labels).astype(jnp.float32)
                          * valid) / cnt
            return loss, acc

        return jax.vmap(one)(data, n)

    # ------------------------------------------------------------------
    # fault plane (seeded FaultSchedule riding on the compiled plan)
    # ------------------------------------------------------------------
    def _crashed(self, r: int, s: int) -> bool:
        f = self.plan.faults
        return f is not None and bool(f.crash[r, s])

    def _strag_extra(self, r: int, s: int) -> float:
        """Straggler wall-clock penalty of sender ``s`` at round ``r`` —
        added wherever that sender's transfer wall (or async transmit
        wait) is recorded, delivered or not, on BOTH execution paths."""
        f = self.plan.faults
        return f.straggler_extra(r, s) if f is not None else 0.0

    def _fault_report_for(self, r: int) -> FaultReport:
        """Tabulate the round's fault ledger from the compiled schedule."""
        f, es = self.plan.faults, self.plan.edges
        n_e = int(es.ptr[r, -1])
        corruptions = 0
        for j in range(n_e):
            # a tampered slot only *detects* when data actually moved —
            # QBER-aborted or flapped slots never reach the receiver MAC
            if (f.tamper[r, j] and not f.link_flap[r, j]
                    and not es.abort[r, j]):
                corruptions += 1
        return FaultReport(
            round=r,
            crashes=int(f.crash[r].sum()),
            stragglers=int(f.straggler[r].sum()),
            link_flaps=int(f.link_flap[r, :n_e].sum())
            + int(f.flap_events[r]),
            corruptions=corruptions,
            retries=int(f.retry_events[r]),
            lost=int(f.lost_events[r]),
            recovered=int(f.recovered_events[r]))

    def _raise_round_faults(self, r: int):
        """``on_fault='raise'``: surface the round's first fault as its
        typed error BEFORE the engines degrade — precedence crash >
        retry-exhaustion > link flap > corruption (worst loss first)."""
        f, es = self.plan.faults, self.plan.edges
        if f.crash[r].any():
            sites = [(r, int(s)) for s in np.where(f.crash[r])[0]]
            raise SatCrashError(
                f"satellite crash(es) at round {r}: {sites}", sites=sites)
        if int(f.lost_events[r]) > 0:
            raise RetryExhaustedError(
                f"{int(f.lost_events[r])} async update(s) lost at round "
                f"{r} after {self.fl.max_retries} retransmission(s)",
                sites=[(r, "async")])
        n_e = int(es.ptr[r, -1])
        flaps = [(int(es.born[r, j]), es.edge_tuple(r, j))
                 for j in range(n_e) if f.link_flap[r, j]]
        if flaps or int(f.flap_events[r]) > 0:
            raise LinkFlapError(
                f"link flap(s) at round {r}: {flaps or 'async transmit'}",
                sites=[(r, e) for _, e in flaps] or [(r, "async")])
        tampers = [(r, es.edge_tuple(r, j)) for j in range(n_e)
                   if (f.tamper[r, j] and not f.link_flap[r, j]
                       and not es.abort[r, j])]
        if tampers:
            raise CorruptionError(
                f"payload corruption at round {r}: MAC rejected "
                f"{[e for _, e in tampers]}", sites=tampers)

    # ------------------------------------------------------------------
    # secure exchange (Algorithm 2) — returns params as seen by receiver
    # ------------------------------------------------------------------
    def _exchange(self, params, edge: tuple, round_idx: int, link: str,
                  concurrent: int = 1):
        """Per-edge Algorithm 2 — the numerics/accounting oracle for the
        edge-batched plane. Returns (params_as_received, wall_s); params
        is None when the edge QBER-aborted under on_qber_abort='drop', or
        when an injected fault (link flap / payload tamper) dropped it."""
        fl = self.fl
        fs = self.plan.faults
        # async ISL arrivals are never flapped live: their flap/retry
        # history was resolved by the plan's retransmit simulation
        flapped = (fs is not None
                   and not (fl.mode == "async" and link == "isl")
                   and fs.flap_of(round_idx, edge))
        nbytes = tree_bytes(params)
        if fl.security == "none":
            if flapped:
                return None, 0.0    # link dropped before any data moved
            t = (self.comm.isl_transfer(nbytes, concurrent) if link == "isl"
                 else self.comm.feeder_transfer(nbytes, concurrent))
            self.log.count_transfer(nbytes)   # wall time recorded per round
            return params, t

        t = 0.0
        ek = self.keymgr.get(edge)
        if ek.edge not in self._qkd_established:
            self._qkd_established.add(ek.edge)
            tq = self.comm.qkd_time(fl.qkd_bits)
            self.log.add_security(tq)
            t += tq
        if ek.compromised:
            # eavesdropping detected at key establishment: the edge aborts
            # BEFORE any data moves (nothing counted for this transfer)
            self.aborted_edges.add(ek.edge)
            if fl.on_qber_abort == "raise":
                raise SecurityError(f"QBER abort on edge {ek.edge}",
                                    edges=[ek.edge])
            return None, t                    # drop: sat leaves C(t)
        if flapped:
            # establishment (when due) was paid; the payload never moved
            return None, t

        t += (self.comm.isl_transfer(nbytes, concurrent) if link == "isl"
              else self.comm.feeder_transfer(nbytes, concurrent))
        self.log.count_transfer(nbytes)   # wall time recorded per round

        if fl.security in ("qkd", "qkd_fernet"):
            seed = ek.round_seed(round_idx)
            ct = encrypt_tree(params, seed)
            tv = fs.tamper_of(round_idx, edge) if fs is not None else 0
            if fl.verify_mac:
                r, s = ek.mac_keys(round_idx)
                stream = tree_to_u32(ct)
                tag = poly_mac_u32(stream, r, s)
                # receiver-side recompute over the RECEIVED words — an
                # injected tamper flips the first wire word, so the MAC
                # genuinely rejects it (drop decisions stay driven by the
                # compiled schedule: a ~2^-31 tag collision changes
                # nothing)
                rx = (stream.at[0].set(stream[0] ^ jnp.uint32(tv))
                      if tv else stream)
                if not bool(mac_verify(rx, tag, r, s)) and not tv:
                    raise SecurityError(f"MAC mismatch on edge {ek.edge}",
                                        edges=[ek.edge])
            tc = 2 * self.comm.crypto_time(nbytes)
            if fl.security == "qkd_fernet":
                # control-plane metadata rides in a Fernet token (paper's
                # QKD+Fernet mode); key material from the QKD seed
                from repro.security.fernet_lite import (fernet_decrypt,
                                                        fernet_encrypt)
                fkey = int(seed).to_bytes(4, "big") * 8
                meta = f"edge={ek.edge} round={round_idx} n={nbytes}".encode()
                tok = fernet_encrypt(fkey, meta)
                if fernet_decrypt(fkey, tok) != meta:
                    raise SecurityError(
                        f"Fernet token corrupt on edge {ek.edge}",
                        edges=[ek.edge])
                tc += 2 * self.comm.crypto_time(len(tok))
            self.log.add_security(tc)
            t += tc
            if tv:
                # corruption detected AFTER transfer + crypto were paid:
                # the receiver discards the payload (per-mode degradation)
                return None, t
            return decrypt_tree(ct, seed), t

        if fl.security == "teleport":
            # feasibility primitive: teleport a sample of (θ, φ) angle pairs
            flat = jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                                    for x in jax.tree_util.tree_leaves(params)])
            n = min(fl.teleport_pairs, flat.shape[0] // 2)
            thetas = jnp.clip(jnp.abs(flat[:n]) % jnp.pi, 0.0, jnp.pi)
            phis = ((flat[n:2 * n] + jnp.pi) % (2 * jnp.pi)) - jnp.pi
            self.key, k = jax.random.split(self.key)
            _, _, fid = teleport_params(k, thetas, phis)
            self._last_fidelity = float(fid)
            tt = self.comm.teleport_time(n)
            self.log.add_security(tt)
            t += tt
            return params, t
        raise ValueError(fl.security)

    def _exchange_rows_batched(self, stacked, rows, edges, r: int,
                               stage: int, link: str, conc, borns=None):
        """Edge-batched Algorithm 2 for one round stage.

        Key material, first-contact and abort masks come from the
        compiled EdgeSchedule; the device work for ALL edges is one
        stacked dispatch, and the stage's Fernet control tokens are one
        batched call. The scalar accounting walks edges in the exact
        per-edge-oracle order, so comm/security totals are equal to the
        float, not just close.
        """
        fl = self.fl
        es = self.plan.edges
        fs = self.plan.faults
        lo, hi = es.stage_bounds(r, stage)
        assert hi - lo == len(edges), (r, stage, hi - lo, len(edges))
        nbytes = self._row_nbytes
        tq = self.comm.qkd_time(fl.qkd_bits)
        walls, delivered, tampv, fern = [], [], [], []
        for j, edge in enumerate(edges):
            e = es.edge_tuple(r, lo + j)
            # link/concurrency/born come from the compiled schedule; the
            # cross-checks catch any drift between plan and engine
            c = int(es.conc[r, lo + j])
            bn = int(es.born[r, lo + j])
            assert e == canonical_edge(edge), (e, edge)
            assert c == conc[j] and link == ("feeder" if es.link[r, lo + j]
                                             else "isl"), (e, link, conc[j])
            assert bn == (borns[j] if borns is not None else r), (e, bn)
            t = 0.0
            if es.first[r, lo + j]:
                self._qkd_established.add(e)
                self.log.add_security(tq)
                t += tq
            if es.abort[r, lo + j]:
                self.aborted_edges.add(e)
                if fl.on_qber_abort == "raise":
                    raise SecurityError(f"QBER abort on edge {e}", edges=[e])
                walls.append(t)
                delivered.append(False)
                tampv.append(0)
                continue
            if fs is not None and fs.link_flap[r, lo + j]:
                # injected flap: establishment (when due) was paid, the
                # payload never moved — the row drops like a QBER abort
                walls.append(t)
                delivered.append(False)
                tampv.append(0)
                continue
            t += (self.comm.isl_transfer(nbytes, c) if link == "isl"
                  else self.comm.feeder_transfer(nbytes, c))
            self.log.count_transfer(nbytes)
            tc = 2 * self.comm.crypto_time(nbytes)
            if fl.security == "qkd_fernet":
                # control-plane metadata: the accounting stays in-loop
                # (token length is structural), the hashlib byte work is
                # deferred to ONE batched token call for the whole stage
                meta = f"edge={e} round={bn} n={nbytes}".encode()
                fern.append((e, int(es.seed[r, lo + j]), meta))
                tc += 2 * self.comm.crypto_time(TOKEN_OVERHEAD + len(meta))
            self.log.add_security(tc)
            t += tc
            walls.append(t)
            # injected tamper: transfer + crypto were paid, then the
            # receiver's MAC rejects the payload — the row is dropped
            tv = int(fs.tamper[r, lo + j]) if fs is not None else 0
            tampv.append(tv)
            delivered.append(tv == 0)

        if fern:
            from repro.security.fernet_lite import (InvalidToken,
                                                    fernet_decrypt_rows,
                                                    fernet_encrypt_rows)
            fkeys = [seed.to_bytes(4, "big") * 8 for _, seed, _ in fern]
            toks = fernet_encrypt_rows(fkeys, [m for _, _, m in fern])
            try:
                back = fernet_decrypt_rows(fkeys, toks)
            except InvalidToken as err:
                raise SecurityError(
                    f"Fernet token corrupt in stage {(r, stage)}: {err}",
                    edges=[e for e, _, _ in fern]) from err
            bad = [e for (e, _, m), p in zip(fern, back) if p != m]
            if bad:
                raise SecurityError(f"Fernet token corrupt on edges {bad}",
                                    edges=bad)

        # device plane: one dispatch for the whole stage, row-aligned on
        # the fixed frame (non-edge / aborted rows get seed 0 → identity)
        K = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        seeds = np.zeros((K,), np.uint32)
        mr = np.zeros((K,), np.uint32)
        ms = np.zeros((K,), np.uint32)
        tam = np.zeros((K,), np.uint32)
        live_rows = []
        for j, row in enumerate(rows):
            if delivered[j]:
                seeds[row] = es.seed[r, lo + j]
                mr[row] = es.mac_r[r, lo + j]
                ms[row] = es.mac_s[r, lo + j]
                live_rows.append((row, edges[j]))
            elif tampv[j]:
                # tampered rows ride the dispatch with their real keys +
                # the injected wire-corruption word, so the batched MAC
                # rejects them in-graph; they stay out of live_rows (the
                # schedule, not the ~2^-31-collision tag, decides drops)
                seeds[row] = es.seed[r, lo + j]
                mr[row] = es.mac_r[r, lo + j]
                ms[row] = es.mac_s[r, lo + j]
                tam[row] = tampv[j]
        out, ok = _secure_stage_impl(stacked, jnp.asarray(seeds),
                                     jnp.asarray(mr), jnp.asarray(ms),
                                     jnp.asarray(tam),
                                     verify_mac=fl.verify_mac)
        if fl.verify_mac and live_rows:
            ok = np.asarray(ok)
            bad = [canonical_edge(e) for row, e in live_rows if not ok[row]]
            if bad:
                raise SecurityError(f"MAC mismatch on edges {bad}",
                                    edges=bad)
        return out, walls, delivered

    def _exchange_rows(self, stacked, rows: list[int], edges: list[tuple],
                       r: int, stage: int, link: str, concurrents=None,
                       borns=None):
        """Algorithm-2 exchange over rows of a stacked (K, ...) tree.

        ``rows[j]`` is the stacked-tree row carrying ``edges[j]``'s
        payload; ``borns[j]`` (default: this round) is the round the
        payload was trained — async deferred deliveries key their pad
        seeds off it. Returns (stacked, walls, delivered) — delivered[j]
        False for QBER-dropped edges (their rows pass through untouched
        and the caller masks them out of aggregation).

        security='none' never touches the tensors — accounting only (the
        stacked aggregate stays on device, zero host round-trips). The
        OTP(+MAC) modes run ONE edge-batched dispatch per stage
        (``edge_batched=True``, the default) or the per-edge oracle loop
        on row slices — identical bits, identical accounting.
        """
        k = len(edges)
        conc = concurrents or [1] * k
        walls = []
        if self.fl.security == "none":
            fs = self.plan.faults
            flap = [False] * k
            if fs is not None and fs.link_flap_rate > 0:
                lo, _ = self.plan.edges.stage_bounds(r, stage)
                flap = [bool(fs.link_flap[r, lo + j]) for j in range(k)]
            delivered = []
            for j, c in enumerate(conc):
                if flap[j]:
                    # link dropped before any data moved: nothing counted
                    walls.append(0.0)
                    delivered.append(False)
                    continue
                t = (self.comm.isl_transfer(self._row_nbytes, c)
                     if link == "isl"
                     else self.comm.feeder_transfer(self._row_nbytes, c))
                self.log.count_transfer(self._row_nbytes)
                walls.append(t)
                delivered.append(True)
            return stacked, walls, delivered
        if self.edge_batched:
            return self._exchange_rows_batched(stacked, rows, edges, r,
                                               stage, link, conc, borns)
        out_rows, delivered = [], []
        for j, (edge, c) in enumerate(zip(edges, conc)):
            p_j = jax.tree_util.tree_map(lambda x: x[rows[j]], stacked)
            p_j, t = self._exchange(p_j, edge,
                                    borns[j] if borns is not None else r,
                                    link, c)
            delivered.append(p_j is not None)
            out_rows.append(p_j)
            walls.append(t)
        live = [j for j in range(k) if delivered[j]]
        if live:
            # one gather-scatter, not one full-tree copy per row
            idx = jnp.asarray([rows[j] for j in live])
            exchanged = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[out_rows[j] for j in live])
            stacked = jax.tree_util.tree_map(
                lambda full, new: full.at[idx].set(new), stacked, exchanged)
        return stacked, walls, delivered

    # ------------------------------------------------------------------
    # shared aggregation + accounting helpers (all schedulers use these)
    # ------------------------------------------------------------------
    def _weight_of(self, s: int) -> float:
        return float(self.plan.weights[s])

    def _aggregate(self, models: list, ws: list):
        """FedAvg: normalized weighted sum; ws parallel to models."""
        wsum = sum(ws)
        return tree_weighted_sum(models, [w / wsum for w in ws])

    def _wmean_rows(self, stacked, w):
        """Weighted mean over the stacked client axis (fp32 accumulate)."""
        wn = jnp.asarray(w, jnp.float32)
        wn = wn / jnp.maximum(jnp.sum(wn), 1e-9)
        return jax.tree_util.tree_map(
            lambda x: jnp.tensordot(wn, x.astype(jnp.float32), axes=(0, 0),
                                    precision=HIGHEST).astype(x.dtype),
            stacked)

    # ------------------------------------------------------------------
    # per-mode group schedulers (per-client oracle) — each merges one
    # {main: secs} group and returns
    # (merged_params, group_wall_s, group_wait_s, delivered_count)
    # ------------------------------------------------------------------
    def _merge_seq(self, r: int, main: int, secs: list):
        # the chain is SERIAL: wall = sum of hop transfers
        theta = self.global_params
        chain_wall = 0.0
        delivered = 0
        for s in secs:
            prev = theta
            theta, _ = self._train_sat(s, theta, r)
            theta, t = self._exchange(theta, (s, main), r, "isl")
            chain_wall += t + self._strag_extra(r, s)
            if theta is None:
                theta = prev    # hop QBER-aborted/faulted: chain reverts
            else:
                delivered += 1
        return theta, chain_wall, 0.0, delivered

    def _merge_sim(self, r: int, main: int, secs: list):
        # parallel uploads CONTEND for the main's ISL aperture
        # (bandwidth / n_concurrent): wall = max over secs
        collected, ws, up_walls = [], [], [0.0]
        for s in secs:
            p, _ = self._train_sat(s, self.global_params, r)
            p, t = self._exchange(p, (s, main), r, "isl",
                                  concurrent=max(len(secs), 1))
            up_walls.append(t + self._strag_extra(r, s))
            if p is None:
                continue            # QBER abort / injected fault: dropped
            collected.append(p)
            ws.append(self._weight_of(s))
        merged = (self._aggregate(collected, ws) if collected
                  else self.global_params)
        return merged, max(up_walls), 0.0, len(collected)

    def _secagg_merge_oracle(self, m: int, fresh: list):
        """Unmask + dequantize one main's secagg merge batch.

        ``fresh``: [(y_stream, sat, born)] in canonical (sat, born) order.
        Masks of partners inside the batch cancel by construction; every
        absent cohort partner's signed pads are recovered from the key
        registry and cancelled EXACTLY (mod-2^32 arithmetic).
        """
        st = self.plan.stale
        agg = jnp.sum(jnp.stack([y["y"] for y, _, _ in fresh]), axis=0,
                      dtype=jnp.uint32)
        inset = {(s, b) for _, s, b in fresh}
        pairs, borns, signs = [], [], []
        for _, s, b in fresh:
            # the cohort is the born round's LIVE group — the compiled
            # pairwise-mask schedule was dealt over it
            for s2 in self.plan.live_groups(b)[m]:
                if s2 == s or (s2, b) in inset:
                    continue            # partner merges here: masks cancel
                pairs.append(canonical_edge((s, s2)))
                borns.append(b)
                signs.append(-(1 if s < s2 else -1))
        agg = agg + self.keymgr.recover_masks(pairs, borns, signs,
                                              self._q_words)
        sumw = sum(int(st.wq[s]) for _, s, _ in fresh)
        return q32_to_tree(agg, self.global_params, jnp.float32(sumw))

    def _async_oracle_prepare(self, r: int):
        """Async v2, per-main-list oracle: one round's buffer mechanics.

        Phase 1 trains every grouped secondary and schedules its send at
        the compiled delivery round (``plan.stale.deliver_round``); phase
        2 drains this round's arrivals — per-edge Algorithm 2, pad seeds
        keyed by BORN round — into the live per-main lists; phase 3 lets
        each current main merge its fresh entries (staleness filter, then
        the same frame-shaped weighted reduction the ring dispatch runs,
        so merged parameters match it bit-for-bit) and discard the rest.
        Window waits are recorded per trained secondary as
        min(wait, comm.window_wait_s) — a windowless satellite clamps to
        the cap instead of silently reporting zero.
        """
        fl, st, cap = self.fl, self.plan.stale, self.comm.window_wait_s
        groups = self.plan.live_groups(r)
        mains = list(groups)
        state = {"merged": {}, "walls": {}, "waits": {}, "delivered": {}}
        secagg = fl.agg_security == "secagg"
        for m, secs in groups.items():
            gw = 0.0
            for s in secs:
                p, _ = self._train_sat(s, self.global_params, r)
                # every sender's transmit wait counts — a window that
                # never reopens clamps to the comm model's mean window
                # wait instead of silently reporting zero; a straggler
                # pays its extra on top of the clamp
                gw = max(gw, min(float(st.tx_wait_s[r, s]), cap)
                         + self._strag_extra(r, s))
                rd = int(st.deliver_round[r, s])
                if rd < 0:
                    continue    # windowless / stale-on-arrival / horizon
                if secagg:
                    p = {"y": _mask_one(
                        p, jnp.int32(int(st.wq[s])),
                        jnp.asarray(st.pair_seed[r, s]),
                        jnp.asarray(st.pair_sign[r, s]))}
                self._outbox.setdefault(rd, []).append((s, m, r, p))
            state["waits"][m] = gw
        for (s, m, b, payload) in self._outbox.pop(r, []):
            p2, t = self._exchange(payload, (s, m), b, "isl")
            # an arrival whose destination lost primary status still costs
            # its transfer; fold it into the round wall via the first group
            key = m if m in groups else mains[0]
            state["walls"][key] = max(state["walls"].get(key, 0.0), t)
            if p2 is None:
                continue                    # QBER abort: update dropped
            self.pending.setdefault(m, []).append((p2, s, b))
        nd = (self.n_sats + 1) * st.D
        for m in mains:
            q = self.pending.get(m, [])
            fresh = sorted([e for e in q
                            if r - e[2] <= fl.max_staleness],
                           key=lambda e: (e[1], e[2]))
            self.pending[m] = []            # merged or stale-discarded
            state["delivered"][m] = len(fresh)
            if not fresh:
                state["merged"][m] = self.global_params
            elif secagg:
                state["merged"][m] = self._secagg_merge_oracle(m, fresh)
            else:
                ws = [float(self.plan.weights[s]) for _, s, _ in fresh]
                wsum = sum(ws)
                wf = np.zeros((nd,), np.float32)
                rows = []
                for (_, s, b), w in zip(fresh, ws):
                    pos = s * st.D + b % st.D
                    wf[pos] = np.float32(w / wsum)
                    rows.append(pos)
                stacked = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *[p for p, _, _ in fresh])
                state["merged"][m] = _amerge_frame_impl(
                    stacked, jnp.asarray(rows), jnp.asarray(wf))
        self._async_state = state

    def _merge_async(self, r: int, main: int, secs: list):
        stt = self._async_state
        if self.async_debug:
            self.async_merge_log.append(
                (r, main, jax.tree_util.tree_map(np.asarray,
                                                 stt["merged"][main])))
        return (stt["merged"][main], stt["walls"].get(main, 0.0),
                stt["waits"][main], stt["delivered"][main])

    _GROUP_SCHEDULERS = {"seq": _merge_seq, "sim": _merge_sim,
                         "async": _merge_async}

    # ------------------------------------------------------------------
    # per-mode group schedulers (constellation-batched executor) — each
    # returns (merged_stacked (n_mains, ...), group_walls, group_waits,
    # delivered_count), one vmapped dispatch per stage
    # ------------------------------------------------------------------
    def _merge_sim_batched(self, r: int, mains: list, groups: dict,
                           mp: int):
        secs_all = [s for m in mains for s in groups[m]]
        group_walls = [0.0] * len(mains)
        if not secs_all:
            return self._broadcast_global(mp), group_walls, [0.0], 0
        sp = self._frame
        p, _ = self._train_group_batched(
            secs_all, self._broadcast_global(sp), r)
        conc = [max(len(groups[m]), 1) for m in mains for _ in groups[m]]
        edges = [(s, m) for m in mains for s in groups[m]]
        p, walls, delivered = self._exchange_rows(
            p, list(range(len(secs_all))), edges, r, 0, "isl", conc)
        # masked weighted group reduction over the stacked client axis
        # (padded to bucket shapes so the reduction compiles once per
        # bucket, not once per round); QBER-dropped rows carry no weight
        a = np.zeros((mp, sp), np.float32)
        j = 0
        for g, m in enumerate(mains):
            for s in groups[m]:
                if delivered[j]:
                    a[g, j] = self._weight_of(s)
                group_walls[g] = max(group_walls[g],
                                     walls[j] + self._strag_extra(r, s))
                j += 1
        row_sum = a.sum(axis=1, keepdims=True)
        empty = row_sum[:, 0] == 0
        an = jnp.asarray(a / np.where(row_sum > 0, row_sum, 1.0))
        keep = jnp.asarray(empty)

        def _merge(x, g):
            m = jnp.tensordot(an, x.astype(jnp.float32), axes=(1, 0),
                              precision=HIGHEST)
            k = keep.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.where(k, g.astype(jnp.float32), m).astype(x.dtype)

        merged = jax.tree_util.tree_map(_merge, p, self._broadcast_global(mp))
        return merged, group_walls, [0.0], int(sum(delivered))

    def _merge_async_batched(self, r: int, mains: list, groups: dict,
                             mp: int):
        """Async v2 round: train (one dispatch), scatter-into-ring (one
        dispatch), exchange the plan's compiled arrivals (one stage
        dispatch), and merge every main's buffer (one dispatch) — no
        per-main lists, no per-row tree slicing."""
        fl, st = self.fl, self.plan.stale
        cap = self.comm.window_wait_s
        secagg = fl.agg_security == "secagg"
        N, D = self.n_sats, st.D
        assert [int(x) for x in st.main_ids[r] if x >= 0] == mains
        group_walls = [0.0] * len(mains)
        group_waits = [0.0] * len(mains)
        secs_all = [s for m in mains for s in groups[m]]
        if secs_all:
            p, _ = self._train_group_batched(
                secs_all, self._broadcast_global(self._frame), r)
            for g, m in enumerate(mains):
                for s in groups[m]:
                    group_waits[g] = max(
                        group_waits[g],
                        min(float(st.tx_wait_s[r, s]), cap)
                        + self._strag_extra(r, s))
            sats = np.full((self._frame,), N, np.int64)
            slots = np.zeros((self._frame,), np.int64)
            for j, s in enumerate(secs_all):
                if st.send_slot[r, s] >= 0:
                    sats[j], slots[j] = s, st.send_slot[r, s]
            if secagg:
                wq = np.ones((self._frame,), np.int32)
                seeds = np.zeros((self._frame,) + st.pair_seed.shape[2:],
                                 np.uint32)
                signs = np.zeros((self._frame,) + st.pair_sign.shape[2:],
                                 np.int32)
                for j, s in enumerate(secs_all):
                    wq[j] = st.wq[s]
                    seeds[j] = st.pair_seed[r, s]
                    signs[j] = st.pair_sign[r, s]
                self._ring_y = _ring_send_y_impl(
                    self._ring_y, p, jnp.asarray(sats), jnp.asarray(slots),
                    jnp.asarray(wq), jnp.asarray(seeds), jnp.asarray(signs))
            else:
                self._ring = _ring_send_impl(
                    self._ring, p, jnp.asarray(sats), jnp.asarray(slots))
        # arrivals: updates whose window has opened by this round (the
        # plan's stage-0 edge list IS the delivery schedule)
        es = self.plan.edges
        lo, hi = es.stage_bounds(r, 0)
        arr = [(int(es.src[r, j]), int(es.dst[r, j]), int(es.born[r, j]))
               for j in range(lo, hi)]
        if arr:
            gathered = None
            if fl.security != "none":
                gi = np.full((self._async_exframe,), N, np.int64)
                gd = np.zeros((self._async_exframe,), np.int64)
                for k, (s, m, b) in enumerate(arr):
                    gi[k], gd[k] = s, b % D
                gi, gd = jnp.asarray(gi), jnp.asarray(gd)
                gathered = ({"y": self._ring_y[gi, gd]} if secagg else
                            jax.tree_util.tree_map(lambda x: x[gi, gd],
                                                   self._ring))
            _, walls, _ = self._exchange_rows(
                gathered, list(range(len(arr))), [(s, m) for s, m, _ in arr],
                r, 0, "isl", borns=[b for _, _, b in arr])
            widx = {m: g for g, m in enumerate(mains)}
            for t, (s, m, b) in zip(walls, arr):
                group_walls[widx.get(m, 0)] = max(
                    group_walls[widx.get(m, 0)], t)
        # the merge: every main's queue append / staleness filter /
        # weighted aggregation is already baked into the plan's masks
        delivered = int(st.merge_count[r].sum())
        anyv = np.zeros((mp,), bool)
        anyv[:st.n_mains_max] = st.merge_any[r]
        if secagg:
            sel = np.zeros((mp, N + 1, D), np.uint32)
            sel[:st.n_mains_max] = st.merge_w[r] > 0
            cs = np.zeros((mp,) + st.corr_seed.shape[2:], np.uint32)
            cg = np.zeros((mp,) + st.corr_sign.shape[2:], np.int32)
            cs[:st.n_mains_max] = st.corr_seed[r]
            cg[:st.n_mains_max] = st.corr_sign[r]
            sw = np.zeros((mp,), np.float32)
            sw[:st.n_mains_max] = st.sum_wq[r]
            merged = _async_merge_y_impl(
                self._ring_y, jnp.asarray(sel), jnp.asarray(cs),
                jnp.asarray(cg), jnp.asarray(sw), jnp.asarray(anyv),
                self.global_params)
        else:
            mw = np.zeros((mp, N + 1, D), np.float32)
            mw[:st.n_mains_max] = st.merge_w[r]
            merged = _async_merge_impl(self._ring, jnp.asarray(mw),
                                       jnp.asarray(anyv), self.global_params)
        if self.async_debug:
            for g, m in enumerate(mains):
                self.async_merge_log.append(
                    (r, m, jax.tree_util.tree_map(
                        lambda x: np.asarray(x[g]), merged)))
        return merged, group_walls, group_waits, delivered

    def _merge_seq_batched(self, r: int, mains: list, groups: dict,
                           mp: int):
        # chains are serial WITHIN a group but parallel ACROSS groups: hop
        # h trains the h-th secondary of every chain as one dispatch
        chains = [groups[m] for m in mains]
        n_chains = len(mains)
        theta = self._broadcast_global(mp)
        chain_walls = [0.0] * n_chains
        delivered = 0
        for hop in range(max((len(c) for c in chains), default=0)):
            active = np.array([len(c) > hop for c in chains]
                              + [False] * (mp - n_chains))
            ids = [c[hop] if len(c) > hop else mains[g]
                   for g, c in enumerate(chains)]
            theta_prev = theta
            p_new, _ = self._train_group_batched(ids, theta, r,
                                                 update_opt=active[:n_chains],
                                                 pad_to=mp)
            mask = jnp.asarray(active)
            theta = jax.tree_util.tree_map(
                lambda new, old: jnp.where(
                    mask.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
                p_new, theta)
            act_rows = [g for g in range(n_chains) if active[g]]
            if self.fl.security == "none":
                fs = self.plan.faults
                dropped = []
                for g in act_rows:
                    s = chains[g][hop]
                    chain_walls[g] += self._strag_extra(r, s)
                    if fs is not None and fs.flap_of(r, (s, mains[g])):
                        dropped.append(g)   # link flapped: nothing moved
                        continue
                    chain_walls[g] += self.comm.isl_transfer(self._row_nbytes)
                    self.log.count_transfer(self._row_nbytes)
                    delivered += 1
            else:
                edges = [(chains[g][hop], mains[g]) for g in act_rows]
                theta, walls, ok = self._exchange_rows(theta, act_rows,
                                                       edges, r, hop, "isl")
                for t, g in zip(walls, act_rows):
                    chain_walls[g] += t + self._strag_extra(r,
                                                            chains[g][hop])
                dropped = [g for g, d in zip(act_rows, ok) if not d]
                delivered += int(sum(ok))
            if dropped:
                # hop QBER-aborted or fault-dropped: those chains revert
                # to their pre-hop state (the trained update never
                # arrived at the next hop)
                idx = jnp.asarray(dropped)
                theta = jax.tree_util.tree_map(
                    lambda full, old: full.at[idx].set(old[idx]),
                    theta, theta_prev)
        return theta, chain_walls, [0.0], delivered

    _BATCHED_SCHEDULERS = {"seq": _merge_seq_batched,
                           "sim": _merge_sim_batched,
                           "async": _merge_async_batched}

    # ------------------------------------------------------------------
    # round schedulers
    # ------------------------------------------------------------------
    def _round_qfl(self, r: int) -> int:
        """Flat FedAvg baseline: every satellite talks to the server over
        its own feeder beam — transfers are PARALLEL (wall = max)."""
        if self.batched:
            ids = self.plan.live_sats(r)        # crashed sats sit out
            npad = self._frame
            if not ids:
                self.log.add_wall(0.0)
                return 0
            p, _ = self._train_group_batched(
                ids, self._broadcast_global(npad), r)
            p, walls, delivered = self._exchange_rows(
                p, list(range(len(ids))), [("gs", s) for s in ids], r, 0,
                "feeder")
            walls = [t + self._strag_extra(r, s)
                     for t, s in zip(walls, ids)]
            self.log.add_wall(2 * max([0.0] + walls))
            w = np.zeros((npad,), np.float32)
            for j, s in enumerate(ids):
                if delivered[j]:
                    w[j] = self.plan.weights[s]
            if any(delivered):
                self.global_params = self._wmean_rows(p, w)
            return int(sum(delivered))
        updates, ws, walls = [], [], [0.0]
        for s in self.plan.live_sats(r):        # crashed sats sit out
            p, _ = self._train_sat(s, self.global_params, r)
            p, t = self._exchange(p, ("gs", s), r, "feeder")
            walls.append(t + self._strag_extra(r, s))
            if p is None:
                continue            # QBER abort / injected fault: dropped
            updates.append(p)
            ws.append(self._weight_of(s))
        self.log.add_wall(2 * max(walls))   # up + broadcast down
        if updates:
            self.global_params = self._aggregate(updates, ws)
        return len(updates)

    def _round_hierarchical(self, r: int) -> int:
        """Algorithm 1 proper: per-group merge (mode-specific), optional
        main-satellite training, feeder uplink, global FedAvg.

        The global FedAvg runs through the SAME ``_frame``-padded
        weighted reduction as the batched driver (zero-weight pad rows
        are exact float no-ops), so the oracle and batched paths differ
        only where local training is vmapped — not in aggregation order.
        """
        fl = self.fl
        merge_group = self._GROUP_SCHEDULERS[fl.mode]
        if fl.mode == "async":
            # cross-group phases (training, deferred arrivals, buffer
            # appends) run once per round; the per-main scheduler below
            # then reads its group's prepared merge
            self._async_oracle_prepare(r)
        mp = self._frame
        main_ws = np.zeros((mp,), np.float32)
        main_models = [None] * mp
        group_walls, feeder_walls, group_waits = [0.0], [0.0], [0.0]
        participants = 0
        for g, (main, secs) in enumerate(self.plan.live_groups(r).items()):
            merged, wall, wait, delivered = merge_group(self, r, main, secs)
            group_walls.append(wall)
            group_waits.append(wait)
            participants += delivered
            if fl.main_trains and not self._crashed(r, main):
                # a crashed MAIN still relays/merges/feeds (the comms bus
                # survives) but its own payload computer skips training
                merged, _ = self._train_sat(main, merged, r)
                participants += 1
            merged, t = self._exchange(merged, (main, "gs"), r, "feeder")
            feeder_walls.append(t + self._strag_extra(r, main))
            if merged is None:
                continue        # feeder QBER abort / fault: group lost
            main_models[g] = merged
            main_ws[g] = (self._weight_of(main)
                          + sum(self._weight_of(s) for s in secs))
        if main_ws.any():
            zeros = jax.tree_util.tree_map(jnp.zeros_like,
                                           self.global_params)
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[m if m is not None else zeros for m in main_models])
            self.global_params = self._wmean_rows(stacked, main_ws)
        # round wall: slowest group (groups run in parallel), then the
        # slowest feeder uplink, plus the global broadcast back down;
        # window waits overlap the same way, so the round blocks on the
        # single slowest wait — recorded once, not once per group
        self.log.add_wait(max(group_waits))
        self.log.add_wall(max(group_walls) + 2 * max(feeder_walls))
        return participants

    def _round_hierarchical_batched(self, r: int) -> int:
        """The same Algorithm-1 round as ``_round_hierarchical``, but with
        local training dispatched once per stage over the stacked client
        axis: secondaries (mode-specific merge), then mains, then one
        weighted reduction for the global model."""
        fl = self.fl
        groups = self.plan.live_groups(r)
        mains = list(groups.keys())
        if not mains:
            self.log.add_wait(0.0)
            self.log.add_wall(0.0)
            return 0
        mp = self._frame
        merged, group_walls, group_waits, participants = \
            self._BATCHED_SCHEDULERS[fl.mode](self, r, mains, groups, mp)
        if fl.main_trains:
            live_m = [not self._crashed(r, m) for m in mains]
            if all(live_m):
                merged, _ = self._train_group_batched(mains, merged, r,
                                                      pad_to=mp)
            else:
                # crashed mains ride the dispatch as masked rows: their
                # optimizer slots stay untouched and their merged params
                # pass through untrained (the payload computer is down)
                p_new, _ = self._train_group_batched(
                    mains, merged, r, update_opt=live_m, pad_to=mp)
                keep = jnp.asarray(np.array(
                    live_m + [False] * (mp - len(mains))))
                merged = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(
                        keep.reshape((-1,) + (1,) * (new.ndim - 1)),
                        new, old),
                    p_new, merged)
            participants += int(sum(live_m))
        feeder_stage = int(self.plan.edges.n_stages[r]) - 1
        merged, feeder_walls, fdel = self._exchange_rows(
            merged, list(range(len(mains))), [(m, "gs") for m in mains], r,
            feeder_stage, "feeder")
        feeder_walls = [t + self._strag_extra(r, m)
                        for t, m in zip(feeder_walls, mains)]
        # pad rows carry zero weight -> the padded reduction is exact;
        # feeder-aborted mains contribute nothing (their group is lost)
        main_ws = np.zeros((mp,), np.float32)
        main_ws[:len(mains)] = [
            (self._weight_of(m)
             + sum(self._weight_of(s) for s in groups[m])) if fdel[g]
            else 0.0
            for g, m in enumerate(mains)]
        if any(fdel):
            self.global_params = self._wmean_rows(merged, main_ws)
        self.log.add_wait(max([0.0] + group_waits))
        self.log.add_wall(max([0.0] + group_walls)
                          + 2 * max([0.0] + feeder_walls))
        return participants

    # ------------------------------------------------------------------
    # round-granularity checkpointing
    # ------------------------------------------------------------------
    # Checkpoint = (device pytree, metadata dict). The pytree carries
    # everything numeric whose bit pattern the resume must reproduce:
    # global params, the teleport RNG key, optimizer slots, and — for
    # async — the staleness ring (batched) or the in-flight buffer
    # payloads (oracle), whose variable-length structure is described by
    # index lists in the metadata so the load template can be rebuilt.
    # Everything host-side (CommLog, history, abort/establishment sets)
    # rides in the metadata. KeyManager state is NOT checkpointed: every
    # plan edge is established deterministically at compile time from
    # fl.seed, so reconstruction is exact.

    def _async_payload_like(self):
        if self.fl.agg_security == "secagg":
            return {"y": jnp.zeros((self._q_words,), jnp.uint32)}
        return self.global_params

    def _stack_opt_states(self):
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                      *self.opt_states)

    def _ckpt_state(self):
        fl = self.fl
        dev = {"params": self.global_params, "key": self.key,
               "opt": (self._opt_stacked if self.batched
                       else self._stack_opt_states())}
        pending_idx, outbox_idx = [], []
        if fl.mode == "async":
            if self.batched:
                dev["ring"] = self._ring
                if fl.agg_security == "secagg":
                    dev["ring_y"] = self._ring_y
            else:
                pend, outp = [], []
                # flatten in dict-insertion + list order; the index lists
                # let restore rebuild the exact same iteration order (the
                # buffer merge and OTP establishment accounting depend on it)
                for mn, lst in self.pending.items():
                    for (p, s, b) in lst:
                        pending_idx.append([int(mn), int(s), int(b)])
                        pend.append(p)
                for rd, lst in self._outbox.items():
                    for (s, mn, b, p) in lst:
                        outbox_idx.append([int(rd), int(s), int(mn), int(b)])
                        outp.append(p)
                dev["pending"] = pend
                dev["outbox"] = outp
        meta = {
            "round": len(self.history),
            "config": asdict(fl),
            "batched": self.batched,
            "edge_batched": self.edge_batched,
            "n_sats": self.n_sats,
            "log": {
                "transfer_s": self.log.transfer_s,
                "wait_s": self.log.wait_s,
                "security_s": self.log.security_s,
                "bytes_moved": self.log.bytes_moved,
                "n_transfers": self.log.n_transfers,
                "per_round": list(self.log.per_round),
                "round_details": self.log.round_details,
            },
            "history": [asdict(h) for h in self.history],
            "fault_reports": [asdict(f) for f in self.fault_reports],
            "aborted_edges": [list(e) for e in self.aborted_edges],
            "qkd_established": [list(e) for e in self._qkd_established],
            "pending_idx": pending_idx,
            "outbox_idx": outbox_idx,
            "last_fidelity": getattr(self, "_last_fidelity", None),
        }
        return dev, meta

    def _ckpt_template(self, meta):
        fl = self.fl
        like = {"params": self.global_params, "key": self.key,
                "opt": (self._opt_stacked if self.batched
                        else self._stack_opt_states())}
        if fl.mode == "async":
            if self.batched:
                like["ring"] = self._ring
                if fl.agg_security == "secagg":
                    like["ring_y"] = self._ring_y
            else:
                pl = self._async_payload_like()
                like["pending"] = [pl] * len(meta["pending_idx"])
                like["outbox"] = [pl] * len(meta["outbox_idx"])
        return like

    def save_round_checkpoint(self, directory: str, keep: int = 3) -> str:
        """Write the full resume state after ``len(self.history)`` rounds."""
        from repro.checkpoint.io import CheckpointManager
        dev, meta = self._ckpt_state()
        return CheckpointManager(directory, keep=keep).save(
            meta["round"], dev, meta)

    def restore_round_checkpoint(self, directory: str,
                                 step: int | None = None) -> int:
        """Restore trainer state; returns the number of completed rounds.

        Resuming from round r and running to the end produces BIT-identical
        final parameters and communication accounting to the uninterrupted
        run (the crash-resume parity suite holds this across all four
        modes and both execution paths)."""
        from repro.checkpoint.io import load_checkpoint, read_metadata
        step, meta = read_metadata(directory, step)
        if meta.get("config") != asdict(self.fl):
            raise ValueError(
                "checkpoint was written under a different SatQFLConfig; "
                "resume with the identical configuration")
        if (meta.get("batched") != self.batched
                or meta.get("edge_batched") != self.edge_batched
                or meta.get("n_sats") != self.n_sats):
            raise ValueError(
                "checkpoint execution-path fingerprint (batched/"
                "edge_batched/n_sats) does not match this trainer")
        dev, _, meta = load_checkpoint(directory, self._ckpt_template(meta),
                                       step)
        fl = self.fl
        self.global_params = dev["params"]
        self.key = dev["key"]
        if self.batched:
            self._opt_stacked = dev["opt"]
        else:
            self.opt_states = [
                jax.tree_util.tree_map(lambda x, i=i: x[i], dev["opt"])
                for i in range(self.n_sats)]
        if fl.mode == "async":
            if self.batched:
                self._ring = dev["ring"]
                if fl.agg_security == "secagg":
                    self._ring_y = dev["ring_y"]
            else:
                self.pending, self._outbox = {}, {}
                for (mn, s, b), p in zip(meta["pending_idx"],
                                         dev["pending"]):
                    self.pending.setdefault(int(mn), []).append(
                        (p, int(s), int(b)))
                for (rd, s, mn, b), p in zip(meta["outbox_idx"],
                                             dev["outbox"]):
                    self._outbox.setdefault(int(rd), []).append(
                        (int(s), int(mn), int(b), p))
        lg = meta["log"]
        self.log = CommLog(
            transfer_s=lg["transfer_s"], wait_s=lg["wait_s"],
            security_s=lg["security_s"], bytes_moved=lg["bytes_moved"],
            n_transfers=lg["n_transfers"], per_round=list(lg["per_round"]),
            round_details=[
                # msgpack flattens tuples to lists; the parity suites
                # compare details with ==, so restore the cum tuple shape
                {**d, "cum": tuple(d["cum"])} for d in lg["round_details"]])
        self.history = [RoundMetrics(**h) for h in meta["history"]]
        self.fault_reports = [FaultReport(**f) for f in meta["fault_reports"]]
        self.aborted_edges = {tuple(e) for e in meta["aborted_edges"]}
        self._qkd_established = {tuple(e) for e in meta["qkd_established"]}
        if meta.get("last_fidelity") is not None:
            self._last_fidelity = meta["last_fidelity"]
        return step

    # ------------------------------------------------------------------
    # one round of Algorithm 1
    # ------------------------------------------------------------------
    def run_round(self, r: int) -> RoundMetrics:
        fl = self.fl
        if r >= self.plan.n_rounds:
            raise IndexError(
                f"round {r} beyond the compiled plan ({self.plan.n_rounds} "
                f"rounds); construct the trainer with fl.n_rounds >= {r + 1}")
        if fl.on_fault == "raise" and self.plan.faults is not None:
            # surface the round's injected faults as typed errors BEFORE
            # the engines degrade (mirrors on_qber_abort='raise')
            self._raise_round_faults(r)
        m = RoundMetrics(round=r)
        round_t0 = self.log.total_s
        sec_t0 = self.log.security_s

        if fl.mode == "qfl":
            m.participants = self._round_qfl(r)
        elif fl.mode in self._GROUP_SCHEDULERS:
            m.participants = (self._round_hierarchical_batched(r)
                              if self.batched
                              else self._round_hierarchical(r))
        else:
            raise ValueError(fl.mode)

        m.comm_s = self.log.total_s - round_t0
        m.security_s = self.log.security_s - sec_t0
        fr = None
        if self.plan.faults is not None:
            fr = self._fault_report_for(r)
            self.fault_reports.append(fr)
        self.log.close_round(faults=asdict(fr) if fr is not None else None)
        if hasattr(self, "_last_fidelity"):
            m.teleport_fidelity = self._last_fidelity

        if r % fl.eval_every == 0:
            m.server_val_loss, m.server_val_acc = evaluate(
                self.api, self.model_cfg, self.global_params,
                self.server_data["val"])
            _, m.server_test_acc = evaluate(
                self.api, self.model_cfg, self.global_params,
                self.server_data["test"])
            # sampled device metrics: ONE vmapped dispatch over the first
            # S stacked client datasets instead of S sequential host calls
            S = min(self.n_sats, 8)
            dev_vl, dev_tr = self._jit_dev_eval(
                self.global_params,
                {k: v[:S] for k, v in self._data_stacked.items()},
                self._n_samples[:S])
            m.dev_train_acc = float(np.mean(np.asarray(dev_tr)))
            m.dev_val_loss = float(np.mean(np.asarray(dev_vl)))
            m.dev_test_acc = m.server_test_acc
        self.history.append(m)
        return m

    def run(self, ckpt_dir: str | None = None, ckpt_every: int = 1,
            ckpt_keep: int = 3) -> list[RoundMetrics]:
        """Run all rounds; with ``ckpt_dir``, checkpoint every
        ``ckpt_every`` rounds and auto-resume from the latest step if the
        directory already holds one (kill-and-restart safe)."""
        start = 0
        if ckpt_dir is not None:
            from repro.checkpoint.io import latest_step
            if latest_step(ckpt_dir) is not None:
                start = self.restore_round_checkpoint(ckpt_dir)
        for r in range(start, self.fl.n_rounds):
            self.run_round(r)
            if ckpt_dir is not None and (
                    (r + 1) % ckpt_every == 0 or r + 1 == self.fl.n_rounds):
                self.save_round_checkpoint(ckpt_dir, keep=ckpt_keep)
        return self.history
