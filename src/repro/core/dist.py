"""Mesh-scale sat-QFL: one jit-compiled FL round on the production mesh.

**Stacked-satellite formulation.** The satellite index is a leading axis of
every parameter/optimizer/data tensor, sharded over the batch-ish mesh axes
(("pod", "data")). One mesh slice == one satellite's compute board; the
model dims shard over "model" (tensor parallelism inside a satellite).
The paper's schedules then become collectives:

  simultaneous — local steps, then mean over the satellite axis
                 (GSPMD lowers to a two-tier all-reduce: intra-pod =
                 secondary→primary ISL traffic, inter-pod = feeder links)
  asynchronous — the same mean but masked by the visibility-window
                 participation vector; non-participants' updates are kept
                 in a staleness buffer and folded in within Δ_max rounds
  sequential   — ring: train, pass parameters to the next satellite
                 (jnp.roll over the sharded axis -> collective_permute).
                 N parallel chains run pipelined — a beyond-paper
                 throughput fix for the paper's serial chain (DESIGN §5).

Security (Algorithm 2) runs in-graph:

  otp     — paper-faithful: OTP-XOR each satellite's update with its
            edge pad, move ciphertext, decrypt at the aggregator. XOR∘XOR
            would cancel algebraically, so optimization_barrier pins the
            ciphertext movement (the honest data path).
  secagg  — beyond-paper: pairwise additive masks Σ m_i = 0 (ring PRF
            construction), so the masked updates psum to the true sum
            with NO gather and no per-edge decrypt — O(d) instead of
            O(N·d) aggregation traffic. See EXPERIMENTS §Perf.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flconfig import SatQFLConfig
from repro.core.localtrain import make_local_train
from repro.nn.optim import Optimizer
from repro.security.mac import mac_verify_rows, poly_mac_rows
from repro.security.otp import encrypt_tree_rows, tree_to_u32_rows
from repro.sharding.context import DistCtx


class FLState(NamedTuple):
    params: Any          # stacked (n_sat, ...) pytree
    opt_slots: Any       # stacked optimizer slots
    stale: Any           # async: buffered undelivered updates (n_sat, ...)
    stale_age: jax.Array # (n_sat,) int32 rounds since buffered (-1 = none)
    round_idx: jax.Array # scalar int32


# ---------------------------------------------------------------------------
# security primitives over stacked pytrees
# ---------------------------------------------------------------------------

def otp_stacked(tree, seeds_u32):
    """OTP over a stacked pytree; seeds (n_sat,) uint32. Involution.

    Thin alias for the shared edge-batched security plane
    (``repro.security.otp.encrypt_tree_rows``) — the same stacked
    pad-expansion + XOR program the host engine dispatches per round
    stage, so the two engines cannot drift.
    """
    return encrypt_tree_rows(tree, seeds_u32)


def mac_tags_stacked(tree, round_seeds_u32):
    """Per-satellite MAC tags over a stacked ciphertext tree, in-graph.

    The (r, s) key pair is derived from the per-round seed with the same
    integer mix as ``repro.security.keys.mac_key_mix`` (uint32 wraparound
    == the host helper's low 32 bits). Returns (tags (N,), r (N,), s (N,));
    the receiver recomputes its own streams from the moved ciphertext.
    """
    r = round_seeds_u32 ^ jnp.uint32(0xA5A5A5A5)
    s = (round_seeds_u32 * jnp.uint32(747796405)) + jnp.uint32(2891336453)
    return poly_mac_rows(tree_to_u32_rows(tree), r, s), r, s


def secagg_mask(tree, seeds_u32, sign_split: int):
    """Pairwise-additive masking: θ_i + PRF(i) − PRF(i+1 mod N).

    The masks telescope to zero over the satellite axis, so the (weighted
    by 1/N) sum of masked updates equals the true mean while each
    individual update is blinded. fp32 mask magnitude is scaled small to
    bound fp cancellation error.
    """
    n = seeds_u32.shape[0]
    base = jax.vmap(jax.random.key)(seeds_u32)
    nxt = jnp.roll(seeds_u32, -1)
    base_n = jax.vmap(jax.random.key)(nxt)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for i, leaf in enumerate(leaves):
        def mk(keyv):
            keys = jax.vmap(lambda k: jax.random.fold_in(k, i + sign_split))(keyv)
            def one(k, row):
                return jax.random.normal(k, row.shape, jnp.float32)
            return jax.vmap(one)(keys, leaf)
        m = mk(base) - mk(base_n)
        out.append((leaf.astype(jnp.float32) + m).astype(leaf.dtype)
                   if leaf.dtype != jnp.float32 else leaf + m)
    return jax.tree_util.tree_unflatten(treedef, out)


def make_secure_exchange(security: str):
    """Returns f(tree, seeds, round) -> tree_as_received_by_aggregator."""
    if security in ("none", "otp_gather"):   # otp_gather handled in round_fn
        return lambda tree, seeds, r: tree

    if security == "otp":
        def exchange(tree, seeds, r):
            s = seeds ^ (r.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
            ct = otp_stacked(tree, s)
            # pin the ciphertext as the moved representation
            ct = jax.lax.optimization_barrier(ct)
            return otp_stacked(ct, s)
        return exchange

    if security == "secagg":
        def exchange(tree, seeds, r):
            s = seeds ^ (r.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
            return secagg_mask(tree, s, sign_split=1000)
        return exchange

    raise ValueError(security)


# ---------------------------------------------------------------------------
# the round
# ---------------------------------------------------------------------------

def _wmean_sats(tree, w):
    """Weighted mean over the satellite axis, broadcast back. w (N,) sums>0."""
    wn = w / jnp.maximum(jnp.sum(w), 1e-9)

    def red(x):
        m = jnp.tensordot(wn.astype(jnp.float32), x.astype(jnp.float32),
                          axes=(0, 0), precision=jax.lax.Precision.HIGHEST)
        return jnp.broadcast_to(m[None], x.shape).astype(x.dtype)

    return jax.tree_util.tree_map(red, tree)


def make_fl_round(model_cfg, api, fl: SatQFLConfig, optimizer: Optimizer,
                  n_sats: int, security: str = "none", seq_hops: int = 4,
                  ctx: DistCtx | None = None):
    """Build the jit-able round function.

    round_fn(state, batches, part_mask, seeds, weights=None,
             fault_mask=None) -> (state, metrics)

      batches:   pytree, leaves (n_sat, steps, batch, ...) — steps is
                 local_steps (sim/async/qfl) or seq_hops·local_steps (seq:
                 each hop of the chain consumes its own slice)
      part_mask: (n_sat,) float — visibility-window participation (async)
      seeds:     (n_sat,) uint32 — per-edge QKD-derived pad seeds
      weights:   (n_sat,) float — FedAvg sample-count weights (None = uniform)
      fault_mask:(n_sat,) float, 1 = healthy / 0 = crashed (None = all
                 healthy; ``plan.fault_mask(r)``). Graceful degradation
                 mirrors the host engine: a crashed satellite trains
                 nothing (params/opt slots frozen), sim/qfl drop its
                 FedAvg weight, seq passes the chain through its hop
                 untrained, async removes it from both delivery and
                 rebuffering (its stale entry just ages)

    All three per-round inputs come from a compiled
    :class:`repro.core.plan.RoundPlan` (``plan.dist_inputs(r)``) so the
    in-graph engine follows the constellation trace, not caller guesses.
    """
    if security == "otp_gather" and fl.mode not in ("sim", "qfl"):
        raise ValueError("otp_gather models the central-server topology — "
                         "sim/qfl schedules only")
    if security == "secagg" and fl.mode != "sim":
        # the ring-PRF masks telescope only over the FULL satellite set:
        # sequential is point-to-point, and async's partial participation
        # would need dropout-tolerant secret sharing (Bonawitz et al.) —
        # out of scope. Paper-faithful 'otp' covers those modes.
        raise ValueError("secagg requires full participation — only the "
                         "'sim' schedule; use 'otp' for seq/async")
    exchange = make_secure_exchange(security)

    # the per-satellite local-training program is the SAME one the host
    # engine's batched executor vmaps (repro.core.localtrain) — this engine
    # simply puts the stacked-satellite axis in front of it
    local_train = make_local_train(api, model_cfg, fl, optimizer)
    vtrain = jax.vmap(local_train, in_axes=(0, 0, 0, None))

    def _hop_batches(batches, hop):
        """Hop h of the chain trains on steps [h·E, (h+1)·E) of the batch
        axis (wrapping if the caller under-provisioned), so sequential
        hops see DISTINCT data instead of replaying the same batches."""
        E = fl.local_steps

        def slc(x):
            idx = (jnp.arange(E) + hop * E) % x.shape[1]
            return jnp.take(x, idx, axis=1)

        return jax.tree_util.tree_map(slc, batches)

    def round_fn(state: FLState, batches, part_mask, seeds, weights=None,
                 fault_mask=None):
        r = state.round_idx
        step0 = r * fl.local_steps
        if fault_mask is not None and security == "secagg":
            # the ring-PRF masks telescope to zero only over the FULL
            # satellite set — a dropped row would leave its neighbors'
            # pads uncancelled (the host engine's async secagg has the
            # dropout-recovery construction; this in-graph one does not)
            raise ValueError("secagg cannot drop crashed rows — "
                             "run faults with security 'none'/'otp'")
        # secagg's ring masks telescope to zero only under UNIFORM weights;
        # sample-count FedAvg there would need weighted secret sharing
        if weights is None or security == "secagg":
            w_agg = jnp.ones((n_sats,))
        else:
            w_agg = weights
        mac_ok = None           # otp_gather: per-round integrity verdict

        def _freeze_faulted(new, old):
            """Crashed rows keep their pre-round value (no local training)."""
            if fault_mask is None:
                return new
            return jax.tree_util.tree_map(
                lambda n, s: jnp.where(_bshape(fault_mask, n) > 0, n, s),
                new, old)

        def _masked_mean_loss(l):
            """Mean loss over the rows that actually trained."""
            if fault_mask is None:
                return jnp.mean(l)
            return jnp.sum(l * fault_mask) / jnp.maximum(
                jnp.sum(fault_mask), 1.0)

        if fl.mode == "seq":
            # pipelined sequential: train -> secure hand-off to next satellite
            p, o = state.params, state.opt_slots
            losses = jnp.zeros(())
            for hop in range(seq_hops):
                p2, o2, l = vtrain(p, o, _hop_batches(batches, hop),
                                   step0 + hop)
                # a crashed satellite's hop is a pass-through: the chain
                # reroutes over it untrained, its optimizer slot frozen
                p, o = _freeze_faulted(p2, p), _freeze_faulted(o2, o)
                p = exchange(p, seeds ^ jnp.uint32(hop + 1), r)
                p = jax.tree_util.tree_map(lambda x: jnp.roll(x, 1, axis=0), p)
                losses = losses + _masked_mean_loss(l)
            # each slot now holds a chain that visited seq_hops satellites,
            # so per-satellite sample weights don't map to slots — uniform
            new_params = _wmean_sats(p, jnp.ones((n_sats,)))
            mean_loss = losses / seq_hops
            new_stale, new_age = state.stale, state.stale_age
        else:
            p, o, l = vtrain(state.params, state.opt_slots, batches, step0)
            p = _freeze_faulted(p, state.params)
            o = _freeze_faulted(o, state.opt_slots)
            mean_loss = _masked_mean_loss(l)
            if fl.mode == "sim" or fl.mode == "qfl":
                w = (w_agg if fault_mask is None else w_agg * fault_mask)
                if security == "otp_gather":
                    # PAPER-FAITHFUL topology: the aggregator receives every
                    # satellite's ciphertext (an all-gather of the stacked
                    # axis: O(N·d) bytes/device) and decrypts centrally.
                    # Compare with 'secagg' (masked psum, O(d)) — §Perf D.
                    s = seeds ^ (r.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
                    ct = otp_stacked(p, s)
                    # sender-side tags over the stacked ciphertexts — the
                    # same batched MAC plane the host engine dispatches
                    tags, rk, sk = mac_tags_stacked(ct, s)
                    from jax.sharding import PartitionSpec as P
                    ct = jax.lax.with_sharding_constraint(
                        ct, jax.tree_util.tree_map(
                            lambda leaf: P(*([None] * leaf.ndim)), ct))
                    # aggregator-side verify of every edge, in-graph
                    mac_ok = jnp.all(mac_verify_rows(
                        tree_to_u32_rows(ct), tags, rk, sk))
                    moved = otp_stacked(ct, s)        # decrypt at aggregator
                else:
                    moved = exchange(p, seeds, r)
                new_params = _wmean_sats(moved, w)
                if fault_mask is not None:
                    # every satellite crashed → keep the model (a
                    # zero-weight mean would zero every parameter)
                    any_w = jnp.sum(w) > 0
                    new_params = jax.tree_util.tree_map(
                        lambda m, old: jnp.where(any_w, m, old),
                        new_params, state.params)
                new_stale, new_age = state.stale, state.stale_age
            elif fl.mode == "async":
                # deliver participants now; buffer the rest (bounded
                # staleness). A crashed satellite neither delivers nor
                # rebuffers — its frozen params are not an update
                live = (part_mask if fault_mask is None
                        else part_mask * fault_mask)
                moved = exchange(p, seeds, r)
                sel_now = live                            # binary selects
                # stale buffer usable if within Δ_max
                stale_ok = ((state.stale_age >= 0)
                            & (state.stale_age <= fl.max_staleness))
                # keyed off sel_now, not part_mask: a crashed-but-visible
                # satellite delivers nothing fresh, yet its previously
                # buffered update is aggregator-side and still folds in
                sel_stale = stale_ok.astype(jnp.float32) * (1.0 - sel_now)
                combined = jax.tree_util.tree_map(
                    lambda now, st: (now.astype(jnp.float32)
                                     * _bshape(sel_now, now)
                                     + st.astype(jnp.float32)
                                     * _bshape(sel_stale, st)).astype(now.dtype),
                    moved, state.stale)
                # sample-count weights enter only the normalized mean
                w_tot = (sel_now + sel_stale) * w_agg
                # nobody delivered and no usable stale buffer → keep the
                # model (a zero-weight mean would zero every parameter)
                any_w = jnp.sum(w_tot) > 0
                new_params = jax.tree_util.tree_map(
                    lambda m, old: jnp.where(any_w, m, old),
                    _wmean_sats(combined, w_tot), state.params)
                # rebuffer: non-participants' fresh updates wait for a window;
                # crashed rows produced no update, so their entry just ages
                new_stale = jax.tree_util.tree_map(
                    lambda fresh, st: jnp.where(
                        _bshape(live, fresh) > 0, fresh.astype(jnp.float32),
                        st.astype(jnp.float32)).astype(fresh.dtype),
                    moved, state.stale)
                new_age = jnp.where(live > 0, 0, state.stale_age + 1)
            else:
                raise ValueError(fl.mode)

        metrics = {"loss": mean_loss}
        if mac_ok is not None:
            metrics["mac_ok"] = mac_ok
        return FLState(new_params, o, new_stale, new_age, r + 1), metrics

    return round_fn


def _bshape(w, like):
    """Broadcast (N,) weights against (N, ...) leaf."""
    return w.reshape((-1,) + (1,) * (like.ndim - 1)).astype(jnp.float32)


def fl_init_state(model_cfg, api, optimizer, n_sats: int, key) -> FLState:
    keys = jax.random.split(key, n_sats)
    params = jax.vmap(lambda k: api.init(model_cfg, k))(keys)
    # every satellite starts from the same global model (round 0 broadcast)
    params = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[:1], x.shape), params)
    stale = jax.tree_util.tree_map(jnp.zeros_like, params)
    return FLState(params=params,
                   opt_slots=jax.vmap(optimizer.init)(params),
                   stale=stale,
                   stale_age=jnp.full((n_sats,), -1, jnp.int32),
                   round_idx=jnp.zeros((), jnp.int32))


def stack_client_data(sat_data: list) -> dict:
    """Per-satellite datasets -> one (n_sat, n, ...) array per field,
    every client cut to the shortest one's length n."""
    n = min(len(next(iter(d.values()))) for d in sat_data)
    return {k: jnp.stack([d[k][:n] for d in sat_data]) for k in sat_data[0]}


def sample_round_batches(data: dict, rng: np.random.Generator, steps: int,
                         batch_size: int) -> dict:
    """One round's ``batches`` for ``round_fn``: leaves (n_sat, steps,
    batch_size, ...), drawn uniformly from each satellite's own rows of
    the stacked ``data`` — one gather per field."""
    n_sat, n = next(iter(data.values())).shape[:2]
    idx = jnp.asarray(rng.integers(0, n, (n_sat, steps, batch_size)))
    return {k: jax.vmap(lambda x, i: x[i])(v, idx) for k, v in data.items()}


def sat_axis_shardings(tree, mesh):
    """A NamedSharding per leaf: the leading (satellite) axis split over
    the mesh's "data" axis, scalars replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, P("data") if x.shape else P()), tree)


def fl_input_specs(model_cfg, api, fl: SatQFLConfig, n_sats: int,
                   feature_shape: tuple, n_classes: int, seq_hops: int = 1):
    """ShapeDtypeStructs for the FL dry-run (classifier workloads)."""
    steps = fl.local_steps * (seq_hops if fl.mode == "seq" else 1)
    bs = (n_sats, steps, fl.batch_size)
    return {
        "batches": {
            "features": jax.ShapeDtypeStruct(bs + feature_shape, jnp.float32),
            "labels": jax.ShapeDtypeStruct(bs, jnp.int32),
        },
        "part_mask": jax.ShapeDtypeStruct((n_sats,), jnp.float32),
        "seeds": jax.ShapeDtypeStruct((n_sats,), jnp.uint32),
        "weights": jax.ShapeDtypeStruct((n_sats,), jnp.float32),
    }
