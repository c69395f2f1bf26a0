"""Plain reference for the first rounds of a sat-QFL run.

Written from the paper's description (arXiv 2509.16504, Algorithms 1
and 2) in straightforward ``jax.numpy``, float32, with every contraction
at the precision it is given: ``"highest"`` (full float32) for the
reference, ``"high"`` for the control, the nearest precision below:
three bfloat16 passes per product, spelled out here so that it means the
same on every backend. It imports nothing of the program and takes nothing the
program made: the weights are the benchmark's own, the groups come from
the constellation's access arrays, and the FedAvg weights from the
satellites' sample counts.

What it shares with the program is the published contract of the data
stream: satellite ``s`` at round ``r`` draws its ``E`` local batches with
``randint`` from ``split(fold_in(fold_in(fold_in(PRNGKey(seed), 0x5A7),
r), s), E)``, uniform over its rows, and steps SGD at
``lr / sqrt(max(r*E + e, 1))``.

Per round (``sim`` and ``async``, every main satellite training):
  * each secondary trains from the global model, plain gate-by-gate
    statevector forward pass, gradient by reverse-mode autodiff (the
    parameter-shift rule gives the same gradient exactly);
  * the secure exchange (QKD-keyed OTP with MAC) is the identity on a
    delivered update; the staleness buffer of ``async`` delivers an
    update born at round b at the first round whose trace step is past
    its next ISL window, and merges it if its staleness is within
    ``max_staleness``; ``secagg`` merges in 16-bit fixed point, integer
    weights, with the pairwise masks cancelled;
  * each main averages its group's updates by sample count, trains, and
    the server averages the mains weighted by their groups' counts.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

TRAIN_SALT = 0x5A7
FRAC_BITS = 16            # secagg fixed point: 2^16 steps per unit
CLIP = 1 << 20            # |q| cap (|x| <= 16)
W_MAX = 7                 # integer FedAvg weight cap


# ---------------------------------------------------------------------------
# contractions at a stated precision
# ---------------------------------------------------------------------------

def _bf16_parts(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _three_pass(eq, a, b):
    """float32 product from bfloat16 parts: hi*hi + hi*lo + lo*hi."""
    (ah, al), (bh, bl) = _bf16_parts(a), _bf16_parts(b)

    def f(x, y):
        return jnp.einsum(eq, x, y, precision=jax.lax.Precision.HIGHEST)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


def contract(eq: str, a, b, prec: str):
    """``einsum(eq, a, b)`` at ``prec`` ("highest" or "high")."""
    if prec == "highest":
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
    if prec != "high":
        raise ValueError(f"unknown precision {prec!r}")
    if not (jnp.iscomplexobj(a) or jnp.iscomplexobj(b)):
        return _three_pass(eq, a, b)
    ar, ai = jnp.real(a).astype(jnp.float32), jnp.imag(a).astype(jnp.float32)
    br, bi = jnp.real(b).astype(jnp.float32), jnp.imag(b).astype(jnp.float32)
    re = _three_pass(eq, ar, br) - _three_pass(eq, ai, bi)
    im = _three_pass(eq, ar, bi) + _three_pass(eq, ai, br)
    return jax.lax.complex(re, im)


# ---------------------------------------------------------------------------
# the VQC, gate by gate
# ---------------------------------------------------------------------------

def ry(a):
    c, s = jnp.cos(a / 2), jnp.sin(a / 2)
    return jnp.stack([jnp.stack([c, -s], -1),
                      jnp.stack([s, c], -1)], -2).astype(jnp.complex64)


def rz(a):
    e = jnp.exp(-0.5j * a.astype(jnp.complex64))
    z = jnp.zeros_like(e)
    return jnp.stack([jnp.stack([e, z], -1),
                      jnp.stack([z, jnp.conj(e)], -1)], -2)


def apply_gate(state, gate, q, prec):
    """state (B, 2^n), little-endian; gate (2, 2) or (B, 2, 2) on qubit q."""
    b, dim = state.shape
    lo = 1 << q
    st = state.reshape(b, dim // (2 * lo), 2, lo)
    eq = "ab,nhbl->nhal" if gate.ndim == 2 else "nab,nhbl->nhal"
    return contract(eq, gate, st, prec).reshape(b, dim)


def cz_signs(nq: int, q1: int, q2: int) -> np.ndarray:
    idx = np.arange(1 << nq)
    return np.where((idx >> q1) & (idx >> q2) & 1, -1.0, 1.0).astype(
        np.float32)


def logits(model: dict, params, x, prec):
    """x (B, n_features) -> (B, n_classes)."""
    nq, nl, nc = model["vqc_qubits"], model["vqc_layers"], model["n_classes"]
    dim = 1 << nq
    state = jnp.zeros((x.shape[0], dim), jnp.complex64).at[:, 0].set(1.0)
    for q in range(min(model["n_features"], nq)):
        state = apply_gate(state, ry(x[:, q]), q, prec)
    for layer in range(nl):
        for q in range(nq):
            state = apply_gate(state, ry(params["theta"][layer, q]), q, prec)
            state = apply_gate(state, rz(params["phi"][layer, q]), q, prec)
        for q in range(nq):
            state = state * cz_signs(nq, q, (q + 1) % nq)
    probs = state.real ** 2 + state.imag ** 2
    idx = np.arange(dim)
    zsign = np.stack([np.where((idx >> c) & 1, -1.0, 1.0)
                      for c in range(nc)]).astype(np.float32)
    exps = contract("bi,ci->bc", probs, jnp.asarray(zsign), prec)
    return params["w_out"] * exps + params["b_out"]


def loss(model: dict, params, x, y, prec):
    lg = logits(model, params, x, prec)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0])


def init_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed), 0xB3)


def init_params(model: dict, key):
    """The benchmark's weights: rotation angles uniform in [0, pi), the
    readout scale 3 and bias 0, from ``init_key(seed)``."""
    nq, nl, nc = model["vqc_qubits"], model["vqc_layers"], model["n_classes"]
    k1, k2 = jax.random.split(key, 2)
    return {"theta": jax.random.uniform(k1, (nl, nq), jnp.float32, 0, np.pi),
            "phi": jax.random.uniform(k2, (nl, nq), jnp.float32, 0, np.pi),
            "w_out": jnp.full((nc,), 3.0, jnp.float32),
            "b_out": jnp.zeros((nc,), jnp.float32)}


# ---------------------------------------------------------------------------
# one satellite's local training
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("model", "steps", "batch", "lr", "prec",
                                   "keep"))
def local_train(params, x, y, key, step0, *, model, steps, batch, lr, prec,
                keep=1.0):
    """``steps`` SGD steps on batches drawn from (x, y). ``keep`` < 1
    trains on the first share of every batch only (a planted fault)."""
    model = dict(model)
    rows = max(1, int(batch * keep))

    def body(p, ke):
        k, e = ke
        idx = jax.random.randint(k, (batch,), 0, x.shape[0])[:rows]
        g = jax.grad(loss, argnums=1)(model, p, x[idx], y[idx], prec)
        eta = lr * jax.lax.rsqrt(jnp.maximum(step0 + e, 1).astype(
            jnp.float32))
        return jax.tree_util.tree_map(lambda a, b: a - eta * b, p, g), None

    keys = jax.random.split(key, steps)
    out, _ = jax.lax.scan(body, params, (keys, jnp.arange(steps)))
    return out


# ---------------------------------------------------------------------------
# the round schedule, from the constellation's access arrays
# ---------------------------------------------------------------------------

def groups_at(trace, t: int) -> dict:
    """{main: [secondaries]} at trace step t: mains see a ground station,
    a secondary joins the nearest main it has an ISL to."""
    prim = trace.sg_access[:, :, t].any(axis=1)
    pos = np.asarray(trace.sat_pos[:, t], np.float64)
    isl = trace.ss_access[:, :, t]
    out = {int(m): [] for m in np.where(prim)[0]}
    for s in np.where(~prim)[0]:
        cand = np.where(isl[s] & prim)[0]
        if len(cand):
            d = np.linalg.norm(pos[cand] - pos[s], axis=1)
            out[int(cand[int(np.argmin(d))])].append(int(s))
    return out


def round_steps(trace, plan_rounds: int) -> np.ndarray:
    stride = max(trace.n_steps // plan_rounds, 1)
    return np.minimum(np.arange(plan_rounds) * stride, trace.n_steps - 1)


def arrivals(trace, t_idx, groups, n_rounds: int, max_staleness: int):
    """{round: [(sat, main, born)]}: an update born at round b moves at the
    first trace step after t_idx[b] its (sat, main) ISL is open, and
    arrives at the first round with mains at or past that step."""
    out = {r: [] for r in range(n_rounds)}
    for b in range(n_rounds):
        t = int(t_idx[b])
        for m, secs in groups[b].items():
            for s in secs:
                hits = np.where(trace.ss_access[s, m, t + 1:])[0]
                if not len(hits):
                    continue
                k_tx = t + 1 + int(hits[0])
                later = [r for r in range(b, len(t_idx)) if t_idx[r] >= k_tx]
                if not later:
                    continue
                rd = next((r for r in range(later[0], len(t_idx))
                           if r >= n_rounds or groups[r]), None)
                if rd is None or rd - b > max_staleness or rd >= n_rounds:
                    continue
                out[rd].append((s, m, b))
    return out


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

def weighted_mean(trees: list, w, prec):
    w = jnp.asarray(np.asarray(w, np.float32) / np.sum(w))
    return jax.tree_util.tree_map(
        lambda *xs: contract("k,k...->...", w, jnp.stack(xs), prec), *trees)


def fixed_point_mean(trees: list, wq: list):
    """secagg: sum of integer-weighted 16-bit fixed-point updates, then
    dequantized; the pairwise masks cancel, so they do not appear."""
    total = None
    for tree, w in zip(trees, wq):
        q = jax.tree_util.tree_map(
            lambda x: np.clip(np.round(np.asarray(x, np.float32)
                                       * np.float32(1 << FRAC_BITS)),
                              -CLIP, CLIP).astype(np.int64) * int(w), tree)
        total = q if total is None else jax.tree_util.tree_map(
            np.add, total, q)
    scale = np.float32(1 << FRAC_BITS) * np.float32(sum(wq))
    return jax.tree_util.tree_map(
        lambda q: jnp.asarray(q.astype(np.float32) / scale), total)


# ---------------------------------------------------------------------------
# the first rounds
# ---------------------------------------------------------------------------

def run_rounds(*, model: dict, fl: dict, trace, plan_rounds: int,
               sats: list, params0, seed: int, n_rounds: int, prec,
               keep: float = 1.0, stats: dict | None = None):
    """Global params after each of the first ``n_rounds`` rounds.
    ``stats`` (if given) counts the updates merged and mains trained."""
    mode, agg = fl["mode"], fl.get("agg_security", "none")
    follows = (mode in ("sim", "async") and fl["optimizer"] == "sgd"
               and fl["lr_schedule"] == "inv_sqrt" and fl["main_trains"]
               and fl["weight_by_samples"]
               and fl.get("link_flap_rate", 0) == 0
               and fl.get("crash_rate", 0) == 0)
    if not follows:
        raise ValueError(f"the reference follows sim or async rounds with "
                         f"SGD at lr/sqrt(t), every main training, FedAvg "
                         f"by sample count and no faults; got {fl}")
    t_idx = round_steps(trace, plan_rounds)
    groups = [groups_at(trace, int(t_idx[r])) for r in range(n_rounds)]
    counts = np.array([len(d["labels"]) for d in sats], np.float64)
    wq = np.maximum(1, np.round(counts * W_MAX / counts.max())).astype(int)
    steps, batch, lr = fl["local_steps"], fl["batch_size"], fl["lr"]
    data = [(jnp.asarray(d["features"]), jnp.asarray(d["labels"]))
            for d in sats]
    train_key = jax.random.fold_in(jax.random.PRNGKey(seed), TRAIN_SALT)
    frozen = tuple(sorted(model.items()))
    stats = {} if stats is None else stats
    stats.update(merged=0, mains=0)

    def train(p, s, r):
        key = jax.random.fold_in(jax.random.fold_in(train_key, r), s)
        return local_train(p, *data[s], key, jnp.int32(r * steps),
                           model=frozen, steps=steps, batch=batch, lr=lr,
                           prec=prec, keep=keep)

    late = arrivals(trace, t_idx, groups, n_rounds,
                    fl["max_staleness"]) if mode == "async" else None
    pending: dict = {}
    born: dict = {}
    theta, out = params0, []
    for r in range(n_rounds):
        g = groups[r]
        if mode == "async":
            for m, secs in g.items():
                for s in secs:
                    born[(s, r)] = train(theta, s, r)
            for s, m, b in late[r]:
                pending.setdefault(m, []).append((s, b))
        mains, ws = [], []
        for m, secs in g.items():
            if mode == "sim":
                senders = [(s, r) for s in secs]
                ups = [train(theta, s, r) for s in secs]
            else:
                senders = sorted((s, b) for s, b in pending.get(m, [])
                                 if r - b <= fl["max_staleness"])
                pending[m] = []
                ups = [born[sb] for sb in senders]
            if not ups:
                merged = theta
            elif agg == "secagg":
                merged = fixed_point_mean(ups, [wq[s] for s, _ in senders])
            else:
                merged = weighted_mean(ups, [counts[s] for s, _ in senders],
                                       prec)
            mains.append(train(merged, m, r))
            ws.append(counts[m] + counts[secs].sum())
            stats["merged"] += len(ups)
        stats["mains"] += len(mains)
        if mains:
            theta = weighted_mean(mains, ws, prec)
        out.append(theta)
    return out


def val_loss(model: dict, params, server: dict, prec) -> float:
    v = server["val"]
    return float(loss(model, params, jnp.asarray(v["features"]),
                      jnp.asarray(v["labels"]), prec))
