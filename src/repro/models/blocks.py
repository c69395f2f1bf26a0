"""Building blocks for all assigned architectures.

Every block comes as an ``*_init`` (returning a *stacked* parameter dict with
leading layer axis L, so models can ``lax.scan`` over layers — essential to
keep HLO size O(1 layer) for the 512-device dry-run compiles) and apply
functions for the two execution modes:

  * ``*_apply``  — full-sequence training / prefill forward
  * ``*_decode`` — single-token serve step against a (possibly ring-buffer
    sliding-window) cache

Conventions:
  x          (B, S, d) activations
  positions  (B, S) or (B,) absolute int32 token positions
  window     0 = full causal attention; >0 = sliding window (ring cache)
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.nn.common import apply_rope, layer_norm, rms_norm, rope_angles, swiglu
from repro.nn.init import normal_init, ones_init, scaled_init, zeros_init

NEG_INF = -1e30


def _pre(L) -> tuple:
    """Leading stack axes: None -> (), int -> (L,), tuple -> tuple (vlm groups)."""
    if L is None:
        return ()
    if isinstance(L, (tuple, list)):
        return tuple(L)
    return (L,)


def _norm(cfg: ArchConfig, x, scale):
    if cfg.norm_type == "rmsnorm":
        return rms_norm(x, scale, cfg.norm_eps)
    if cfg.norm_type == "nonparam_ln":
        return layer_norm(x, None, None, cfg.norm_eps)
    if cfg.norm_type == "layernorm":
        s, b = (scale if isinstance(scale, tuple) else (scale, None))
        return layer_norm(x, s, b, cfg.norm_eps)
    raise ValueError(cfg.norm_type)


def norm_init(cfg: ArchConfig, L: int | None, dtype):
    """Stacked norm scale, or None for non-parametric (olmo)."""
    if cfg.norm_type == "nonparam_ln":
        return None
    shape = (cfg.d_model,) if L is None else (L, cfg.d_model)
    return jnp.ones(shape, dtype)


# =========================================================================
# Attention (self / cross, GQA, qk-norm, sliding window, ring-buffer cache)
# =========================================================================

def attn_init(key, cfg: ArchConfig, L: int | None, dtype, bias: bool = False):
    H, KV, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model
    pre = _pre(L)
    ks = jax.random.split(key, 4)
    p = {
        "wq": scaled_init(ks[0], pre + (d, H * hd), dtype),
        "wk": scaled_init(ks[1], pre + (d, KV * hd), dtype),
        "wv": scaled_init(ks[2], pre + (d, KV * hd), dtype),
        "wo": scaled_init(ks[3], pre + (H * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones(pre + (hd,), dtype)
        p["k_norm"] = jnp.ones(pre + (hd,), dtype)
    if bias:
        p["bq"] = jnp.zeros(pre + (H * hd,), dtype)
        p["bv"] = jnp.zeros(pre + (KV * hd,), dtype)
        p["bo"] = jnp.zeros(pre + (d,), dtype)
    return p


def _project_qkv(p, cfg: ArchConfig, xq, xkv, cos_q=None, sin_q=None,
                 cos_k=None, sin_k=None):
    """Project q from xq and k,v from xkv; apply qk-norm and rope."""
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    Bq, Sq, _ = xq.shape
    Bk, Sk, _ = xkv.shape
    q = jnp.einsum("bsd,dh->bsh", xq, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    k = jnp.einsum("bsd,dh->bsh", xkv, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", xkv, p["wv"])
    if "bv" in p:
        v = v + p["bv"]
    q = q.reshape(Bq, Sq, H, hd)
    k = k.reshape(Bk, Sk, KV, hd)
    v = v.reshape(Bk, Sk, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cos_q is not None:
        q = apply_rope(q, cos_q, sin_q)
    if cos_k is not None:
        k = apply_rope(k, cos_k, sin_k)
    return q, k, v


def _repeat_kv(k, n_heads):
    """(B, S, KV, hd) -> (B, S, H, hd) by broadcasting each kv head."""
    B, S, KV, hd = k.shape
    rep = n_heads // KV
    if rep == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, rep, hd))
    return k.reshape(B, S, KV * rep, hd)


def _sdpa_core(q, k, v, mask):
    """Softmax attention pre-projection: q (B,Sq,H,hd), k/v (B,Sk,H,hd),
    mask (B,1,Sq,Sk) -> (B,Sq,H,hd). Softmax statistics in fp32; the
    quadratic score/prob tensors stay in the activation dtype (fp32 copies
    of them are what blows the training footprint at long S)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.asarray(NEG_INF, scores.dtype))
    m = jax.lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True,
                            dtype=jnp.float32), 1e-30)
    probs = p / l.astype(p.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _sdpa(q, k, v, mask, out_proj, bo=None):
    o = _sdpa_core(q, k, v, mask)
    o = o.reshape(o.shape[0], o.shape[1], -1)
    y = jnp.einsum("bsh,hd->bsd", o, out_proj)
    if bo is not None:
        y = y + bo
    return y


# sequences longer than this compute attention in query chunks: exact
# softmax per chunk, O(S·chunk) memory instead of O(S²) — what makes the
# 32k prefill shapes fit 16 GiB/chip (the Pallas swa kernel is the TPU-hot
# version; this is the XLA-lowerable equivalent used by the dry-run).
QCHUNK_THRESHOLD = 8192
QCHUNK = 512


def _attn_qchunked(q, k, v, positions, causal, window, chunk=QCHUNK):
    B, S, H, hd = q.shape
    c = min(chunk, S)
    while S % c != 0:
        c -= 1
    nc = S // c
    qs = jnp.moveaxis(q.reshape(B, nc, c, H, hd), 1, 0)      # (nc,B,c,H,hd)
    pos_q = jnp.moveaxis(positions.reshape(B, nc, c), 1, 0)  # (nc,B,c)
    pk = positions[:, None, :]                               # (B,1,S)

    def body(_, inp):
        qc, pq = inp
        mask = jnp.ones((B, c, S), bool)
        if causal:
            mask &= pk <= pq[:, :, None]
        if window > 0:
            mask &= pk > pq[:, :, None] - window
        return (), _sdpa_core(qc, k, v, mask[:, None])

    _, outs = jax.lax.scan(body, (), (qs, pos_q))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)


def attn_apply(p, cfg: ArchConfig, x, positions, *, window: int = 0,
               causal: bool = True, use_rope: bool = True, ctx=None):
    """Full-sequence self-attention (training / prefill).

    §Perf A5 (ctx.seq_attn): with a seq-sharded residual, queries KEEP the
    sequence sharding through the whole attention (scores/softmax/mix are
    local in the query dim); only K/V — a kv_heads/heads fraction of the
    bytes under GQA/MQA — are gathered. Replaces the 2 full-activation
    gathers per layer with 2 small K/V gathers.
    """
    B, S, _ = x.shape
    cos = sin = None
    if use_rope:
        cos, sin = rope_angles(positions, cfg.hd, cfg.rope_theta)
    q, k, v = _project_qkv(p, cfg, x, x, cos, sin, cos, sin)
    if (ctx is not None and ctx.mesh is not None and ctx.seq_shard
            and getattr(ctx, "seq_attn", False) and S > 1
            and S % ctx.model_size == 0
            and 2 * cfg.n_kv_heads <= cfg.n_heads):
        # GQA/MQA only: under MHA the K/V gather is full-size and the
        # forced layout hurts (§Perf B1, refuted for kv == H)
        from jax.sharding import PartitionSpec as P
        dp = ctx.data_spec_axes
        q = jax.lax.with_sharding_constraint(
            q, P(dp, ctx.model_axis, None, None))
        k = jax.lax.with_sharding_constraint(k, P(dp, None, None, None))
        v = jax.lax.with_sharding_constraint(v, P(dp, None, None, None))
    k = _repeat_kv(k, cfg.n_heads)
    v = _repeat_kv(v, cfg.n_heads)
    if S > QCHUNK_THRESHOLD:
        o = _attn_qchunked(q, k, v, positions, causal, window)
        o = o.reshape(B, S, -1)
        y = jnp.einsum("bsh,hd->bsd", o, p["wo"])
        if "bo" in p:
            y = y + p["bo"]
        return y
    pq = positions[:, :, None]          # (B, S, 1)
    pk = positions[:, None, :]          # (B, 1, S)
    mask = jnp.ones((B, S, S), bool)
    if causal:
        mask &= pk <= pq
    if window > 0:
        mask &= pk > pq - window
    return _sdpa(q, k, v, mask[:, None], p["wo"], p.get("bo"))


def cross_attn_apply(p, cfg: ArchConfig, x, kv_states):
    """Cross-attention: q from text x, k/v from encoder/vision states."""
    q, k, v = _project_qkv(p, cfg, x, kv_states)
    k = _repeat_kv(k, cfg.n_heads)
    v = _repeat_kv(v, cfg.n_heads)
    return _sdpa(q, k, v, None, p["wo"], p.get("bo"))


def attn_cache_init(cfg: ArchConfig, L: int | None, batch: int, cache_len: int,
                    dtype) -> dict:
    """Ring-buffer KV cache. ``pos`` holds the absolute position stored in
    each slot (-1 = empty); one pos table per segment (shared across its
    layers, which write identical slots).

    kv_cache_dtype == "int8": K/V stored quantized with per-(slot, head)
    fp16 scales — cache HBM halves, which is the decode roofline's dominant
    term at 32k+ context (§Perf serving lever)."""
    KV, hd = cfg.n_kv_heads, cfg.hd
    pre = _pre(L)
    cache = {
        "pos": jnp.full(pre + (batch, cache_len), -1, jnp.int32),
    }
    if cfg.kv_cache_dtype == "int8":
        cache["k"] = jnp.zeros(pre + (batch, cache_len, KV, hd), jnp.int8)
        cache["v"] = jnp.zeros(pre + (batch, cache_len, KV, hd), jnp.int8)
        cache["k_scale"] = jnp.zeros(pre + (batch, cache_len, KV),
                                     jnp.float16)
        cache["v_scale"] = jnp.zeros(pre + (batch, cache_len, KV),
                                     jnp.float16)
    else:
        cache["k"] = jnp.zeros(pre + (batch, cache_len, KV, hd), dtype)
        cache["v"] = jnp.zeros(pre + (batch, cache_len, KV, hd), dtype)
    return cache


def _quant_kv(x):
    """(B, KV, hd) -> int8 values + per-head fp16 scale."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-9
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _dequant_kv(q, scale, dtype):
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)


def attn_decode(p, cfg: ArchConfig, x, pos, cache, *, window: int = 0,
                use_rope: bool = True):
    """Single-token decode. x (B,1,d); pos (B,) absolute position.

    Returns (y (B,1,d), new_cache). The cache slot is pos % cache_len — a
    ring buffer, which is exactly the sliding-window semantics when
    cache_len == window, and a plain append when cache_len >= max_len.
    """
    B = x.shape[0]
    cos = sin = None
    if use_rope:
        cos, sin = rope_angles(pos[:, None], cfg.hd, cfg.rope_theta)
    q, k, v = _project_qkv(p, cfg, x, x, cos, sin, cos, sin)
    ck, cv, cpos = cache["k"], cache["v"], cache["pos"]
    Sc = ck.shape[1]
    slot = (pos % Sc).astype(jnp.int32)
    bidx = jnp.arange(B)
    new_cache = {}
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quant_kv(k[:, 0])
        vq, vs = _quant_kv(v[:, 0])
        ck = ck.at[bidx, slot].set(kq)
        cv = cv.at[bidx, slot].set(vq)
        ksc = cache["k_scale"].at[bidx, slot].set(ks)
        vsc = cache["v_scale"].at[bidx, slot].set(vs)
        k_use = _dequant_kv(ck, ksc, x.dtype)
        v_use = _dequant_kv(cv, vsc, x.dtype)
        new_cache.update(k_scale=ksc, v_scale=vsc)
    else:
        ck = ck.at[bidx, slot].set(k[:, 0])
        cv = cv.at[bidx, slot].set(v[:, 0])
        k_use, v_use = ck, cv
    cpos = cpos.at[bidx, slot].set(pos)
    kk = _repeat_kv(k_use, cfg.n_heads)
    vv = _repeat_kv(v_use, cfg.n_heads)
    valid = (cpos >= 0) & (cpos <= pos[:, None])
    if window > 0:
        valid &= cpos > (pos[:, None] - window)
    mask = valid[:, None, None, :]      # (B,1,1,Sc)
    y = _sdpa(q, kk, vv, mask, p["wo"], p.get("bo"))
    new_cache.update(k=ck, v=cv, pos=cpos)
    return y, new_cache


def cross_attn_cache_init(cfg: ArchConfig, L: int | None, batch: int,
                          n_kv: int, dtype) -> dict:
    KV, hd = cfg.n_kv_heads, cfg.hd
    pre = _pre(L)
    return {
        "k": jnp.zeros(pre + (batch, n_kv, KV, hd), dtype),
        "v": jnp.zeros(pre + (batch, n_kv, KV, hd), dtype),
    }


def cross_attn_prefill_cache(p, cfg: ArchConfig, kv_states):
    """Precompute cross-attention K/V from encoder states (done once)."""
    B, Sk, _ = kv_states.shape
    KV, hd = cfg.n_kv_heads, cfg.hd
    k = jnp.einsum("bsd,dh->bsh", kv_states, p["wk"]).reshape(B, Sk, KV, hd)
    v = jnp.einsum("bsd,dh->bsh", kv_states, p["wv"])
    if "bv" in p:
        v = v + p["bv"]
    v = v.reshape(B, Sk, KV, hd)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return {"k": k, "v": v}


def cross_attn_decode(p, cfg: ArchConfig, x, cache):
    """Decode-time cross-attention against precomputed K/V."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, 1, cfg.n_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    kk = _repeat_kv(cache["k"], cfg.n_heads)
    vv = _repeat_kv(cache["v"], cfg.n_heads)
    return _sdpa(q, kk, vv, None, p["wo"], p.get("bo"))


# =========================================================================
# Dense FFN
# =========================================================================

def ffn_init(key, cfg: ArchConfig, L: int | None, dtype, d_ff: int = 0):
    d, f = cfg.d_model, (d_ff or cfg.d_ff)
    pre = _pre(L)
    ks = jax.random.split(key, 3)
    return {
        "wg": scaled_init(ks[0], pre + (d, f), dtype),
        "wu": scaled_init(ks[1], pre + (d, f), dtype),
        "wd": scaled_init(ks[2], pre + (f, d), dtype),
    }


def ffn_apply(p, x):
    return swiglu(x, p["wg"], p["wu"], p["wd"])


# =========================================================================
# Mixture of Experts
# =========================================================================

def moe_init(key, cfg: ArchConfig, L: int | None, dtype):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    pre = _pre(L)
    ks = jax.random.split(key, 5)
    p = {
        "router": scaled_init(ks[0], pre + (d, E), jnp.float32),
        "we_g": scaled_init(ks[1], pre + (E, d, f), dtype),
        "we_u": scaled_init(ks[2], pre + (E, d, f), dtype),
        "we_d": scaled_init(ks[3], pre + (E, f, d), dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = ffn_init(ks[4], cfg, L, dtype,
                               d_ff=cfg.n_shared_experts * f)
    return p


def _moe_local(xt, p, cfg: ArchConfig, e_off: int, e_num: int, t_scale: int):
    """Token-choice top-k MoE over the local expert slice [e_off, e_off+e_num).

    xt: (T, d) local tokens. Routing is computed over ALL experts (router is
    replicated) so gates are globally correct; only tokens assigned to local
    experts are dispatched here. Capacity-based dispatch via scatter/gather
    (never materializes a (T, E, C) one-hot).

    t_scale: number of times tokens are replicated across the expert axis
    (== model-axis size under expert parallelism) — only used for capacity
    normalization, which depends on global token count per expert.
    """
    T, d = xt.shape
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    probs = jax.nn.softmax(
        jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"]), axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                     # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)      # renormalize top-k

    # aux load-balance loss (switch-style), from global routing stats
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = E * jnp.sum(me * ce)

    # ---- dispatch to local experts ------------------------------------
    cap = max(int(cfg.capacity_factor * T * k / E), 4)
    a = idx.reshape(T * k)                                   # expert of each slot
    g = gate.reshape(T * k).astype(xt.dtype)
    local = (a >= e_off) & (a < e_off + e_num)
    e_loc = jnp.where(local, a - e_off, e_num)               # e_num = drop bucket
    # position of each slot within its expert (order: token-major)
    onehot_pos = jax.nn.one_hot(e_loc, e_num + 1, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot_pos, axis=0) * onehot_pos
    slot = jnp.sum(pos_in_e, axis=1) - 1                     # (T*k,), -1 if none
    keep = local & (slot < cap) & (slot >= 0)
    target = jnp.where(keep, e_loc * cap + slot, e_num * cap)  # overflow row

    tok_of_slot = jnp.repeat(jnp.arange(T), k)
    xin = jnp.zeros((e_num * cap + 1, d), xt.dtype)
    xin = xin.at[target].add(xt[tok_of_slot] * keep[:, None].astype(xt.dtype))
    xin = xin[:-1].reshape(e_num, cap, d)

    # ---- expert computation (grouped matmuls -> MXU) -------------------
    h_g = jnp.einsum("ecd,edf->ecf", xin, p["we_g"])
    h_u = jnp.einsum("ecd,edf->ecf", xin, p["we_u"])
    h = jax.nn.silu(h_g) * h_u                   # native dtype: keeps the
    y_e = jnp.einsum("ecf,efd->ecd", h, p["we_d"])  # stacked grads bf16

    # ---- combine back ---------------------------------------------------
    y_flat = jnp.concatenate(
        [y_e.reshape(e_num * cap, d), jnp.zeros((1, d), xt.dtype)], axis=0)
    y_slots = y_flat[target] * (g * keep.astype(g.dtype))[:, None]
    out = jnp.zeros((T, d), xt.dtype).at[tok_of_slot].add(y_slots)
    return out, aux


def moe_apply(p, cfg: ArchConfig, x, ctx=None):
    """MoE FFN. Returns (y, aux_loss).

    Distribution strategies (DESIGN.md §Arch-applicability):
      * mesh ctx + enough tokens -> **all-to-all expert parallelism**
        (shard_map over the full mesh): tokens stay sharded over every mesh
        axis, each rank routes its local slice, dispatches token buffers to
        the experts' owner ranks with one all_to_all, computes its expert
        slice, and an inverse all_to_all brings results home. Per-layer
        comm = 2 a2a of (ranks, C_send, d) ≈ k/ranks of the token bytes —
        ~4x less than the psum-replicated scheme, and no token replication
        in memory.
      * mesh ctx but few tokens (decode steps) -> replicated-token EP:
        routing computed on every model rank, each computes its expert
        slice, psum combines.
      * ctx None (CPU smoke / vmapped FL) -> single-device capacity MoE.
    """
    B, S, d = x.shape
    T = B * S

    if ctx is not None and ctx.mesh is not None and ctx.model_size > 1:
        if (B % ctx.data_size == 0 and S % ctx.model_size == 0
                and (T // (ctx.data_size * ctx.model_size))
                >= ctx.model_size):
            # pass (B,S,d) straight through — flattening happens on LOCAL
            # shards inside the shard_map, so no global merged-dim reshard
            # (the multi-pod (B·S) reshape caused involuntary full
            # rematerialization in GSPMD — §Perf B2)
            y, aux = _moe_a2a(x, p, cfg, ctx)
            if "shared" in p:
                y = y + ffn_apply(p["shared"], x)
            return y, aux
        if T % ctx.data_size == 0:
            out, aux = _moe_replicated_ep(x.reshape(T, d), p, cfg, ctx)
        else:
            # tiny token counts (B=1 long-context decode): plain local MoE;
            # GSPMD partitions the expert einsums over the sharded E axis
            out, aux = _moe_local(x.reshape(T, d), p, cfg, 0,
                                  cfg.n_experts, 1)
    else:
        out, aux = _moe_local(x.reshape(T, d), p, cfg, 0, cfg.n_experts, 1)

    y = out.reshape(B, S, d)
    if "shared" in p:
        y = y + ffn_apply(p["shared"], x)
    return y, aux


def _moe_replicated_ep(xt, p, cfg: ArchConfig, ctx):
    """Tokens replicated across the model axis; each rank computes its
    expert slice; psum combines. Used for small token counts (decode)."""
    from jax.sharding import PartitionSpec as P

    e_num = cfg.n_experts // ctx.model_size
    dp = ctx.data_spec_axes

    def local_fn(xt_l, router, we_g, we_u, we_d):
        rank = jax.lax.axis_index(ctx.model_axis)
        p_l = {"router": router, "we_g": we_g, "we_u": we_u, "we_d": we_d}
        out, aux = _moe_local_dynamic(xt_l, p_l, cfg, rank * e_num, e_num)
        return jax.lax.psum(out, ctx.model_axis), \
            jax.lax.pmean(aux, ctx.model_axis)

    specs_in = (P(dp, None), P(None, None),
                P(ctx.model_axis, None, None),
                P(ctx.model_axis, None, None),
                P(ctx.model_axis, None, None))
    return jax.shard_map(local_fn, mesh=ctx.mesh, in_specs=specs_in,
                         out_specs=(P(dp, None), P()), check_vma=False)(
        xt, p["router"], p["we_g"], p["we_u"], p["we_d"])


def _moe_a2a(x3, p, cfg: ArchConfig, ctx):
    """All-to-all expert parallelism (see moe_apply docstring).

    Takes x (B, S, d) with B sharded over the data axes and S over
    "model" (the seq-sharded residual layout) — shards flatten locally.
    """
    from jax.sharding import PartitionSpec as P

    ms = ctx.model_size
    e_num = cfg.n_experts // ms
    dp = ctx.data_spec_axes
    full_spec = (dp if isinstance(dp, tuple) else (dp,)) + (ctx.model_axis,)
    k = cfg.n_experts_per_tok

    def local_fn(x_l, router, we_g, we_u, we_d):
        B_l, S_l, d = x_l.shape
        xt_l = x_l.reshape(B_l * S_l, d)
        T_l = B_l * S_l
        E = cfg.n_experts
        probs = jax.nn.softmax(
            jnp.einsum("td,de->te", xt_l.astype(jnp.float32), router), -1)
        gate, idx = jax.lax.top_k(probs, k)
        gate = gate / jnp.sum(gate, -1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), 1), 0)
        all_axes = full_spec  # tokens shard over every mesh axis
        aux = jax.lax.pmean(E * jnp.sum(me * ce), all_axes)

        # ---- send-side dispatch: group (token, k) slots by owner rank ----
        c_send = max(int(cfg.capacity_factor * T_l * k / ms), 4)
        a = idx.reshape(T_l * k)
        g = gate.reshape(T_l * k).astype(xt_l.dtype)
        dst = a // e_num                                  # owner rank
        eid = a - dst * e_num                             # local expert there
        oh = jax.nn.one_hot(dst, ms, dtype=jnp.int32)
        slot = jnp.sum(jnp.cumsum(oh, 0) * oh, 1) - 1
        keep = slot < c_send
        tgt = jnp.where(keep, dst * c_send + slot, ms * c_send)
        tok = jnp.repeat(jnp.arange(T_l), k)

        buf_x = jnp.zeros((ms * c_send + 1, d), xt_l.dtype
                          ).at[tgt].add(xt_l[tok] * keep[:, None])
        buf_e = jnp.full((ms * c_send + 1,), -1, jnp.int32
                         ).at[tgt].set(jnp.where(keep, eid, -1))
        send_x = buf_x[:-1].reshape(ms, c_send, d)
        send_e = buf_e[:-1].reshape(ms, c_send)

        # ---- exchange: row r goes to rank r --------------------------------
        recv_x = jax.lax.all_to_all(send_x, ctx.model_axis, 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, ctx.model_axis, 0, 0, tiled=False)
        R = ms * c_send
        rx = recv_x.reshape(R, d)
        re_ = recv_e.reshape(R)

        # ---- receive-side dispatch to local experts ------------------------
        c_exp = max(int(cfg.capacity_factor * T_l * k / e_num), 4)
        valid = re_ >= 0
        e_loc = jnp.where(valid, re_, e_num)
        oh2 = jax.nn.one_hot(e_loc, e_num + 1, dtype=jnp.int32)
        slot2 = jnp.sum(jnp.cumsum(oh2, 0) * oh2, 1) - 1
        keep2 = valid & (slot2 < c_exp)
        tgt2 = jnp.where(keep2, e_loc * c_exp + slot2, e_num * c_exp)
        xin = jnp.zeros((e_num * c_exp + 1, d), xt_l.dtype
                        ).at[tgt2].add(rx * keep2[:, None])
        xin = xin[:-1].reshape(e_num, c_exp, d)

        h_g = jnp.einsum("ecd,edf->ecf", xin, we_g)
        h_u = jnp.einsum("ecd,edf->ecf", xin, we_u)
        h = jax.nn.silu(h_g) * h_u
        y_e = jnp.einsum("ecf,efd->ecd", h, we_d)

        # ---- inverse path ----------------------------------------------------
        y_flat = jnp.concatenate(
            [y_e.reshape(e_num * c_exp, d), jnp.zeros((1, d), xt_l.dtype)], 0)
        y_recv = y_flat[tgt2] * keep2[:, None]            # received order
        y_send = jax.lax.all_to_all(
            y_recv.reshape(ms, c_send, d), ctx.model_axis, 0, 0, tiled=False)
        y_rows = y_send.reshape(R, d)
        safe = jnp.where(keep, tgt, 0)
        y_slots = y_rows[safe] * (g * keep)[:, None]
        out = jnp.zeros((T_l, d), xt_l.dtype).at[tok].add(y_slots)
        return out.reshape(B_l, S_l, d), aux

    specs_in = (P(dp, ctx.model_axis, None), P(None, None),
                P(ctx.model_axis, None, None),
                P(ctx.model_axis, None, None),
                P(ctx.model_axis, None, None))
    return jax.shard_map(local_fn, mesh=ctx.mesh, in_specs=specs_in,
                         out_specs=(P(dp, ctx.model_axis, None), P()),
                         check_vma=False)(
        x3, p["router"], p["we_g"], p["we_u"], p["we_d"])


def _moe_local_dynamic(xt, p, cfg: ArchConfig, e_off, e_num: int):
    """Same as _moe_local but with a traced (rank-dependent) expert offset."""
    T, d = xt.shape
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    probs = jax.nn.softmax(
        jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"]), axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = E * jnp.sum(me * ce)

    cap = max(int(cfg.capacity_factor * T * k / E), 4)
    a = idx.reshape(T * k)
    g = gate.reshape(T * k).astype(xt.dtype)
    local = (a >= e_off) & (a < e_off + e_num)
    e_loc = jnp.where(local, a - e_off, e_num)
    onehot_pos = jax.nn.one_hot(e_loc, e_num + 1, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot_pos, axis=0) * onehot_pos
    slot = jnp.sum(pos_in_e, axis=1) - 1
    keep = local & (slot < cap) & (slot >= 0)
    target = jnp.where(keep, e_loc * cap + slot, e_num * cap)

    tok_of_slot = jnp.repeat(jnp.arange(T), k)
    xin = jnp.zeros((e_num * cap + 1, d), xt.dtype)
    xin = xin.at[target].add(xt[tok_of_slot] * keep[:, None].astype(xt.dtype))
    xin = xin[:-1].reshape(e_num, cap, d)

    h_g = jnp.einsum("ecd,edf->ecf", xin, p["we_g"])
    h_u = jnp.einsum("ecd,edf->ecf", xin, p["we_u"])
    h = jax.nn.silu(h_g) * h_u                   # native dtype: keeps the
    y_e = jnp.einsum("ecf,efd->ecd", h, p["we_d"])  # stacked grads bf16

    y_flat = jnp.concatenate(
        [y_e.reshape(e_num * cap, d), jnp.zeros((1, d), xt.dtype)], axis=0)
    y_slots = y_flat[target] * (g * keep.astype(g.dtype))[:, None]
    out = jnp.zeros((T, d), xt.dtype).at[tok_of_slot].add(y_slots)
    return out, aux


# =========================================================================
# Mamba-2 (SSD) block
# =========================================================================

def ssm_init(key, cfg: ArchConfig, L: int | None, dtype):
    d, di, nh = cfg.d_model, cfg.d_inner, cfg.ssm_nheads
    G, N, dc = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_dconv
    pre = _pre(L)
    ks = jax.random.split(key, 8)
    # A init in [1, 16) as in mamba2; dt_bias from inv-softplus of U(1e-3, 0.1)
    a0 = jax.random.uniform(ks[5], pre + (nh,), jnp.float32, 1.0, 16.0)
    dt0 = jax.random.uniform(ks[6], pre + (nh,), jnp.float32, 1e-3, 0.1)
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    return {
        "wx": scaled_init(ks[0], pre + (d, di), dtype),
        "wz": scaled_init(ks[1], pre + (d, di), dtype),
        "wB": scaled_init(ks[2], pre + (d, G * N), dtype),
        "wC": scaled_init(ks[3], pre + (d, G * N), dtype),
        "wdt": scaled_init(ks[4], pre + (d, nh), dtype),
        "dt_bias": dt_bias.astype(jnp.float32),
        "A_log": jnp.log(a0).astype(jnp.float32),
        "D": jnp.ones(pre + (nh,), jnp.float32),
        "gnorm": jnp.ones(pre + (di,), dtype),
        "wo": scaled_init(ks[7], pre + (di, d), dtype),
        "conv_w": (jax.random.normal(ks[7], pre + (dc, cfg.conv_dim), jnp.float32)
                   * (1.0 / math.sqrt(dc))).astype(dtype),
        "conv_b": jnp.zeros(pre + (cfg.conv_dim,), dtype),
    }


def _segsum(x):
    """(..., l) -> (..., l, l) cumulative segment sums, lower triangular."""
    l = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_ref(xh, dt, A, Bv, Cv, chunk: int = 128, init_state=None):
    """Chunked SSD (state-space duality) forward — pure-jnp oracle.

    xh (b,s,h,p); dt (b,s,h) (post-softplus); A (h,) negative; Bv/Cv
    (b,s,g,n). Returns (y (b,s,h,p), final_state (b,h,p,n)). All math fp32.
    """
    b, s, h, pdim = xh.shape
    g, n = Bv.shape[2], Bv.shape[3]
    rep = h // g
    c = min(chunk, s)
    while s % c != 0:           # fall back to a divisor for tiny smoke seqs
        c -= 1
    nc = s // c

    xf = xh.astype(jnp.float32).reshape(b, nc, c, h, pdim)
    dtf = dt.astype(jnp.float32).reshape(b, nc, c, h)
    Bf = Bv.astype(jnp.float32).reshape(b, nc, c, g, n)
    Cf = Cv.astype(jnp.float32).reshape(b, nc, c, g, n)
    # broadcast groups to heads
    Bh = jnp.repeat(Bf, rep, axis=3)     # (b,nc,c,h,n)
    Ch = jnp.repeat(Cf, rep, axis=3)

    dA = dtf * A[None, None, None, :]     # (b,nc,c,h) negative
    dA_cs = jnp.cumsum(dA, axis=2)

    # intra-chunk (diagonal blocks)
    Lmat = jnp.exp(_segsum(jnp.moveaxis(dA, 3, 2)))          # (b,nc,h,c,c)
    scores = jnp.einsum("bzlhn,bzshn->bzhls", Ch, Bh)        # (b,nc,h,c,c)
    y_diag = jnp.einsum("bzhls,bzhls,bzsh,bzshp->bzlhp",
                        scores, Lmat, dtf, xf)

    # chunk states
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)       # (b,nc,c,h)
    states = jnp.einsum("bzlhn,bzlh,bzlh,bzlhp->bzhpn",
                        Bh, decay_states, dtf, xf)            # (b,nc,h,p,n)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))                # (b,nc,h)

    def scan_fn(carry, inp):
        st, dec = inp                                         # (b,h,p,n), (b,h)
        new = carry * dec[:, :, None, None] + st
        return new, carry                                     # emit state BEFORE chunk

    init = (jnp.zeros((b, h, pdim, n), jnp.float32)
            if init_state is None else init_state.astype(jnp.float32))
    final, prev_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)             # (b,nc,h,p,n)

    # off-diagonal contribution
    state_decay = jnp.exp(dA_cs)                              # (b,nc,c,h)
    y_off = jnp.einsum("bzlhn,bzlh,bzhpn->bzlhp",
                       Ch, state_decay, prev_states)

    y = (y_diag + y_off).reshape(b, s, h, pdim)
    return y.astype(xh.dtype), final


def _causal_conv(u, w, b):
    """Depthwise causal conv. u (B,S,C); w (K,C); b (C,)."""
    K = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    # sum over taps: y[t] = sum_k w[k] * u[t - (K-1) + k]
    S = u.shape[1]
    y = jnp.zeros_like(u, dtype=jnp.float32)
    for k in range(K):
        y = y + pad[:, k:k + S, :].astype(jnp.float32) * w[k].astype(jnp.float32)
    return (y + b.astype(jnp.float32)).astype(u.dtype)


def ssm_apply(p, cfg: ArchConfig, x, *, chunk: int = 128, ssd_fn=None):
    """Full-sequence Mamba-2 mixer (training / prefill)."""
    B, S, d = x.shape
    nh, hd = cfg.ssm_nheads, cfg.ssm_headdim
    G, N = cfg.ssm_ngroups, cfg.ssm_state
    z = jnp.einsum("bsd,de->bse", x, p["wz"])
    xin = jnp.einsum("bsd,de->bse", x, p["wx"])
    Bv = jnp.einsum("bsd,de->bse", x, p["wB"])
    Cv = jnp.einsum("bsd,de->bse", x, p["wC"])
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x, p["wdt"]).astype(jnp.float32)
        + p["dt_bias"])

    u = jnp.concatenate([xin, Bv, Cv], axis=-1)
    u = _causal_conv(u, p["conv_w"], p["conv_b"])
    u = jax.nn.silu(u.astype(jnp.float32)).astype(x.dtype)
    xin = u[..., :cfg.d_inner]
    Bv = u[..., cfg.d_inner:cfg.d_inner + G * N].reshape(B, S, G, N)
    Cv = u[..., cfg.d_inner + G * N:].reshape(B, S, G, N)

    A = -jnp.exp(p["A_log"])
    xh = xin.reshape(B, S, nh, hd)
    fn = ssd_fn or ssd_ref
    y, _ = fn(xh, dt, A, Bv, Cv, chunk=chunk)
    y = y + xh * jnp.broadcast_to(
        p["D"][None, None, :, None].astype(y.dtype), xh.shape)
    y = y.reshape(B, S, cfg.d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 p["gnorm"], cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, p["wo"])


def ssm_cache_init(cfg: ArchConfig, L: int | None, batch: int, dtype) -> dict:
    nh, hd, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    pre = _pre(L)
    return {
        "state": jnp.zeros(pre + (batch, nh, hd, N), jnp.float32),
        "conv": jnp.zeros(pre + (batch, cfg.ssm_dconv - 1, cfg.conv_dim), dtype),
    }


def ssm_decode(p, cfg: ArchConfig, x, cache):
    """Single-token recurrent SSD step. x (B,1,d)."""
    B = x.shape[0]
    nh, hd = cfg.ssm_nheads, cfg.ssm_headdim
    G, N = cfg.ssm_ngroups, cfg.ssm_state
    xt = x[:, 0]
    z = xt @ p["wz"]
    xin = xt @ p["wx"]
    Bv = xt @ p["wB"]
    Cv = xt @ p["wC"]
    dt = jax.nn.softplus(
        (xt @ p["wdt"]).astype(jnp.float32) + p["dt_bias"])    # (B, nh)

    u = jnp.concatenate([xin, Bv, Cv], axis=-1)                # (B, conv_dim)
    conv = cache["conv"]                                       # (B, K-1, C)
    hist = jnp.concatenate([conv, u[:, None]], axis=1)         # (B, K, C)
    w = p["conv_w"]
    uc = jnp.einsum("bkc,kc->bc", hist.astype(jnp.float32),
                    w.astype(jnp.float32)) + p["conv_b"].astype(jnp.float32)
    uc = jax.nn.silu(uc).astype(x.dtype)
    new_conv = hist[:, 1:]

    xin = uc[:, :cfg.d_inner]
    Bv = uc[:, cfg.d_inner:cfg.d_inner + G * N].reshape(B, G, N)
    Cv = uc[:, cfg.d_inner + G * N:].reshape(B, G, N)
    rep = nh // G
    Bh = jnp.repeat(Bv, rep, axis=1)                           # (B, nh, N)
    Ch = jnp.repeat(Cv, rep, axis=1)

    A = -jnp.exp(p["A_log"])                                   # (nh,)
    xh = xin.reshape(B, nh, hd).astype(jnp.float32)
    dA = jnp.exp(dt * A[None, :])                              # (B, nh)
    state = cache["state"]                                     # (B,nh,hd,N) f32
    state = (state * dA[:, :, None, None]
             + (dt[:, :, None] * xh)[..., None] * Bh[:, :, None, :].astype(jnp.float32))
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch.astype(jnp.float32))
    y = y + xh * p["D"][None, :, None]
    y = y.reshape(B, cfg.d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 p["gnorm"], cfg.norm_eps)
    out = (y @ p["wo"])[:, None, :]
    return out, {"state": state, "conv": new_conv}
