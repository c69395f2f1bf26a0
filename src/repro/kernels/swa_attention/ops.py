"""Public wrapper: (B, S, H, hd) layout, GQA repeat, custom VJP.

Forward = Pallas kernel; backward = recompute through the jnp oracle
(rematerialized flash backward — O(S·W) memory like the forward since the
oracle band-masks; a fused backward kernel is a known further step and is
listed in EXPERIMENTS §Perf as future work for the training path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.swa_attention.kernel import swa_attention_bhsd
from repro.kernels.swa_attention.ref import swa_attention_ref


def _fold(q):
    B, S, H, hd = q.shape
    return q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)


def _unfold(o, B, H):
    BH, S, hd = o.shape
    return o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def _repeat_kv(k, n_heads):
    B, S, KV, hd = k.shape
    rep = n_heads // KV
    if rep == 1:
        return k
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (B, S, KV, rep, hd)).reshape(B, S, KV * rep, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _swa(qf, kf, vf, window, interpret, use_kernel):
    if use_kernel:
        return swa_attention_bhsd(qf, kf, vf, window=window,
                                  interpret=interpret)
    return swa_attention_ref(qf, kf, vf, window=window)


def _swa_fwd(qf, kf, vf, window, interpret, use_kernel):
    return _swa(qf, kf, vf, window, interpret, use_kernel), (qf, kf, vf)


def _swa_bwd(window, interpret, use_kernel, res, cot):
    qf, kf, vf = res
    _, vjp = jax.vjp(lambda a, b, c: swa_attention_ref(a, b, c, window),
                     qf, kf, vf)
    return vjp(cot)


_swa.defvjp(_swa_fwd, _swa_bwd)


def swa_attention(q: jax.Array, k: jax.Array, v: jax.Array, window: int = 0,
                  interpret: bool | None = None, use_kernel: bool = True):
    """q (B, S, H, hd); k/v (B, S, KV, hd) GQA -> o (B, S, H, hd)."""
    B, S, H, hd = q.shape
    k = _repeat_kv(k, H)
    v = _repeat_kv(v, H)
    o = _swa(_fold(q), _fold(k), _fold(v), window, interpret, use_kernel)
    return _unfold(o, B, H)
