"""Fused OTP-XOR + polynomial-MAC-partial Pallas kernel.

One streaming pass over the parameter ciphertext: each grid step loads a
(128, 128)-aligned uint32 tile of message and pad into VMEM (the default —
the block size is part of the wire format, see ops.py), XORs them (the
OTP), splits the ciphertext words into 16-bit MAC symbols, multiplies by
the per-position key powers (precomputed once per block offset — identical
for every block), and reduces a per-block partial tag in GF(2^31 − 1).

This is exactly the memory-bound fusion the roofline wants: 2 loads + 1
store per word, MAC arithmetic rides along at ~12 int ops/word — far under
the ALU:HBM ratio, so the fused kernel stays bandwidth-bound and the MAC is
"free" relative to a separate pass (2x HBM traffic saved vs XOR-then-MAC).

Layout: msg/pad (n_blocks, R, C) uint32 with (R, C) = (block_rows, 128);
powers (2, R, C): powers[0] for the lo-16 symbol of each word, powers[1]
for the hi-16 symbol (global symbol order lo, hi, lo, hi, ...). Out: ct
same shape; per-lane partial tags (n_blocks, 1, 128) — the block's rows
are reduced in the kernel, its 128 lanes by ``lane_tags`` after it, so
every output block stays lane-dense.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

P31 = 0x7FFFFFFF        # python ints: pallas kernels cannot
MASK16 = 0xFFFF         # capture traced scalar constants


def _mod31(x):
    y = (x >> 31) + (x & P31)
    return jnp.where(y >= P31, y - P31, y)


def _addmod(a, b):
    return _mod31(a + b)


def _mulmod(a, b):
    a1, a0 = a >> 16, a & MASK16
    b1, b0 = b >> 16, b & MASK16
    t11 = a1 * b1
    t10 = a1 * b0 + a0 * b1
    t00 = a0 * b0
    t10h, t10l = t10 >> 15, t10 & 0x7FFF
    acc = _mod31(t11 * 2)
    acc = _addmod(acc, _mod31(t10h))
    acc = _addmod(acc, _mod31(t10l << 16))
    acc = _addmod(acc, _mod31(t00))
    return acc


def _sum_mod(v, axis: int):
    """Exact modular sum of terms < p along ``axis`` (kept as size 1).

    Each term is split into 16-bit halves, which are summed in int32 —
    the TPU reduces signed integers only — exactly for up to 2^15 terms
    (lo <= n·(2^16−1), hi <= n·(2^15−1), both < 2^31); hi·2^16 is then
    folded back mod p. Two vectorized reductions, no sequential tree.
    """
    assert v.shape[axis] <= (1 << 15), "too many terms for exact i32 sums"
    s_lo = jnp.sum((v & MASK16).astype(jnp.int32), axis=axis, keepdims=True)
    s_hi = jnp.sum((v >> 16).astype(jnp.int32), axis=axis, keepdims=True)
    return _addmod(_mod31(s_lo.astype(jnp.uint32)),
                   _mulmod(_mod31(s_hi.astype(jnp.uint32)),
                           jnp.uint32(1 << 16)))


def lane_tags(parts):
    """(..., 1, 128) per-lane partial tags -> (...) per-block tags."""
    return _sum_mod(parts[..., 0, :], -1)[..., 0]


def _otp_mac_kernel(msg_ref, pad_ref, pw_ref, ct_ref, tag_ref):
    msg = msg_ref[0]
    pad = pad_ref[0]
    ct = msg ^ pad
    ct_ref[0] = ct
    lo = (ct & MASK16) + 1          # MAC symbols (+1 padding-proof)
    hi = (ct >> 16) + 1
    terms = _addmod(_mulmod(lo, pw_ref[0]), _mulmod(hi, pw_ref[1]))
    tag_ref[0] = _sum_mod(terms, 0)


def _otp_mac_edge_kernel(msg_ref, pad_ref, pw_ref, ct_ref, tag_ref):
    """Same fused XOR+MAC body as ``_otp_mac_kernel``, lifted to an edge
    axis: blocks are (1, 1, R, C) slices of the (E, nb, R, C) streams and
    the key-power table is per edge ((1, 2, R, C) — each edge has its own
    evaluation point r)."""
    msg = msg_ref[0, 0]
    pad = pad_ref[0, 0]
    ct = msg ^ pad
    ct_ref[0, 0] = ct
    lo = (ct & MASK16) + 1          # MAC symbols (+1 padding-proof)
    hi = (ct >> 16) + 1
    terms = _addmod(_mulmod(lo, pw_ref[0, 0]), _mulmod(hi, pw_ref[0, 1]))
    tag_ref[0, 0] = _sum_mod(terms, 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def otp_xor_mac_edge_blocks(msg: jax.Array, pad: jax.Array,
                            powers: jax.Array, block_rows: int = 128,
                            interpret: bool | None = None):
    """Edge-batched entry: msg/pad (E, nb, R, 128); powers (E, 2, R, 128).

    Grid (E, nb) — edges × word blocks — so one kernel launch streams
    EVERY edge's ciphertext and partial tags of a round stage. Returns
    (ct same shape, tags (E, nb) uint32 per-(edge, block) partials).
    """
    E, nb, R, C = msg.shape
    assert C == 128 and R == block_rows and powers.shape == (E, 2, R, C)
    ct, parts = pl.pallas_call(
        _otp_mac_edge_kernel,
        grid=(E, nb),
        in_specs=[
            pl.BlockSpec((1, 1, R, C), lambda e, i: (e, i, 0, 0)),
            pl.BlockSpec((1, 1, R, C), lambda e, i: (e, i, 0, 0)),
            pl.BlockSpec((1, 2, R, C), lambda e, i: (e, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, R, C), lambda e, i: (e, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, C), lambda e, i: (e, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((E, nb, R, C), jnp.uint32),
            jax.ShapeDtypeStruct((E, nb, 1, C), jnp.uint32),
        ],
        interpret=resolve_interpret(interpret),
    )(msg, pad, powers)
    return ct, lane_tags(parts)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def otp_xor_mac_blocks(msg: jax.Array, pad: jax.Array, powers: jax.Array,
                       block_rows: int = 128, interpret: bool | None = None):
    """msg/pad (n_blocks, R, 128) uint32; powers (2, R, 128).

    Returns (ct same shape, tags (n_blocks,) uint32 partial MACs).
    """
    nb, R, C = msg.shape
    assert C == 128 and R == block_rows and powers.shape == (2, R, C)
    ct, parts = pl.pallas_call(
        _otp_mac_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, R, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, R, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((2, R, C), lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, R, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, R, C), jnp.uint32),
            jax.ShapeDtypeStruct((nb, 1, C), jnp.uint32),
        ],
        interpret=resolve_interpret(interpret),
    )(msg, pad, powers)
    return ct, lane_tags(parts)
