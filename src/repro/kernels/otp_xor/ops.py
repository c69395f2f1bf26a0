"""Public jit'd wrapper for the fused OTP-XOR + MAC kernel.

Handles stream padding/alignment, builds the per-block key-power table,
launches the kernel, and combines per-block partial tags into the final
GF(2^31−1) tag — bit-identical to ``repro.security.mac.poly_mac_u32`` over
the padded stream (tests assert this).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.otp_xor.kernel import otp_xor_mac_blocks
from repro.security.mac import P31, _mod31, addmod, mulmod, _powers


def _pow_mod(r, e: int):
    """r^e mod p by square-and-multiply (host ints for the exponent)."""
    acc = jnp.uint32(1)
    base = r
    while e:
        if e & 1:
            acc = mulmod(acc, base)
        base = mulmod(base, base)
        e >>= 1
    return acc


# 128 rows × 128 lanes = 16384 words/block: the 16k-word exchange in
# bench_kernels is ONE grid step (interpret-mode step overhead dominated
# the old 8-row tiling), and the per-block powers table stays exact-u32.
# Both ends of a link must agree on the tiling — the MAC covers the
# padded stream, so the block size is part of the wire format.
DEFAULT_BLOCK_ROWS = 128


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "use_kernel"))
def otp_xor_mac(msg_u32: jax.Array, pad_u32: jax.Array, r_key, s_key,
                block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool | None = None, use_kernel: bool = True):
    """Encrypt-and-tag a flat uint32 stream.

    Returns (ciphertext (n,) uint32, tag uint32). The MAC is computed over
    the zero-padded aligned stream (length folded into the tag), so tags
    are comparable only for equal logical lengths — which the receiver
    knows from the tree structure.
    """
    n = msg_u32.shape[0]
    R, C = block_rows, 128
    words_pb = R * C
    nb = max((n + words_pb - 1) // words_pb, 1)
    padded = nb * words_pb

    r = _mod31(jnp.asarray(r_key, jnp.uint32)) | jnp.uint32(1)
    s = _mod31(jnp.asarray(s_key, jnp.uint32))

    msg = jnp.zeros((padded,), jnp.uint32).at[:n].set(msg_u32)
    pad = jnp.zeros((padded,), jnp.uint32).at[:n].set(pad_u32[:n])
    msg = msg.reshape(nb, R, C)
    pad = pad.reshape(nb, R, C)

    # per-block symbol powers: word w -> lo symbol r^(sb-2w), hi r^(sb-2w-1)
    sb = 2 * words_pb
    pw_all = _powers(r, sb)                     # r^1 .. r^sb
    pw_desc = pw_all[::-1]                      # r^sb .. r^1
    pw_lo = pw_desc[0::2].reshape(R, C)
    pw_hi = pw_desc[1::2].reshape(R, C)
    powers = jnp.stack([pw_lo, pw_hi])

    if use_kernel:
        ct_blocks, tags = otp_xor_mac_blocks(msg, pad, powers,
                                             block_rows=R,
                                             interpret=interpret)
    else:
        from repro.kernels.otp_xor.ref import otp_xor_mac_blocks_ref
        ct_blocks, tags = otp_xor_mac_blocks_ref(msg, pad, powers)

    # combine partial tags: tag = sum_j tags[j] * r^(sb*(nb-1-j)) + N*s
    r_sb = _pow_mod(r, sb)
    def body(carry, t):
        # Horner over blocks: carry = carry * r^sb + tag_j
        return addmod(mulmod(carry, r_sb), t), ()
    tag, _ = jax.lax.scan(body, jnp.uint32(0), tags)
    n_sym = jnp.uint32((2 * padded) % 0x7FFFFFFF)
    tag = addmod(tag, mulmod(n_sym, s))
    return ct_blocks.reshape(-1)[:n], tag


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "use_kernel"))
def otp_xor_mac_edges(msgs_u32: jax.Array, pads_u32: jax.Array, r_keys,
                      s_keys, block_rows: int = DEFAULT_BLOCK_ROWS,
                      interpret: bool | None = None, use_kernel: bool = True):
    """Edge-batched encrypt-and-tag: one launch for a whole round stage.

    msgs/pads (E, n) uint32 — row e is edge e's wire stream; r/s keys
    (E,). Returns (ciphertexts (E, n), tags (E,)), each row identical to
    ``otp_xor_mac(msgs[e], pads[e], r_keys[e], s_keys[e])`` — same block
    layout, same padded-stream tag convention, exact GF(2^31−1) math.
    """
    E, n = msgs_u32.shape
    R, C = block_rows, 128
    words_pb = R * C
    nb = max((n + words_pb - 1) // words_pb, 1)
    padded = nb * words_pb

    r = _mod31(jnp.asarray(r_keys, jnp.uint32)) | jnp.uint32(1)
    s = _mod31(jnp.asarray(s_keys, jnp.uint32))

    msg = jnp.zeros((E, padded), jnp.uint32).at[:, :n].set(msgs_u32)
    pad = jnp.zeros((E, padded), jnp.uint32).at[:, :n].set(pads_u32[:, :n])
    msg = msg.reshape(E, nb, R, C)
    pad = pad.reshape(E, nb, R, C)

    # per-edge symbol powers: each edge has its own evaluation point r_e
    sb = 2 * words_pb
    pw_all = jax.vmap(lambda re: _powers(re, sb))(r)         # (E, sb)
    pw_desc = pw_all[:, ::-1]                                # r^sb .. r^1
    pw_lo = pw_desc[:, 0::2].reshape(E, R, C)
    pw_hi = pw_desc[:, 1::2].reshape(E, R, C)
    powers = jnp.stack([pw_lo, pw_hi], axis=1)               # (E, 2, R, C)

    if use_kernel:
        from repro.kernels.otp_xor.kernel import otp_xor_mac_edge_blocks
        ct_blocks, tags_b = otp_xor_mac_edge_blocks(msg, pad, powers,
                                                    block_rows=R,
                                                    interpret=interpret)
    else:
        from repro.kernels.otp_xor.ref import otp_xor_mac_edge_blocks_ref
        ct_blocks, tags_b = otp_xor_mac_edge_blocks_ref(msg, pad, powers)

    r_sb = jax.vmap(lambda re: _pow_mod(re, sb))(r)          # (E,) r_e^sb

    def combine(tags_e, r_sb_e, s_e):
        def body(carry, t):
            return addmod(mulmod(carry, r_sb_e), t), ()
        tag, _ = jax.lax.scan(body, jnp.uint32(0), tags_e)
        n_sym = jnp.uint32((2 * padded) % 0x7FFFFFFF)
        return addmod(tag, mulmod(n_sym, s_e))

    tags = jax.vmap(combine)(tags_b, r_sb, s)
    return ct_blocks.reshape(E, -1)[:, :n], tags
