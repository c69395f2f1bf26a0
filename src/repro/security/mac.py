"""Polynomial MAC over GF(p), p = 2^31 − 1 (Mersenne) — integrity for the
OTP ciphertext (the "authenticated" in authenticated encryption).

Carter–Wegman structure: tag = (Σ_i (m_i + 1) · r^(n−i) + n·s) mod p with a
secret evaluation point r and blind s, both derived from the QKD key. All
arithmetic in uint32 with exact 16×16→32 partial products (no x64
dependency; TPU-friendly). 2^31 ≡ 1 (mod p) makes the reductions one-liner
shifts.

The fused XOR+MAC Pallas kernel (``repro.kernels.otp_xor``) computes
per-block partial tags with this exact arithmetic; tests cross-check.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

P31 = jnp.uint32(0x7FFFFFFF)      # 2^31 - 1
_MASK31 = jnp.uint32(0x7FFFFFFF)


def _mod31(x: jax.Array) -> jax.Array:
    """Reduce a uint32 (< 2^32) mod 2^31−1 using 2^31 ≡ 1."""
    y = (x >> 31) + (x & _MASK31)
    return jnp.where(y >= P31, y - P31, y)


def addmod(a, b):
    return _mod31(a + b)          # a,b < p so a+b < 2^32: exact


def mulmod(a: jax.Array, b: jax.Array) -> jax.Array:
    """(a*b) mod (2^31−1) for a,b < 2^31, in uint32 only.

    Split into 16-bit halves; all partial products are exact in uint32.
    a·b = t11·2^32 + (t10h·2^15 + t10l)·2^16 + t00
        ≡ 2·t11 + t10h + t10l·2^16 + t00   (mod p)   [2^32≡2, 2^31≡1]
    """
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    a1, a0 = a >> 16, a & jnp.uint32(0xFFFF)
    b1, b0 = b >> 16, b & jnp.uint32(0xFFFF)
    t11 = a1 * b1                           # < 2^30
    t10 = a1 * b0 + a0 * b1                 # < 2^32 (exact, see module doc)
    t00 = a0 * b0                           # < 2^32
    t10h, t10l = t10 >> 15, t10 & jnp.uint32(0x7FFF)
    acc = _mod31(t11 * jnp.uint32(2))
    acc = addmod(acc, _mod31(t10h))
    acc = addmod(acc, _mod31(t10l << 16))
    acc = addmod(acc, _mod31(t00))
    return acc


def _sum_mod(v: jax.Array) -> jax.Array:
    """Modular sum of a vector (< p elements) via log-depth pairwise addmod."""
    n = v.shape[0]
    while n > 1:
        if n % 2:
            v = jnp.concatenate([v, jnp.zeros((1,), jnp.uint32)])
            n += 1
        v = addmod(v[0::2], v[1::2])
        n = n // 2
    return v[0]


def _powers(r: jax.Array, n: int) -> jax.Array:
    """[r^1, r^2, ..., r^n] mod p by doubling: r^1..r^k times r^k is
    r^(k+1)..r^(2k), so log2(n) vectorized mulmod steps. (A batched
    ``associative_scan`` of ``mulmod`` takes XLA:CPU minutes to compile.)"""
    pw = jnp.reshape(r.astype(jnp.uint32), (1,))
    while pw.shape[0] < n:
        pw = jnp.concatenate([pw, mulmod(pw, pw[-1])])
    return pw[:n]


def poly_mac_u32(msg_u32: jax.Array, r_key: jax.Array, s_key: jax.Array) -> jax.Array:
    """Tag a flat uint32 message stream.

    Each u32 word is split into two 16-bit symbols (< p). r/s are reduced
    into (0, p) from arbitrary 32-bit key material.
    """
    r = _mod31(r_key.astype(jnp.uint32)) | jnp.uint32(1)   # nonzero
    s = _mod31(s_key.astype(jnp.uint32))
    lo = (msg_u32 & jnp.uint32(0xFFFF)).astype(jnp.uint32)
    hi = (msg_u32 >> 16).astype(jnp.uint32)
    m = jnp.stack([lo, hi], axis=1).reshape(-1) + jnp.uint32(1)  # symbols < p
    n = m.shape[0]
    pw = _powers(r, n)[::-1]                               # r^n ... r^1
    terms = mulmod(m, pw)
    tag = _sum_mod(terms)
    return addmod(tag, mulmod(jnp.uint32(n % 0x7FFFFFFF), s))


def mac_verify(msg_u32: jax.Array, tag: jax.Array, r_key, s_key) -> jax.Array:
    """Constant-time verify: returns bool scalar."""
    return poly_mac_u32(msg_u32, r_key, s_key) == tag


# ---------------------------------------------------------------------------
# edge-batched (stacked) entries
# ---------------------------------------------------------------------------

def poly_mac_rows(msgs_u32: jax.Array, r_keys: jax.Array,
                  s_keys: jax.Array) -> jax.Array:
    """Tag E equal-length streams in one dispatch.

    msgs (E, n) uint32, r/s keys (E,) → tags (E,). Row e is the exact
    ``poly_mac_u32(msgs[e], r_keys[e], s_keys[e])`` value — the arithmetic
    is exact modular math, so batching cannot change a single tag bit.
    """
    return jax.vmap(poly_mac_u32)(msgs_u32, r_keys, s_keys)


def mac_verify_rows(msgs_u32: jax.Array, tags: jax.Array, r_keys,
                    s_keys) -> jax.Array:
    """Vectorized verify: (E,) bool — one recompute for the whole stage."""
    return poly_mac_rows(msgs_u32, r_keys, s_keys) == tags
