"""Sliding-window flash attention — Pallas TPU kernel (forward).

Attention restricted to the causal band [i−W+1, i]: only the key blocks
the band can touch are visited instead of all S/BK — the sub-quadratic
variant that makes the dense/MoE/VLM archs feasible at 500 k context
(O(S·W) work, O(S) memory).

Tiling (the retile that finally beats the folded-ref XLA path):

  * the batch·heads axis is FOLDED INTO THE BLOCKS (up to ``BLOCK_BH``
    rows per block) rather than spent as a grid axis — every step runs
    one batched MXU matmul instead of BH vector ones;
  * the kv band is loaded as ``nkv`` SEPARATE block inputs of the same
    k/v arrays (one BlockSpec per banded block index, anchored at the
    LAST query row's block and clamped to 0), so a query block sees its
    whole band at once and the softmax is a SINGLE exact pass — no
    (m, l, acc) running-rescale chain, no scratch, no sequential grid
    axis. ``nkv`` is exact for the band: ceil((W−1)/BK) + (BQ−1)/BK + 1
    blocks (the old formula over-provisioned by one).

Defaults are BQ=256, BK=128: at S=256, W=64, BH=8 the whole op is ONE
grid step (was 32) — a single fused banded-attention block per
batch·head slab — and at longer S each 256-row query slab touches only
ceil((W−1)/128) + 3 key blocks. Clamped duplicate blocks (raw index < 0)
are fully masked, contributing zero weight. window == 0 degrades to full
causal attention (the band covers every block up to the diagonal) — the
baseline in the kernel benchmarks.

The single-pass plan keeps the whole band resident, so its VMEM need
grows with the band. Bands wider than ``MAX_BAND_STEPS`` blocks (huge W,
or window == 0 at long S) take the STREAMING plan instead: the same
block layout but with the kv axis as a sequential grid dimension
carrying (m, l, acc) online-softmax state in scratch — O(BQ·BK) memory
regardless of S and W, the classic flash recurrence.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

# the MXU's default rounds f32 operands to bf16; these kernels promise
# their f32 oracle's numbers (2e-3 / 1e-2 off at the default on a v5e)
FULL_F32 = jax.lax.Precision.HIGHEST

NEG_INF = -1e30
BLOCK_BH = 8       # batch·head rows folded into one block
MAX_BAND_STEPS = 4  # widest band (in BK blocks) kept fully VMEM-resident
# full-f32 matmuls split their operands in VMEM: the band-resident plan
# at BLOCK_BH=8, BQ=256 outgrows the default 16 MiB scoped VMEM of a v5e
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def kv_steps(S: int, W: int, BQ: int, BK: int) -> int:
    if W <= 0:
        return S // BK                     # full causal: every block to diag
    # exact block count of the band [i−W+1, i] across a BQ-row query block
    steps = (BQ - 1) // BK + math.ceil((W - 1) / BK) + 1
    return min(steps, S // BK)


def _fwd_kernel(*refs, BQ: int, BK: int, W: int, nkv: int, scale: float):
    q_ref = refs[0]
    k_refs = refs[1:1 + nkv]
    v_refs = refs[1 + nkv:1 + 2 * nkv]
    o_ref = refs[1 + 2 * nkv]
    qi = pl.program_id(1)
    qb_last = (qi * BQ + BQ - 1) // BK

    q = q_ref[...].astype(jnp.float32) * scale          # (BBH, BQ, hd)
    q_pos = qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
    scores = []
    for j in range(nkv):                                # static unroll
        # mirror the index_map's clamped banded block choice
        raw_kb = qb_last - (nkv - 1) + j
        kb = jnp.maximum(raw_kb, 0)
        k_pos = kb * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
        mask = k_pos <= q_pos
        if W > 0:
            mask &= k_pos > q_pos - W
        # drop duplicate clamped blocks (raw_kb < 0 maps onto block 0,
        # which a later j visits legitimately)
        mask &= jnp.broadcast_to(raw_kb >= 0, mask.shape)
        k = k_refs[j][...].astype(jnp.float32)          # (BBH, BK, hd)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                precision=FULL_F32,
                                preferred_element_type=jnp.float32)
        scores.append(jnp.where(mask[None], s, NEG_INF))

    s = jnp.concatenate(scores, axis=2)                 # (BBH, BQ, nkv·BK)
    m = jnp.max(s, axis=2, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=2, keepdims=True)
    v = jnp.concatenate([vr[...].astype(jnp.float32) for vr in v_refs],
                        axis=1)                         # (BBH, nkv·BK, hd)
    o = jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                            precision=FULL_F32,
                            preferred_element_type=jnp.float32)
    o_ref[...] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _fwd_kernel_stream(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                       *, BQ: int, BK: int, W: int, nkv: int, scale: float):
    """Online-softmax recurrence over the kv grid axis (wide-band plan)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qb_last = (qi * BQ + BQ - 1) // BK
    raw_kb = qb_last - (nkv - 1) + ki
    kb = jnp.maximum(raw_kb, 0)
    q_pos = qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
    k_pos = kb * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
    mask = k_pos <= q_pos
    if W > 0:
        mask &= k_pos > q_pos - W
    mask &= jnp.broadcast_to(raw_kb >= 0, mask.shape)

    q = q_ref[...].astype(jnp.float32) * scale
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            precision=FULL_F32,
                            preferred_element_type=jnp.float32)
    s = jnp.where(mask[None], s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=2, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        precision=FULL_F32,
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nkv - 1)
    def _flush():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret"))
def swa_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array,
                       window: int = 0, block_q: int = 256,
                       block_k: int = 128,
                       interpret: bool | None = None):
    """q/k/v (BH, S, hd) — heads folded into batch, kv pre-repeated for GQA.
    Returns o (BH, S, hd)."""
    interpret = resolve_interpret(interpret)
    BH, S, hd = q.shape
    # degrade block sizes to divisors of S (e.g. S=384 -> BQ 256->128);
    # the band formula and qb_last anchor additionally need the q and kv
    # block boundaries to nest (one a multiple of the other), so shrink
    # BK until they do
    BQ = min(block_q, S)
    while S % BQ:
        BQ //= 2
    BK = min(block_k, S)
    while S % BK or not (BQ % BK == 0 or BK % BQ == 0):
        BK //= 2
    nkv = kv_steps(S, window, BQ, BK)
    nq = S // BQ
    scale = 1.0 / math.sqrt(hd)
    # widest BH slab that tiles the folded batch-head axis
    bbh = BLOCK_BH
    while BH % bbh:
        bbh //= 2

    def kv_map(j):
        def index(bh, qi):
            qb_last = (qi * BQ + BQ - 1) // BK
            return (bh, jnp.maximum(qb_last - (nkv - 1) + j, 0), 0)
        return index

    if nkv <= MAX_BAND_STEPS:
        # band-resident plan: all nkv blocks in one step, exact softmax
        kv_spec = [pl.BlockSpec((bbh, BK, hd), kv_map(j)) for j in range(nkv)]
        return pl.pallas_call(
            functools.partial(_fwd_kernel, BQ=BQ, BK=BK, W=window, nkv=nkv,
                              scale=scale),
            grid=(BH // bbh, nq),
            in_specs=[pl.BlockSpec((bbh, BQ, hd),
                                   lambda bh, qi: (bh, qi, 0))]
            + kv_spec + kv_spec,
            out_specs=pl.BlockSpec((bbh, BQ, hd),
                                   lambda bh, qi: (bh, qi, 0)),
            out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
            compiler_params=COMPILER_PARAMS,
            interpret=interpret,
        )(q, *([k] * nkv), *([v] * nkv))

    # wide band: stream kv blocks with the online-softmax recurrence
    def kv_map_seq(bh, qi, ki):
        qb_last = (qi * BQ + BQ - 1) // BK
        return (bh, jnp.maximum(qb_last - (nkv - 1) + ki, 0), 0)

    return pl.pallas_call(
        functools.partial(_fwd_kernel_stream, BQ=BQ, BK=BK, W=window,
                          nkv=nkv, scale=scale),
        grid=(BH // bbh, nq, nkv),
        in_specs=[
            pl.BlockSpec((bbh, BQ, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((bbh, BK, hd), kv_map_seq),
            pl.BlockSpec((bbh, BK, hd), kv_map_seq),
        ],
        out_specs=pl.BlockSpec((bbh, BQ, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bbh, BQ, 1), jnp.float32),
            pltpu.VMEM((bbh, BQ, 1), jnp.float32),
            pltpu.VMEM((bbh, BQ, hd), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v)
