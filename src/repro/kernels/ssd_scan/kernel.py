"""Mamba-2 SSD chunked scan — Pallas TPU kernel (forward).

The SSD duality splits the selective-scan into (i) an intra-chunk part
that is pure matmul work — (Q,N)x(N,Q) score + (Q,Q)x(Q,P) mix, which the
MXU eats — and (ii) a tiny inter-chunk recurrence on the (P,N) state.

Tiling: the whole HEAD axis is folded into the block (the retile that
took this kernel past its reference): the grid is (batch, chunks) with
the chunk axis innermost (sequential on TPU) carrying the running
(H, P, N) state in VMEM scratch — at B=1, H=4, S=256 that is 2 grid
steps instead of the 8 the per-(batch, head) grid paid, and every matmul
is one batched MXU dispatch over all heads. GQA B/C groups ride in as
(G, Q, N) blocks and are repeated to heads inside the kernel. The
recurrence never leaves VMEM, and HBM traffic is exactly one read of
x/dt/B/C and one write of y — the memory lower bound for the op.

Per chunk (Q = chunk length, P = head dim, N = state dim, per head):
    dA        = dt * A_h                         (Q,)
    L         = exp(segsum(dA)) causal           (Q, Q)
    y_diag    = ((C Bᵀ) ∘ L ∘ dt) x              (Q, P)
    y_off     = (C state_inᵀ) ∘ exp(cumsum dA)   (Q, P)
    state_out = state_in · exp(sum dA) + (B ∘ decay ∘ dt)ᵀ x    (P, N)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

# the MXU's default rounds f32 operands to bf16; these kernels promise
# their f32 oracle's numbers (2e-3 / 1e-2 off at the default on a v5e)
FULL_F32 = jax.lax.Precision.HIGHEST


def prefix_sum_matmul(x):
    """Inclusive prefix sum along the last axis as a lower-triangular mask
    matmul (Mosaic has no cumsum): out[h, i] = sum_j x[h, j] · [j <= i]."""
    q = x.shape[-1]
    mask = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    return jax.lax.dot_general(x, mask.astype(jnp.float32),
                               (((1,), (0,)), ((), ())), precision=FULL_F32,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_scr,
                *, Q: int, P: int, N: int, H: int, rep: int, nchunks: int,
                prefix_sum):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (H, Q, P)
    dt = dt_ref[0, 0, :, :, 0].astype(jnp.float32)   # (H, Q)
    A = a_ref[0]                                 # (H, 1) per-head scalars
    Bg = b_ref[0, 0].astype(jnp.float32)         # (G, Q, N)
    Cg = c_ref[0, 0].astype(jnp.float32)
    if rep > 1:                                  # GQA: groups -> heads
        Bm = jnp.repeat(Bg, rep, axis=0)         # (H, Q, N)
        Cm = jnp.repeat(Cg, rep, axis=0)
    else:
        Bm, Cm = Bg, Cg

    dA = dt * A                                  # (H, Q) negative
    dA_cs = prefix_sum(dA)                       # (H, Q)

    # intra-chunk: L[h,i,j] = exp(dA_cs[h,i] - dA_cs[h,j]) for i >= j
    seg = dA_cs[:, :, None] - dA_cs[:, None, :]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    L = jnp.where(tri[None], jnp.exp(seg), 0.0)  # (H, Q, Q)
    scores = jax.lax.dot_general(Cm, Bm, (((2,), (2,)), ((0,), (0,))),
                                 precision=FULL_F32,
                                 preferred_element_type=jnp.float32)
    mix = scores * L * dt[:, None, :]            # (H, Q, Q) weight on x_j
    y = jax.lax.dot_general(mix, x, (((2,), (1,)), ((0,), (0,))),
                            precision=FULL_F32,
                            preferred_element_type=jnp.float32)

    # off-diagonal: contribution of the incoming state
    state = state_scr[...]                       # (H, P, N) f32
    decay_out = jnp.exp(dA_cs)[:, :, None]       # (H, Q, 1)
    y = y + jax.lax.dot_general(Cm, state, (((2,), (2,)), ((0,), (0,))),
                                precision=FULL_F32,
                                preferred_element_type=jnp.float32) * decay_out

    # state update
    last = dA_cs[:, Q - 1:Q]                                # (H, 1)
    chunk_decay = jnp.exp(last)[:, :, None]                 # (H, 1, 1)
    decay_states = jnp.exp(last - dA_cs)                    # (H, Q)
    wB = Bm * (decay_states * dt)[:, :, None]               # (H, Q, N)
    state_scr[...] = state * chunk_decay + jax.lax.dot_general(
        x, wB, (((1,), (1,)), ((0,), (0,))),
        precision=FULL_F32,
        preferred_element_type=jnp.float32)

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nchunks - 1)
    def _emit_state():
        st_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_bhsp(x: jax.Array, dt: jax.Array, A: jax.Array, Bv: jax.Array,
                  Cv: jax.Array, chunk: int = 128,
                  interpret: bool | None = None):
    """x (B, H, S, P); dt (B, H, S); A (H,); Bv/Cv (B, G, S, N) with H % G == 0.

    Returns (y (B, H, S, P), final_state (B, H, P, N) f32).
    """
    interpret = resolve_interpret(interpret)
    Bb, H, S, P = x.shape
    G, N = Bv.shape[1], Bv.shape[3]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    rep = H // G

    a2 = jnp.broadcast_to(A.astype(jnp.float32)[None, :, None], (Bb, H, 1))
    # chunk-major views: (B, nc, H, Q, ·) so one block holds every head
    x_k = x.reshape(Bb, H, nc, Q, P).transpose(0, 2, 1, 3, 4)
    dt_k = dt.reshape(Bb, H, nc, Q, 1).transpose(0, 2, 1, 3, 4)
    B_k = Bv.reshape(Bb, G, nc, Q, N).transpose(0, 2, 1, 3, 4)
    C_k = Cv.reshape(Bb, G, nc, Q, N).transpose(0, 2, 1, 3, 4)

    y, st = pl.pallas_call(
        functools.partial(
            _ssd_kernel, Q=Q, P=P, N=N, H=H, rep=rep, nchunks=nc,
            # interpreted, the kernel keeps the CPU oracle's own cumsum
            # (and its rounding: chunk sums of cs differences amplify a
            # different summation order to ~3e-5 in y); compiled, the
            # mask matmul, which tests/test_kernels.py holds to cumsum
            prefix_sum=(functools.partial(jnp.cumsum, axis=1) if interpret
                        else prefix_sum_matmul)),
        grid=(Bb, nc),
        in_specs=[
            pl.BlockSpec((1, 1, H, Q, P), lambda b, c: (b, c, 0, 0, 0)),
            pl.BlockSpec((1, 1, H, Q, 1), lambda b, c: (b, c, 0, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, 1, G, Q, N), lambda b, c: (b, c, 0, 0, 0)),
            pl.BlockSpec((1, 1, G, Q, N), lambda b, c: (b, c, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, H, Q, P), lambda b, c: (b, c, 0, 0, 0)),
            pl.BlockSpec((1, H, P, N), lambda b, c: (b, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, nc, H, Q, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        interpret=interpret,
    )(x_k, dt_k, a2, B_k, C_k)
    y = y.transpose(0, 2, 1, 3, 4).reshape(Bb, H, S, P)
    return y, st
