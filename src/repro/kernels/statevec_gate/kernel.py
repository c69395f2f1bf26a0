"""Single-qubit gate application over a statevector — Pallas TPU kernel.

The statevector (2^n complex amplitudes) is stored as two f32 planes
(re, im) — TPU has no native complex, and planes keep every op on the VPU.
Applying a 2x2 gate to qubit q pairs each amplitude i with i ^ (1<<q): a
strided 2-point butterfly — *exactly* the memory pattern of an FFT stage,
memory-bound with 14 flops / 4 loads.

Layout: a tile of t = 2^k amplitudes sits in VMEM as a (rows, 128) f32
array, amplitude j at (j // 128, j % 128), zero-padded to at least one
(8, 128) vreg tile (a padded row or lane pairs only with padding, so the
padding never reaches a real amplitude). A butterfly stage never
reshapes: qubit q < 7 pairs lanes 2^q apart, qubit q >= 7 pairs rows
2^(q-7) apart, and each element takes its partner from one of two
rotations (``pltpu.roll``) selected by bit q of its index. The gates'
8 real scalars sit in SMEM.

Per-gate entry (``apply_gate_planes``), two regimes chosen from q:

  * stride-in-tile (2^(q+1) <= tile): one stage over each tile;
  * tile-in-stride (2^q >= tile): the state viewed as (hi, 2, lo) — a
    block (1, 2, T) spans both butterfly halves at matching lo-offsets,
    and the stage is plain elementwise work on the two halves.

The default tile is 8192 amplitudes ((64, 128) per plane), so any state
up to 13 qubits is ONE grid step per state.

``apply_layer_planes`` is the fused-layer entry point: it consumes the
same per-qubit gate tensor the fused simulator path builds — packed
(nq, 8) — and runs ALL nq butterfly stages over a resident state tile in
one kernel (an in-VMEM FFT, one HBM round-trip for the whole layer
instead of one per gate).

``apply_layer_planes_tiled`` extends the fusion past the VMEM cliff: the
qubits are split into GROUPS, and each group's butterfly stages are fused
inside one tile while the grid sweeps the rest of the state — one HBM
pass per qubit *group* instead of one per gate. Group 0 (qubits
0..low_qubits-1: strides inside an 8192-amplitude tile) reuses the
resident kernel per tile; every higher group [q0, q0+gs) views the state
as (hi, 2^gs, lo) and fuses its gs stages over (2^gs, T) blocks spanning
the full middle axis, pairing rows of the block. A 20-qubit layer is 2
passes (13 + 7 qubits) instead of 20 per-gate sweeps. All entries take
states with leading batch dims — the constellation-batched round
engine's client-stacked (B, 2^nq) states fold straight into the grid.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

LANES = 128
SUBLANES = 8
DEFAULT_TILE = 8192
# a whole statevector this size or smaller stays resident for a fused layer
MAX_FUSED_DIM = 8192
# tiled multi-stage defaults: group 0 covers LOW_QUBITS in-tile stages;
# each later pass fuses up to GROUP_QUBITS stages over (2^gs, GROUP_TILE)
# blocks (64k f32 elements per plane per block — comfortably sub-VMEM)
LOW_QUBITS = 13
GROUP_QUBITS = 7
GROUP_TILE = 512

_GATE_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole (k, 8) table


def _butterfly(g, k, a0r, a0i, a1r, a1i):
    """Gate k of the SMEM table g applied to the pair (a0, a1)."""
    g00r, g00i, g01r, g01i, g10r, g10i, g11r, g11i = [g[k, i]
                                                      for i in range(8)]
    y0r = g00r * a0r - g00i * a0i + g01r * a1r - g01i * a1i
    y0i = g00r * a0i + g00i * a0r + g01r * a1i + g01i * a1r
    y1r = g10r * a0r - g10i * a0i + g11r * a1r - g11i * a1i
    y1i = g10r * a0i + g10i * a0r + g11r * a1i + g11i * a1r
    return y0r, y0i, y1r, y1i


def _stage(g, k, xr, xi, axis: int, s: int):
    """Butterfly stage k: element i pairs with i ^ s along ``axis``.

    An element whose bit s is clear is the pair's first amplitude (self
    weight g00, partner weight g01); one whose bit is set is the second
    (g11 and g10). The partner is read from a rotation either way.
    """
    n = xr.shape[axis]
    high = (jax.lax.broadcasted_iota(jnp.int32, xr.shape, axis) & s) != 0

    def partner(x):
        return jnp.where(high, pltpu.roll(x, s, axis),
                         pltpu.roll(x, n - s, axis))

    def coef(i_low, i_high):
        return jnp.where(high, g[k, i_high], g[k, i_low])

    pr, pi = partner(xr), partner(xi)
    sr, si = coef(0, 6), coef(1, 7)
    cr, ci = coef(2, 4), coef(3, 5)
    return (sr * xr - si * xi + cr * pr - ci * pi,
            sr * xi + si * xr + cr * pi + ci * pr)


def _kernel_tile(g_ref, xr_ref, xi_ref, or_ref, oi_ref, *, qubits):
    """Stage k applies gate k to qubit ``qubits[k]`` of a resident
    (rows, 128) tile — the tile never leaves VMEM between stages."""
    xr = xr_ref[0]
    xi = xi_ref[0]
    for k, q in enumerate(qubits):           # static unroll
        if q < 7:
            xr, xi = _stage(g_ref, k, xr, xi, 1, 1 << q)
        else:
            xr, xi = _stage(g_ref, k, xr, xi, 0, 1 << (q - 7))
    or_ref[0] = xr
    oi_ref[0] = xi


def _tile_call(qubits, g, xr, xi, interpret):
    """Run ``_kernel_tile`` over (n, t) rows of t = 2^k amplitudes, one
    grid step per row, each a zero-padded (rows, 128) VMEM tile."""
    n, t = xr.shape
    lanes = min(t, LANES)
    rows = t // lanes
    prow = max(rows, SUBLANES)

    def to_tiles(x):
        x = x.reshape(n, rows, lanes)
        return jnp.pad(x, ((0, 0), (0, prow - rows), (0, LANES - lanes)))

    spec = pl.BlockSpec((1, prow, LANES), lambda i: (i, 0, 0))
    outr, outi = pl.pallas_call(
        functools.partial(_kernel_tile, qubits=tuple(qubits)),
        grid=(n,),
        in_specs=[_GATE_SPEC, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n, prow, LANES), jnp.float32)] * 2,
        interpret=interpret,
    )(g, to_tiles(xr), to_tiles(xi))
    return (outr[:, :rows, :lanes].reshape(n, t),
            outi[:, :rows, :lanes].reshape(n, t))


def _kernel_pair(g_ref, xr_ref, xi_ref, or_ref, oi_ref):
    """Blocks (1, 2, rows, lanes) of the (hi, 2, lo) view span both
    halves of every pair."""
    y0r, y0i, y1r, y1i = _butterfly(
        g_ref, 0, xr_ref[0, 0], xi_ref[0, 0], xr_ref[0, 1], xi_ref[0, 1])
    or_ref[0, 0] = y0r
    oi_ref[0, 0] = y0i
    or_ref[0, 1] = y1r
    oi_ref[0, 1] = y1i


def _batch(shape) -> int:
    return math.prod(shape[:-1])


@functools.partial(jax.jit, static_argnames=("qubit", "tile", "interpret"))
def apply_gate_planes(state_re: jax.Array, state_im: jax.Array,
                      gate8: jax.Array, qubit: int, tile: int = DEFAULT_TILE,
                      interpret: bool | None = None):
    """state planes (..., dim) f32; gate8 (8,) f32 packed
    [g00r, g00i, g01r, g01i, g10r, g10i, g11r, g11i]."""
    interpret = resolve_interpret(interpret)
    shape = state_re.shape
    dim = shape[-1]
    b = _batch(shape)
    lo = 1 << qubit
    g = gate8.reshape(1, 8).astype(jnp.float32)

    if 2 * lo <= min(tile, dim):
        t = min(tile, dim)
        outr, outi = _tile_call((qubit,), g, state_re.reshape(-1, t),
                                state_im.reshape(-1, t), interpret)
        return outr.reshape(shape), outi.reshape(shape)

    # large stride: view (hi, 2, lo), tile the lo axis
    hi = b * dim // (2 * lo)
    t = min(tile, lo)
    lanes = min(t, LANES)
    view = (hi, 2, lo // lanes, lanes)
    spec = pl.BlockSpec((1, 2, t // lanes, lanes),
                        lambda h, j: (h, 0, j, 0))
    outr, outi = pl.pallas_call(
        _kernel_pair,
        grid=(hi, lo // t),
        in_specs=[_GATE_SPEC, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(view, jnp.float32)] * 2,
        interpret=interpret,
    )(g, state_re.reshape(view), state_im.reshape(view))
    return outr.reshape(shape), outi.reshape(shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_layer_planes(state_re: jax.Array, state_im: jax.Array,
                       gates8: jax.Array, interpret: bool | None = None):
    """Apply gate q to qubit q for ALL qubits in one kernel launch.

    state planes (..., dim) f32 with dim <= MAX_FUSED_DIM (the whole state
    must sit in VMEM — larger states take ``apply_layer_planes_tiled``);
    gates8 (nq, 8) f32, the packed per-qubit gate tensor. Leading batch
    dims fold into the grid (one resident tile per stacked state).
    """
    interpret = resolve_interpret(interpret)
    shape = state_re.shape
    dim = shape[-1]
    nq = dim.bit_length() - 1
    assert dim <= MAX_FUSED_DIM, (dim, MAX_FUSED_DIM)
    assert gates8.shape == (nq, 8), gates8.shape
    outr, outi = _tile_call(range(nq), gates8.astype(jnp.float32),
                            state_re.reshape(-1, dim),
                            state_im.reshape(-1, dim), interpret)
    return outr.reshape(shape), outi.reshape(shape)


def _kernel_group(g_ref, xr_ref, xi_ref, or_ref, oi_ref, *, gs: int):
    """Butterfly stages of one qubit GROUP over a (2^gs, T) block.

    The block spans the full middle axis of the (hi, 2^gs, lo) state view,
    so stage t (global qubit q0 + t) pairs block rows differing in bit t —
    all gs stages run with the block resident (one HBM pass for the whole
    group).
    """
    xr = xr_ref[0]
    xi = xi_ref[0]
    for t in range(gs):                      # static unroll
        xr, xi = _stage(g_ref, t, xr, xi, 0, 1 << t)
    or_ref[0] = xr
    oi_ref[0] = xi


@functools.partial(jax.jit, static_argnames=("low_qubits", "group_qubits",
                                             "group_tile", "interpret"))
def apply_layer_planes_tiled(state_re: jax.Array, state_im: jax.Array,
                             gates8: jax.Array, low_qubits: int = LOW_QUBITS,
                             group_qubits: int = GROUP_QUBITS,
                             group_tile: int = GROUP_TILE,
                             interpret: bool | None = None):
    """Fused layer past the VMEM cliff: one HBM pass per qubit group.

    state planes (..., dim) f32, any dim = 2^nq; gates8 (nq, 8) f32.
    Pass 0 fuses qubits [0, low_qubits) with the resident per-tile kernel;
    each later pass fuses up to ``group_qubits`` stages over
    (2^gs, group_tile) blocks of the (hi, 2^gs, lo) view. Leading batch
    dims fold into the hi grid axis.
    """
    interpret = resolve_interpret(interpret)
    shape = state_re.shape
    dim = shape[-1]
    nq = dim.bit_length() - 1
    assert gates8.shape == (nq, 8), (gates8.shape, nq)
    g = gates8.astype(jnp.float32)
    b = _batch(shape)

    # pass 0: in-tile stages, grid over (batch · dim/T) tiles
    g0 = min(nq, low_qubits)
    t0 = 1 << g0
    xr, xi = _tile_call(range(g0), g[:g0], state_re.reshape(-1, t0),
                        state_im.reshape(-1, t0), interpret)

    # higher passes: fuse up to group_qubits stages per (2^gs, Tc) block
    q0 = g0
    while q0 < nq:
        gs = min(nq - q0, group_qubits)
        mid = 1 << gs
        lo = 1 << q0
        hi = b * (dim // (mid * lo))
        tc = min(lo, group_tile)
        spec = pl.BlockSpec((1, mid, tc), lambda h, t: (h, 0, t))
        xr, xi = pl.pallas_call(
            functools.partial(_kernel_group, gs=gs),
            grid=(hi, lo // tc),
            in_specs=[_GATE_SPEC, spec, spec],
            out_specs=[spec, spec],
            out_shape=[jax.ShapeDtypeStruct((hi, mid, lo), jnp.float32)] * 2,
            interpret=interpret,
        )(g[q0:q0 + gs], xr.reshape(hi, mid, lo), xi.reshape(hi, mid, lo))
        q0 += gs

    return xr.reshape(shape), xi.reshape(shape)
