"""Public wrapper for the SSD scan kernel: model layout + custom VJP.

``ssd_scan`` is a drop-in for ``blocks.ssd_ref`` (pass it as
``ssm_apply(..., ssd_fn=ssd_scan)``). Forward = Pallas kernel; backward =
recompute through the jnp oracle (the selective-scan backward is itself a
scan — fusing it is listed as future §Perf work).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan_bhsp
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.models.blocks import ssd_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssd(x, dt, A, Bv, Cv, chunk, interpret, use_kernel):
    if use_kernel:
        return ssd_scan_bhsp(x, dt, A, Bv, Cv, chunk=chunk,
                             interpret=interpret)
    return ssd_scan_ref(x, dt, A, Bv, Cv, chunk=chunk)


def _fwd(x, dt, A, Bv, Cv, chunk, interpret, use_kernel):
    return _ssd(x, dt, A, Bv, Cv, chunk, interpret, use_kernel), \
        (x, dt, A, Bv, Cv)


def _bwd(chunk, interpret, use_kernel, res, cots):
    x, dt, A, Bv, Cv = res
    _, vjp = jax.vjp(
        lambda *a: ssd_scan_ref(*a, chunk=chunk), x, dt, A, Bv, Cv)
    return vjp(cots)


_ssd.defvjp(_fwd, _bwd)


def ssd_scan(xh, dt, A, Bv, Cv, chunk: int = 128, init_state=None,
             interpret: bool | None = None, use_kernel: bool = True):
    """Model-layout drop-in for blocks.ssd_ref: xh (B,S,H,P), dt (B,S,H),
    A (H,), Bv/Cv (B,S,G,N) -> (y (B,S,H,P), final_state (B,H,P,N)).

    init_state is unsupported on the kernel path (always zero — matching
    training/prefill use); pass init_state only through the reference.
    """
    if init_state is not None:
        return ssd_ref(xh, dt, A, Bv, Cv, chunk=chunk, init_state=init_state)
    S = xh.shape[1]
    c = min(chunk, S)
    while S % c != 0:
        c -= 1
    x_k = jnp.moveaxis(xh, 2, 1)
    dt_k = jnp.moveaxis(dt, 2, 1)
    B_k = jnp.moveaxis(Bv, 2, 1)
    C_k = jnp.moveaxis(Cv, 2, 1)
    y, st = _ssd(x_k, dt_k, A, B_k, C_k, c, interpret, use_kernel)
    return jnp.moveaxis(y, 1, 2), st
