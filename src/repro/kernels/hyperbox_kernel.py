"""Pallas TPU kernel for the hyper-rectangle LP special case (paper Sec. 5.6).

The paper dedicates one 32-thread block (one active thread!) per box LP; on
TPU the whole tile is a single fused select+FMA+lane-reduction:

    support = sum_i  d_i * (d_i < 0 ? lo_i : hi_i)

Grid over batch tiles; (tile_b, n_pad) blocks in VMEM; padding lanes carry
d = 0 so they contribute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import round_up


def _hyperbox_kernel(lo_ref, hi_ref, d_ref, out_ref):
    lo = lo_ref[...]
    hi = hi_ref[...]
    d = d_ref[...]
    pick = jnp.where(d < 0, lo, hi)
    out_ref[...] = jnp.sum(d * pick, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def hyperbox_pallas(lo, hi, d, *, tile_b: int = 256, interpret: bool):
    """lo/hi/d: (B, n) -> (B,) support values."""
    B, n = lo.shape
    n_pad = round_up(n, 128)
    B_pad = round_up(B, tile_b)

    def pad(a, fill=0.0):
        return jnp.pad(a, ((0, B_pad - B), (0, n_pad - n)),
                       constant_values=fill)

    out = pl.pallas_call(
        _hyperbox_kernel,
        grid=(B_pad // tile_b,),
        in_specs=[
            pl.BlockSpec((tile_b, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, n_pad), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B_pad, 1), lo.dtype),
        interpret=interpret,
    )(pad(lo), pad(hi), pad(d))
    return out[:B, 0]
