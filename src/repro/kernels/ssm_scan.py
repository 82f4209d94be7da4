"""Pallas TPU kernel for the Mamba-1 selective-scan recurrence (hillclimb 4).

The pure-XLA path (`mamba._chunk_scan`) uses `associative_scan`, which
materializes log2(T) levels of (B, T, d_inner, state) temporaries — the
measured reason falcon-mamba's memory roofline term is ~100x its compute
term. This kernel runs the recurrence

    h_t = dA_t * h_t-1 + dBx_t,        t = 0..T-1

sequentially *inside* VMEM: per (batch-tile, channel-tile) grid cell it
reads dA/dBx once, keeps h in registers/VMEM, and writes hs once — HBM
traffic = 3 tensor passes instead of ~2*log2(T)+2. The time loop is
latency-bound on the VPU, but with (TB x DT) = (1 x 512) lanes busy per step
and the channel grid axis parallel across cores, utilization recovers while
traffic drops ~12x (measured via the dry-run cost model in EXPERIMENTS
§Perf cell D).

Backward is the standard reverse recurrence, also as a kernel:

    g_t   += dA_t+1 * g_t+1                    (suffix scan of cotangents)
    ddBx_t = g_t
    ddA_t  = g_t * h_t-1
    dh0    = dA_0 * g_0

wired through `jax.custom_vjp` so `ssm_scan` is a drop-in for the
associative-scan implementation (gradients verified against it in
tests/test_ssm_kernel.py).

Layout: state `s` rides the sublane axis and channels ride the 128-lane
axis: blocks are (TB, T, S, DT). Callers pass (B, T, d, s) arrays; the ops
wrapper transposes (documented — a fused production version would keep the
(s, d)-minor layout end-to-end).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fwd_kernel(dA_ref, dBx_ref, h0_ref, hs_ref, hT_ref):
    TB, T, S, DT = dA_ref.shape
    h0 = h0_ref[...]                                   # (TB, S, DT)

    def body(t, h):
        h = dA_ref[:, t] * h + dBx_ref[:, t]           # (TB, S, DT)
        hs_ref[:, t] = h
        return h

    h = jax.lax.fori_loop(0, T, body, h0)
    hT_ref[...] = h


def _bwd_kernel(dA_ref, hs_ref, h0_ref, g_ref, ghT_ref,
                ddA_ref, ddBx_ref, dh0_ref):
    TB, T, S, DT = dA_ref.shape
    # suffix recurrence over cotangents; gh carries d L / d h_t (total)
    gh0 = ghT_ref[...]                                 # cotangent of h_T

    def body(i, gh):
        t = T - 1 - i
        gh = gh + g_ref[:, t]
        h_prev = jnp.where(t == 0, h0_ref[...], hs_ref[:, jnp.maximum(t - 1, 0)])
        ddA_ref[:, t] = gh * h_prev
        ddBx_ref[:, t] = gh
        return dA_ref[:, t] * gh

    gh = jax.lax.fori_loop(0, T, body, jnp.zeros_like(gh0) + gh0)
    dh0_ref[...] = gh


def _round_up(v: int, k: int) -> int:
    return (v + k - 1) // k * k


def _grid_call(kernel, arrays, out_shapes, TB: int, DT: int, interpret: bool):
    """Common pallas_call: grid over (batch tiles, channel tiles); every
    array is (B, [T,] S, D)-shaped with D minor."""
    B = arrays[0].shape[0]
    D = arrays[0].shape[-1]
    grid = (B // TB, D // DT)

    def spec_for(a):
        if a.ndim == 4:
            return pl.BlockSpec((TB, a.shape[1], a.shape[2], DT),
                                lambda b, d: (b, 0, 0, d))
        return pl.BlockSpec((TB, a.shape[1], DT), lambda b, d: (b, 0, d))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec_for(a) for a in arrays],
        out_specs=[spec_for(o) for o in out_shapes],
        out_shape=out_shapes,
        interpret=interpret,
    )(*arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ssm_scan(dA, dBx, h0, TB: int, DT: int, interpret: bool):
    """dA, dBx: (B, T, S, D) f32; h0: (B, S, D) f32 ->
    (hs (B, T, S, D), hT (B, S, D))."""
    hs, hT = _ssm_fwd(dA, dBx, h0, TB, DT, interpret)
    return hs, hT


def _ssm_fwd(dA, dBx, h0, TB, DT, interpret):
    B, T, S, D = dA.shape
    out_shapes = [jax.ShapeDtypeStruct((B, T, S, D), dA.dtype),
                  jax.ShapeDtypeStruct((B, S, D), dA.dtype)]
    return _grid_call(_fwd_kernel, [dA, dBx, h0], out_shapes, TB, DT,
                      interpret)


def _fwd_rule(dA, dBx, h0, TB, DT, interpret):
    hs, hT = _ssm_fwd(dA, dBx, h0, TB, DT, interpret)
    return (hs, hT), (dA, hs, h0)


def _bwd_rule(TB, DT, interpret, res, cts):
    dA, hs, h0 = res
    g_hs, g_hT = cts
    B, T, S, D = dA.shape
    zero = jnp.zeros((B, S, D), dA.dtype)
    g_hs = jnp.zeros_like(dA) if isinstance(g_hs, jax.custom_derivatives.SymbolicZero) else g_hs  # pragma: no cover
    g_hT = zero if g_hT is None else g_hT
    out_shapes = [jax.ShapeDtypeStruct((B, T, S, D), dA.dtype),
                  jax.ShapeDtypeStruct((B, T, S, D), dA.dtype),
                  jax.ShapeDtypeStruct((B, S, D), dA.dtype)]
    ddA, ddBx, dh0 = _grid_call(_bwd_kernel, [dA, hs, h0, g_hs, g_hT],
                                out_shapes, TB, DT, interpret)
    return ddA, ddBx, dh0


ssm_scan.defvjp(_fwd_rule, _bwd_rule)


def ssm_scan_bt_ds(dA, dBx, h0) -> Tuple[jax.Array, jax.Array]:
    """Adapter for mamba's (B, T, d, s) layout -> kernel's (B, T, s, d).
    Pads channels to a lane multiple. Returns ((B, T, d, s), (B, d, s)).
    Interpret mode follows the platform (kernels.ops.default_interpret)."""
    from .ops import default_interpret
    B, T, d, s = dA.shape
    DT = 128 if d % 128 == 0 else _round_up(min(d, 128), 8)
    d_pad = _round_up(d, DT)

    def prep(x, time_major):
        x = jnp.moveaxis(x, -2, -1)  # (..., s, d)
        if d_pad != d:
            pad = [(0, 0)] * x.ndim
            pad[-1] = (0, d_pad - d)
            x = jnp.pad(x, pad)
        return x

    hs, hT = ssm_scan(prep(dA, True), prep(dBx, True), prep(h0, False),
                      1, DT, default_interpret())
    hs = jnp.moveaxis(hs, -1, -2)[..., :d, :]
    hT = jnp.moveaxis(hT, -1, -2)[..., :d, :]
    return hs, hT
