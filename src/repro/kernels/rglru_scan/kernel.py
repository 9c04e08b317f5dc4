"""Pallas TPU kernel for the RG-LRU diagonal linear recurrence.

h_t = a_t ⊙ h_{t-1} + b_t  over the time axis, vectorised across a channel
block.  Grid: (B, num_channel_blocks, num_time_blocks) — time innermost and
sequential, the running state h carried in VMEM scratch.  Within a time
block the recurrence is an unavoidable loop-carried dependence, but each
step is a [block_w]-wide VPU op, so the kernel is bandwidth-bound exactly
like the roofline predicts for a diagonal RNN: bytes(a)+bytes(b)+bytes(out)
per step, zero MXU work.  block_w is lane-aligned (multiples of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, hT_ref, h_ref, *,
                  block_t: int):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)       # [bt, bw]
    b = b_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + b[t]
        o_ref[0, t] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, step, h_ref[...])
    h_ref[...] = h

    @pl.when(ti == nt - 1)
    def _fin():
        hT_ref[0] = h_ref[...].astype(hT_ref.dtype)


def rglru_scan_kernel(a, b, h0, *, block_w: int = 512, block_t: int = 128,
                      interpret: bool):
    """a,b: [B,S,W]; h0: [B,W] -> (hs [B,S,W] fp32, hT [B,W] fp32)."""
    B, S, W = a.shape
    block_w = min(block_w, W)
    block_t = min(block_t, S)
    pad_w = (-W) % block_w
    pad_t = (-S) % block_t
    if pad_w or pad_t:
        a = jnp.pad(a, ((0, 0), (0, pad_t), (0, pad_w)))
        b = jnp.pad(b, ((0, 0), (0, pad_t), (0, pad_w)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))
    if pad_t:
        # padded time steps must be identity updates (a=1, b=0) so the
        # carried-out final state hT is the state at the last real step
        tmask = (jnp.arange(S + pad_t) < S)[None, :, None]
        a = jnp.where(tmask, a, 1.0)
    Sp, Wp = S + pad_t, W + pad_w
    nw, nt = Wp // block_w, Sp // block_t

    kernel = functools.partial(_rglru_kernel, block_t=block_t)
    hs, hT = pl.pallas_call(
        kernel,
        grid=(B, nw, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda bb, wi, ti: (bb, ti, wi)),
            pl.BlockSpec((1, block_t, block_w), lambda bb, wi, ti: (bb, ti, wi)),
            pl.BlockSpec((1, block_w), lambda bb, wi, ti: (bb, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda bb, wi, ti: (bb, ti, wi)),
            pl.BlockSpec((1, block_w), lambda bb, wi, ti: (bb, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, Wp), jnp.float32),
            jax.ShapeDtypeStruct((B, Wp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        interpret=interpret,
    )(a, b, h0)
    return hs[:, :S, :W], hT[:, :W]
