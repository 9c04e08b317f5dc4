"""Pallas TPU kernel for the RWKV-6 wkv recurrence.

Grid: (B, H, num_time_blocks) — time innermost/sequential with the [hd, hd]
state matrix carried in VMEM scratch (hd = 64 for the zoo; the state tile is
64x64 fp32 = 16 KiB, far under VMEM).  Within a time block the per-step
update is rank-1 (outer product k_t v_tᵀ) plus a diagonal decay — VPU work —
while the readout r_t·S is a [1,hd]x[hd,hd] matvec.  This is the
TPU-native adaptation of the CUDA wkv kernel: instead of one thread per
channel, the state lives in vector registers/VMEM and the time loop is the
only sequential dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
                 s_ref, *, block_t: int):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)      # [bt, hd]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)         # [hd]

    def step(t, s):
        kt = k[t][:, None]                   # [hd,1]
        vt = v[t][None, :]                   # [1,hd]
        kv = kt * vt                         # [hd,hd]
        s_att = s + u[:, None] * kv
        o_t = jnp.einsum("i,ij->j", r[t], s_att)
        o_ref[0, 0, t] = o_t.astype(o_ref.dtype)
        s = w[t][:, None] * s + kv
        return s

    s = jax.lax.fori_loop(0, block_t, step, s_ref[...])
    s_ref[...] = s

    @pl.when(ti == nt - 1)
    def _fin():
        sT_ref[0, 0] = s_ref[...].astype(sT_ref.dtype)


def rwkv6_scan_kernel(r, k, v, w, u, s0, *, block_t: int = 64,
                      interpret: bool):
    """r,k,v,w: [B,S,H,hd]; u: [H,hd]; s0: [B,H,hd,hd].
    Returns (o [B,S,H,hd] fp32, sT [B,H,hd,hd] fp32)."""
    B, S, H, hd = r.shape
    block_t = min(block_t, S)
    pad_t = (-S) % block_t
    # layout: [B,H,S,hd] so the time axis is blockable per (b,h)
    def to_bhsd(x):
        x = jnp.moveaxis(x, 2, 1)
        if pad_t:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        return x
    rr, kk, vv = to_bhsd(r), to_bhsd(k), to_bhsd(v)
    # padded decay must be 1.0 (identity update) so sT is unaffected
    ww = to_bhsd(w)
    if pad_t:
        tmask = (jnp.arange(S + pad_t) < S)[None, None, :, None]
        ww = jnp.where(tmask, ww, 1.0)
        kk = jnp.where(tmask, kk, 0.0)
    Sp = S + pad_t
    nt = Sp // block_t

    kernel = functools.partial(_rwkv_kernel, block_t=block_t)
    o, sT = pl.pallas_call(
        kernel,
        grid=(B, H, nt),
        in_specs=[
            pl.BlockSpec((1, 1, block_t, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, block_t, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, block_t, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, block_t, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, hd), lambda b, h, t: (h, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_t, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, ww, u, s0)
    o = jnp.moveaxis(o[:, :, :S], 1, 2)      # back to [B,S,H,hd]
    return o, sT
