"""Pallas TPU flash-decode kernel.

One new token per sequence attends to a (possibly very long) KV cache.
Grid: (B, KV, num_kv_blocks) — kv blocks innermost/sequential with the
online-softmax state in VMEM scratch; the q block is the [G, hd] group of
query heads sharing one kv head (GQA), so the matmul shape is
[G, hd] x [hd, block_k] -> MXU-friendly after sublane padding.

With a ``window`` (a sliding-window layer over a full cache), positions
before ``length - window`` are masked and KV blocks wholly before them are
skipped too; ``window=None`` builds the kernel without either.

KV blocks entirely beyond ``length`` are skipped (``pl.when``) — this is the
structural analogue of not reading evicted pages on GPU serving stacks, and
what makes the 500k-context decode cell latency proportional to the *valid*
prefix, not the allocated capacity.

The sequence axis may be sharded over the `model` mesh axis; each shard then
runs this kernel over its chunk and the partial (acc, m, l) triples are
combined with a logsumexp reduction (see ops.flash_decode_sharded).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_ref, m_ref, l_ref,
                   *, sm_scale: float, block_k: int, seq_kv: int,
                   window=None):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    k_start = ki * block_k
    length = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = k_start < length
    if window is not None:
        live = jnp.logical_and(live, k_start + block_k > length - window)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                   # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)                   # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale     # [G, bk]
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        mask = jnp.logical_and(k_pos < length, k_pos < seq_kv)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos >= length - window)
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(logits - m_cur)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_cur
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        lsafe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / lsafe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(lsafe)).astype(jnp.float32)


def decode_attention_kernel(q, k_cache, v_cache, lengths, *,
                            block_k: int = 512, interpret: bool,
                            return_lse: bool = False, window=None):
    """q: [B,H,hd]; k_cache,v_cache: [B,KV,Smax,hd]; lengths: [B];
    window: if set, only positions >= lengths - window are read.
    Returns [B,H,hd] (and optionally the per-head logsumexp [B,H,1] for
    cross-shard combination)."""
    B, H, hd = q.shape
    KV, Smax = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = hd ** -0.5
    block_k = min(block_k, Smax)
    pad_k = (-Smax) % block_k
    if pad_k:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nk = (Smax + pad_k) // block_k
    qg = q.reshape(B, KV, G, hd)

    kernel = functools.partial(
        _decode_kernel, sm_scale=scale, block_k=block_k, seq_kv=Smax,
        window=window)

    out_shapes = [jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
                  jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32)]
    out_specs = [pl.BlockSpec((1, 1, G, hd), lambda b, c, j: (b, c, 0, 0)),
                 pl.BlockSpec((1, 1, G, 1), lambda b, c, j: (b, c, 0, 0))]

    res = pl.pallas_call(
        kernel,
        grid=(B, KV, nk),
        in_specs=[
            # whole [B] lengths vector in SMEM: Mosaic refuses a rank-1
            # block narrower than the 128-lane tiling
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, hd), lambda b, c, j: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, c, j: (b, c, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, c, j: (b, c, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(lengths, qg, k_cache, v_cache)
    out = res[0].reshape(B, H, hd)
    if return_lse:
        return out, res[1].reshape(B, H, 1)
    return out
