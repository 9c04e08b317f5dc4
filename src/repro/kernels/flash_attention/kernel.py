"""Pallas TPU flash-attention forward kernel (causal / sliding window, GQA).

Grid: (B, H, num_q_blocks, num_kv_blocks) — kv innermost (sequential), with
the online-softmax running max / sum / accumulator carried in VMEM scratch
across kv steps.  Causally-masked-out kv blocks are skipped entirely
(``pl.when``), so the kernel does ~half the FLOPs of the dense reference for
causal attention.  Block shapes are (block_q, head_dim) / (block_k, head_dim)
— head_dim is MXU-lane aligned for the zoo (128/256) and block_q/block_k
default to 128 (sublane-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  sm_scale: float, block_q: int, block_k: int,
                  q_offset: int, seq_kv: int, causal: bool, window: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = qi * block_q
    k_start = ki * block_k
    q_off = q_offset  # absolute offset of q positions relative to kv

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Skip kv blocks fully above the causal diagonal or below the window band.
    q_max = q_start + block_q - 1 + q_off          # largest q position
    q_min = q_start + q_off                        # smallest q position
    run = k_start >= 0                             # trivially-true traced bool
    if causal:
        run = jnp.logical_and(run, k_start <= q_max)
    if window:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_min - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                  # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale    # [bq, bk]
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) + q_off
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_kv
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]                                  # [bq,1]
        l_prev = l_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)                      # [bq,1]
        p = jnp.exp(logits - m_cur)                          # [bq, bk]
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_cur
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           sm_scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool):
    """q: [B,H,Sq,hd]; k,v: [B,KV,Skv,hd] -> [B,H,Sq,hd]."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    scale = float(sm_scale) if sm_scale is not None else hd ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (Sq + pad_q) // block_q
    nk = (Skv + pad_k) // block_k
    grid = (B, H, nq, nk)

    kernel = functools.partial(
        _flash_kernel, sm_scale=scale, block_q=block_q, block_k=block_k,
        q_offset=Skv - Sq, seq_kv=Skv, causal=causal, window=window)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :, :Sq]
    return out
