"""Attention cores (pure jnp).

These are the *reference* sequence-mixing implementations used for training,
prefill, decode and the dry-run.  They are written to be:
  * memory-safe at 32k context (chunked over query blocks, online per-block
    softmax peak of [B, H, C, S_kv] instead of [B, H, S, S]);
  * GQA-native (keys/values never repeated to q heads — grouped einsum);
  * SPMD-friendly (batch on the `data` axis, q-heads on `model` for
    train/prefill; KV-sequence on `model` for decode).

Pallas TPU kernels in ``repro.kernels`` implement the same contracts and are
swapped in via ``attention_impl='pallas'`` on real TPUs.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import shardctx

NEG_INF = -1e30

# Single-token decode attention implementation. "dense" is the pure-jnp
# reference below; anything else routes through the kernels/decode_attention
# flash path ("auto" = Pallas on TPU, the jnp reference off-TPU).
# Trace-time state: the continuous-batching backend arms it around its
# jitted decode step, so the choice is baked into each compiled step fn.
_DECODE_IMPL = "dense"


@contextlib.contextmanager
def use_decode_impl(impl: str):
    """Route single-token :func:`decode_attention` calls traced inside this
    context through ``kernels/decode_attention`` (``impl`` in {"dense",
    "auto", "reference", "interpret", "pallas"})."""
    global _DECODE_IMPL
    prev, _DECODE_IMPL = _DECODE_IMPL, impl
    try:
        yield
    finally:
        _DECODE_IMPL = prev


def _grouped_logits(q, k):
    """q: [B,Sq,KV,G,hd], k: [B,Skv,KV,hd] -> [B,KV,G,Sq,Skv] (fp32)."""
    return jnp.einsum("bqcgd,bscd->bcgqs", q, k,
                      preferred_element_type=jnp.float32)


def _grouped_out(p, v, dtype):
    """p: [B,KV,G,Sq,Skv], v: [B,Skv,KV,hd] -> [B,Sq,KV,G,hd]."""
    return jnp.einsum("bcgqs,bscd->bqcgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(dtype)


def _attn_block(q, k, v, q_pos, kv_pos, *, causal: bool, window: int = 0,
                kv_valid=None):
    """One dense attention block.

    q: [B,Sq,H,hd]; k,v: [B,Skv,KV,hd]; q_pos: [Sq] or [B,Sq];
    kv_pos: [Skv] or [B,Skv]; kv_valid: optional bool [B,Skv].
    Returns [B,Sq,H,hd].
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    # pin the batch sharding through the GQA head-split (propagation can
    # otherwise drop it while it resolves the KV×G factorisation)
    qg = shardctx.constrain(qg, "dp", None, None, None, None)
    k = shardctx.constrain(k, "dp", None, None, None)
    v = shardctx.constrain(v, "dp", None, None, None)
    logits = _grouped_logits(qg, k) / jnp.sqrt(jnp.float32(hd))
    # mask construction ([b?, Sq, Skv], broadcastable to [B,KV,G,Sq,Skv])
    qp = q_pos if q_pos.ndim == 2 else q_pos[None]           # [B?,Sq]
    kp = kv_pos if kv_pos.ndim == 2 else kv_pos[None]        # [B?,Skv]
    mask = None
    if causal:
        mask = kp[:, None, :] <= qp[:, :, None]
    if window:
        w = kp[:, None, :] > qp[:, :, None] - window
        mask = w if mask is None else mask & w
    if kv_valid is not None:
        v_ = kv_valid[:, None, :]
        mask = v_ if mask is None else mask & v_
    if mask is not None:
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = _grouped_out(p, v, q.dtype)
    out = out.reshape(B, Sq, H, hd)
    return shardctx.constrain(out, "dp", None, "tp", None)


def causal_attention(q, k, v, *, q_offset=0, window: int = 0,
                     chunk: int = 1024, causal: bool = True):
    """Chunked (flash-style memory profile) attention over full sequences.

    q: [B,Sq,H,hd]; k,v: [B,Skv,KV,hd]. ``q_offset`` is the absolute position
    of q[0] relative to kv[0] (q_offset=Skv-Sq for incremental prefill).
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    kv_pos = jnp.arange(Skv, dtype=jnp.int32)
    if Sq <= chunk:
        q_pos = jnp.arange(Sq, dtype=jnp.int32) + q_offset
        return _attn_block(q, k, v, q_pos, kv_pos, causal=causal, window=window)
    # pad Sq to a chunk multiple, scan over query chunks
    pad = (-Sq) % chunk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (Sq + pad) // chunk
    q_chunks = jnp.moveaxis(qp.reshape(B, nc, chunk, H, hd), 1, 0)
    pos_chunks = (jnp.arange(nc * chunk, dtype=jnp.int32)
                  .reshape(nc, chunk) + q_offset)

    def one(args):
        qc, pc = args
        return _attn_block(qc, k, v, pc, kv_pos, causal=causal, window=window)

    out = jax.lax.map(one, (q_chunks, pos_chunks))
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq + pad, H, hd)
    return out[:, :Sq]


def local_attention(q, k, v, *, window: int):
    """Exact sliding-window causal attention (token t sees [t-window+1, t]).

    Implemented as chunked banded attention: query chunk i (chunk size =
    window) attends to kv chunks i-1 and i only.  Peak logits:
    [B, H, W, 2W] per chunk step.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    W = window
    if S <= W:
        return causal_attention(q, k, v, window=W, chunk=max(W, 256))
    pad = (-S) % W
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // W
    qc = jnp.moveaxis(qp.reshape(B, nc, W, H, hd), 1, 0)      # [nc,B,W,H,hd]
    kc = jnp.moveaxis(kp.reshape(B, nc, W, KV, hd), 1, 0)
    vc = jnp.moveaxis(vp.reshape(B, nc, W, KV, hd), 1, 0)
    # previous chunk (zeros for the first)
    kprev = jnp.concatenate([jnp.zeros_like(kc[:1]), kc[:-1]], axis=0)
    vprev = jnp.concatenate([jnp.zeros_like(vc[:1]), vc[:-1]], axis=0)
    idx = jnp.arange(nc, dtype=jnp.int32)

    def one(args):
        i, qi, ki, vi, kpi, vpi = args
        kv_k = jnp.concatenate([kpi, ki], axis=1)             # [B,2W,KV,hd]
        kv_v = jnp.concatenate([vpi, vi], axis=1)
        q_pos = i * W + jnp.arange(W, dtype=jnp.int32)
        kv_pos = (i - 1) * W + jnp.arange(2 * W, dtype=jnp.int32)
        # kv positions < 0 are the zero-padding of chunk -1
        valid = (kv_pos >= 0)[None]
        return _attn_block(qi, kv_k, kv_v, q_pos, kv_pos, causal=True,
                           window=W, kv_valid=valid)

    out = jax.lax.map(one, (idx, qc, kc, vc, kprev, vprev))
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sp, H, hd)
    return out[:, :S]


def decode_attention(q, k_cache, v_cache, lengths, window: int = 0):
    """Decode-step attention against a full KV cache.

    q: [B,T,H,hd] (T new tokens, already at positions lengths-T..lengths-1);
    k_cache,v_cache: [B,Smax,KV,hd] with the new tokens already written;
    lengths: [B] — number of valid tokens *including* the new ones;
    window: if set, the token at position t sees positions (t-window, t]
    only (a sliding-window layer over a full cache).

    Sharding contract: the Smax axis may be sharded over the `model` mesh
    axis; the softmax reduction then induces collectives, which the Pallas
    flash-decode kernel replaces with a logsumexp-combine on TPU.
    """
    B, T, H, hd = q.shape
    if T == 1 and _DECODE_IMPL != "dense":
        from repro.kernels.decode_attention import ops as dec_ops
        return dec_ops.flash_decode(q, k_cache, v_cache, lengths,
                                    impl=_DECODE_IMPL, window=window or None)
    Smax = k_cache.shape[1]
    kv_pos = jnp.arange(Smax, dtype=jnp.int32)[None]           # [1,Smax]
    q_pos = (lengths[:, None] - T) + jnp.arange(T, dtype=jnp.int32)[None]
    valid = kv_pos < lengths[:, None]                          # [B,Smax]
    return _attn_block(q, k_cache, v_cache, q_pos,
                       jnp.broadcast_to(kv_pos, (B, Smax)),
                       causal=True, window=window, kv_valid=valid)


def ring_decode_attention(q, k_ring, v_ring, pos, window: int):
    """Sliding-window decode against a ring buffer of the last W tokens.

    q: [B,1,H,hd]; k_ring,v_ring: [B,W,KV,hd]; pos: [B] absolute position of
    the new token (already written to slot pos % W).
    """
    B, T, H, hd = q.shape
    assert T == 1, "ring decode is single-token"
    W = window
    j = jnp.arange(W, dtype=jnp.int32)[None]                   # [1,W]
    p = pos[:, None]                                           # [B,1]
    slot_pos = p - jnp.mod(p - j, W)                           # [B,W]
    valid = slot_pos >= 0
    return _attn_block(q, k_ring, v_ring, p, slot_pos, causal=True,
                       window=W, kv_valid=valid)


def seq_sharded_decode_ready(cache_k) -> bool:
    """True when the shard context is armed and the cache's sequence axis
    divides the model axis (the sharded decode fast path applies)."""
    if not shardctx.enabled():
        return False
    mesh, _, tp = shardctx.mesh_info()
    return cache_k.shape[1] % mesh.shape[tp] == 0


def sharded_cache_decode(q, cache_k, cache_v, k_new, v_new, lengths):
    """Decode against a sequence-sharded KV cache: shard-local ring write +
    flash-decode with psum-of-partials (see kernels/decode_attention)."""
    from repro.kernels.decode_attention import ops as dec_ops
    mesh, dp, tp = shardctx.mesh_info()
    dp = shardctx.dp_for(q.shape[0])
    start = lengths - 1
    ck, cv = dec_ops.write_kv_sharded(cache_k, cache_v, k_new, v_new, start,
                                      mesh=mesh, seq_axis=tp, dp_axes=dp)
    out = dec_ops.flash_decode_sharded(q, ck, cv, lengths, mesh=mesh,
                                       seq_axis=tp, dp_axes=dp)
    return out, ck, cv


def write_kv(cache_k, cache_v, k_new, v_new, start):
    """Write k_new [B,T,KV,hd] into cache at per-batch offsets start [B]."""
    B, T = k_new.shape[:2]
    idx = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None]   # [B,T]
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    ck = cache_k.at[bidx, idx].set(k_new)
    cv = cache_v.at[bidx, idx].set(v_new)
    return ck, cv


def write_kv_ring(cache_k, cache_v, k_new, v_new, pos, window: int):
    """Write single-token k/v [B,1,KV,hd] at ring slot pos % window."""
    B = k_new.shape[0]
    slot = jnp.mod(pos, window)
    bidx = jnp.arange(B, dtype=jnp.int32)
    ck = cache_k.at[bidx, slot].set(k_new[:, 0])
    cv = cache_v.at[bidx, slot].set(v_new[:, 0])
    return ck, cv
