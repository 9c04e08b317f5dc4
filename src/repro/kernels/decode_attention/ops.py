"""Public jit'd wrappers for flash-decode (model layout caches).

``flash_decode`` runs on a single device / replicated cache.
``flash_decode_sharded`` shard_maps over a mesh axis holding KV-sequence
chunks and combines per-shard partial attention with a logsumexp reduction —
the TPU-native replacement for paged attention at 32k-500k contexts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.decode_attention.kernel import decode_attention_kernel
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                decode_attention_with_lse_ref)

def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("impl", "block_k", "window"))
def flash_decode(q, k_cache, v_cache, lengths, *, impl: str = "auto",
                 block_k: int = 512, window=None):
    """q: [B,1,H,hd]; k_cache,v_cache: [B,Smax,KV,hd]; lengths: [B].
    ``window``: if set, the token at position lengths-1 reads only the
    positions at or after lengths-window (a sliding-window layer).
    Returns [B,1,H,hd]."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    q3 = q[:, 0]                                   # [B,H,hd]
    kc = jnp.swapaxes(k_cache, 1, 2)               # [B,KV,Smax,hd]
    vc = jnp.swapaxes(v_cache, 1, 2)
    if impl == "reference":
        out = decode_attention_ref(q3, kc, vc, lengths, window=window)
    else:
        out = decode_attention_kernel(
            q3, kc, vc, lengths, block_k=block_k,
            interpret=(impl == "interpret"), window=window)
    return out[:, None]


def gather_kv_blocks(pool, tables):
    """Dense cache view of a paged KV pool.

    pool: [NB, bs, ...] fixed-size blocks; tables: int32 [B, nb] per-
    sequence block tables.  Returns [B, nb*bs, ...] — sequence ``b``'s
    tokens contiguous at positions ``0..len_b-1`` (table order).
    """
    g = jnp.take(pool, tables.reshape(-1), axis=0)     # [B*nb, bs, ...]
    B, nb = tables.shape
    return g.reshape((B, nb * pool.shape[1]) + pool.shape[2:])


@functools.partial(jax.jit, static_argnames=("impl", "block_k"))
def flash_decode_paged(q, k_pool, v_pool, tables, lengths, *,
                       impl: str = "auto", block_k: int = 512):
    """Flash-decode against paged KV pools via block-table gather.

    q: [B,1,H,hd]; k_pool,v_pool: [NB,bs,KV,hd]; tables: int32 [B,nb];
    lengths: [B] valid tokens per sequence.  Returns [B,1,H,hd], bitwise
    equal to ``flash_decode`` over the equivalent dense [B,nb*bs] cache.
    """
    kc = gather_kv_blocks(k_pool, tables)
    vc = gather_kv_blocks(v_pool, tables)
    return flash_decode(q, kc, vc, lengths, impl=impl, block_k=block_k)


def flash_decode_sharded(q, k_cache, v_cache, lengths, *, mesh, seq_axis: str,
                         dp_axes, impl: str = "auto", block_k: int = 512):
    """Flash-decode with the cache sequence axis sharded over ``seq_axis``.

    Each shard computes partial attention over its chunk plus the local
    logsumexp; partials are combined exactly:
        out = Σ_s out_s · softmax_weight_s,   w_s = exp(lse_s - lse_max)·l_s
    Collective cost: one psum of [B,H,hd] + [B,H,1] over seq_axis (vs the
    naive all-gather of the full [B,H,S] logits row).
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    B, _, H, hd = q.shape
    dp = dp_axes if isinstance(dp_axes, tuple) else (dp_axes,)
    dp = dp if dp else None   # B=1 cells: batch replicated

    def local(q_, kc_, vc_, lengths_):
        idx = jax.lax.axis_index(seq_axis)
        chunk = kc_.shape[1]
        # local valid length within this shard's chunk
        loc_len = jnp.clip(lengths_ - idx * chunk, 0, chunk)
        q3 = q_[:, 0]
        kc = jnp.swapaxes(kc_, 1, 2)
        vc = jnp.swapaxes(vc_, 1, 2)
        if impl == "reference":
            # pure-jnp local pass: what the dry-run/roofline analyses see
            # (the pallas path is the TPU-native implementation)
            out, lse = decode_attention_with_lse_ref(q3, kc, vc, loc_len)
        else:
            out, lse = decode_attention_kernel(
                q3, kc, vc, loc_len, block_k=min(block_k, chunk),
                interpret=(impl != "pallas"), return_lse=True)
            # lse of an empty chunk is 0 from the kernel init path; mask it
            empty = (loc_len == 0)[:, None, None]
            lse = jnp.where(empty, -jnp.inf, lse)
        lse_max = jax.lax.pmax(lse, seq_axis)
        wgt = jnp.exp(lse - lse_max)
        wgt = jnp.where(jnp.isfinite(wgt), wgt, 0.0)
        num = jax.lax.psum(out.astype(jnp.float32) * wgt, seq_axis)
        den = jax.lax.psum(wgt, seq_axis)
        return (num / jnp.maximum(den, 1e-30)).astype(q_.dtype)[:, None]

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, seq_axis, None, None),
                  P(dp, seq_axis, None, None), P(dp)),
        out_specs=P(dp, None, None, None), check_vma=False,
    )(q, k_cache, v_cache, lengths)


def write_kv_sharded(cache_k, cache_v, k_new, v_new, start, *, mesh,
                     seq_axis: str, dp_axes):
    """Write single-token k/v into a cache whose sequence axis is sharded.

    The naive scatter forces XLA to all-gather the whole cache (observed:
    ~80 GB of collective traffic per decode step on a 35B/32k cell).  Under
    shard_map the write lands entirely in the shard owning position
    ``start``; every other shard is a masked no-op — zero collectives.

    cache_k/v: [B, Smax, KV, hd] (Smax sharded over seq_axis);
    k_new/v_new: [B, 1, KV, hd]; start: [B] global write positions.
    """
    dp = tuple(dp_axes) if isinstance(dp_axes, (tuple, list)) else (dp_axes,)
    bspec = dp if dp else None

    def local(ck, cv, kn, vn, st):
        idx = jax.lax.axis_index(seq_axis)
        chunk = ck.shape[1]
        loc = st - idx * chunk                      # [B] local position
        ok = (loc >= 0) & (loc < chunk)
        locc = jnp.clip(loc, 0, chunk - 1)
        b = jnp.arange(ck.shape[0])
        cur_k = ck[b, locc]
        cur_v = cv[b, locc]
        m = ok[:, None, None]
        new_k = jnp.where(m, kn[:, 0], cur_k)
        new_v = jnp.where(m, vn[:, 0], cur_v)
        return ck.at[b, locc].set(new_k), cv.at[b, locc].set(new_v)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, seq_axis, None, None),
                  P(bspec, seq_axis, None, None),
                  P(bspec, None, None, None),
                  P(bspec, None, None, None), P(bspec)),
        out_specs=(P(bspec, seq_axis, None, None),
                   P(bspec, seq_axis, None, None)),
        check_vma=False,
    )(cache_k, cache_v, k_new, v_new, start)
