"""Pure-jnp oracle for the flash-decode kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def decode_attention_ref(q, k_cache, v_cache, lengths, window=None):
    """q: [B,H,hd] (single new token, already at position lengths-1);
    k_cache,v_cache: [B,KV,Smax,hd]; lengths: [B] valid tokens; window:
    if set, only positions >= lengths - window are read.
    Returns [B,H,hd]."""
    B, H, hd = q.shape
    KV, Smax = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    logits = jnp.einsum("bcgd,bcsd->bcgs", qg, k_cache,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
                            jnp.float32(hd))
    valid = jnp.arange(Smax)[None] < lengths[:, None]          # [B,Smax]
    if window is not None:
        valid &= jnp.arange(Smax)[None] >= lengths[:, None] - window
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bcgs,bcsd->bcgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, hd).astype(q.dtype)


def decode_attention_with_lse_ref(q, k_cache, v_cache, lengths):
    """Like :func:`decode_attention_ref` but also returns the logsumexp
    over the (local) sequence — the shard-combine statistic.

    q: [B,H,hd]; k_cache,v_cache: [B,KV,Smax,hd]; lengths: [B].
    Returns (out [B,H,hd], lse [B,H,1] fp32).
    """
    B, H, hd = q.shape
    KV, Smax = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    logits = jnp.einsum("bcgd,bcsd->bcgs", qg, k_cache,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
                            jnp.float32(hd))
    valid = jnp.arange(Smax)[None] < lengths[:, None]
    logits = jnp.where(valid[:, None, None], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(logits - m_safe)
    e = jnp.where(jnp.isfinite(logits), e, 0.0)
    l = jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("bcgs,bcsd->bcgd", e.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(l, 1e-30)
    lse = m_safe + jnp.log(jnp.maximum(l, 1e-30))
    lse = jnp.where(l > 0, lse, -jnp.inf)
    return (out.reshape(B, H, hd).astype(q.dtype),
            lse.reshape(B, H, 1).astype(jnp.float32))
