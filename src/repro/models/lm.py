"""Generic decoder-only language model assembled from a block pattern.

The model is ``cfg.lead`` (unrolled; dense-MLP layers of an MoE model), then
``num_periods`` repetitions of ``cfg.period`` (scanned, with stacked params —
keeps HLO small for 64-layer models), then ``cfg.tail`` (unrolled).  Supports
three modes:

  * train:   full sequence, no cache, returns hidden states (+ aux loss)
  * prefill: full (right-padded) sequence, writes decode caches, returns the
             hidden state of the *last valid* token per sequence
  * decode:  single-token step against the cache

Logits / loss are computed by the callers (:func:`logits_last`,
:func:`ce_loss_chunked`) so that the [*, vocab] tensor is never materialised
for a full 4k sequence at once.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import base as cfgs
from repro.models import blocks, shardctx
from repro.models.common import dtype_of, embed_init, norm_init, apply_norm, softcap


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: cfgs.ModelConfig, key, dtype=None) -> Dict[str, Any]:
    dtype = dtype or dtype_of(cfg.dtype)
    keys = jax.random.split(key, 6)
    params: Dict[str, Any] = {
        "embed": {"w": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype)},
        "final_norm": norm_init(cfg.d_model, dtype, cfg.use_layernorm),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": embed_init(keys[1], cfg.d_model,
                                             cfg.vocab_size, dtype)}
    if cfg.learned_pos_embed:
        params["pos_embed"] = {"w": embed_init(keys[2], cfg.max_pos_embed,
                                               cfg.d_model, dtype)}
    # stacked period params
    period_keys = jax.random.split(keys[3], len(cfg.period))
    periods = {}
    for i, blk in enumerate(cfg.period):
        ks = jax.random.split(period_keys[i], cfg.num_periods)
        periods[f"b{i}"] = jax.vmap(
            lambda k, blk=blk: blocks.block_init(blk, k, cfg, dtype))(ks)
    params["periods"] = periods
    # tail params (unrolled)
    if cfg.tail:
        tail_keys = jax.random.split(keys[4], len(cfg.tail))
        params["tail"] = {
            f"t{i}": blocks.block_init(blk, tail_keys[i], cfg, dtype)
            for i, blk in enumerate(cfg.tail)}
    if cfg.lead:
        lead_keys = jax.random.split(keys[5], len(cfg.lead))
        params["lead"] = {
            f"l{i}": blocks.block_init(blk, lead_keys[i], cfg, dtype,
                                       dense=True)
            for i, blk in enumerate(cfg.lead)}
    return params


def init_cache(cfg: cfgs.ModelConfig, batch: int, smax: int,
               dtype=None, paged: bool = False) -> Dict[str, Any]:
    """``paged``: the paged pool's template, a full [batch, smax] cache
    for every attention layer (windowed ones too, not a ring)."""
    dtype = dtype or dtype_of(cfg.dtype)
    one = lambda blk: blocks.block_cache_init(blk, cfg, batch, smax, dtype,
                                              paged=paged)
    periods = {}
    for i, blk in enumerate(cfg.period):
        c = one(blk)
        periods[f"b{i}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_periods,) + x.shape), c)
    cache: Dict[str, Any] = {"periods": periods,
                             "len": jnp.zeros((batch,), jnp.int32)}
    if cfg.tail:
        cache["tail"] = {f"t{i}": one(blk) for i, blk in enumerate(cfg.tail)}
    if cfg.lead:
        cache["lead"] = {f"l{i}": one(blk) for i, blk in enumerate(cfg.lead)}
    return cache


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _embed(cfg, params, batch, mode):
    tokens = batch["tokens"]
    x = params["embed"]["w"][tokens]
    if cfg.scale_embedding:
        x = x * jnp.asarray(jnp.sqrt(jnp.float32(cfg.d_model)), x.dtype)
    if cfg.frontend == "patches" and mode != "decode":
        # VLM: precomputed patch embeddings prepended to the token stream.
        patches = batch["patches"].astype(x.dtype)     # [B, P, D]
        x = jnp.concatenate([patches, x], axis=1)
    if cfg.learned_pos_embed:
        pos = batch["positions"]
        if pos.ndim == 3:
            pos = pos[..., 0]
        x = x + params["pos_embed"]["w"][pos]
    return x


def _default_positions(cfg, batch, x, mode, lengths):
    if "positions" in batch and batch["positions"] is not None:
        return batch["positions"]
    B, S = x.shape[0], x.shape[1]
    if mode == "decode":
        pos = (lengths - 1)[:, None]                   # [B,1]
    else:
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[..., None], pos.shape + (3,))
    return pos


def apply(cfg: cfgs.ModelConfig, params, batch, *, mode: str,
          cache=None, mesh_axes=None, remat: bool = True,
          paged: bool = False, pages: Optional[blocks.Pages] = None):
    """Run the backbone.  Returns dict with:
       hidden [B,S,D] (train) or last_hidden [B,D] (prefill) or
       hidden [B,1,D] (decode); new cache (prefill/decode); aux loss.
       ``paged``: the decode cache is a dense view of a paged pool
       (``init_cache(..., paged=True)``), windowed layers masking their
       window over it.
       ``pages`` (decode): the cache is the paged pool itself (its groups,
       plus ``"len"``), which each attention layer writes and reads in
       place (``blocks.Pages``); the scanned periods' pool rides the scan's
       carry, flattened to [P * NB, ...], and comes back whole.
    """
    assert mode in ("train", "prefill", "decode")
    if mesh_axes is None and shardctx.enabled():
        # launcher context: explicit expert parallelism for MoE layers
        m, dp, tp = shardctx.mesh_info()
        mesh_axes = (m, dp, tp)
    x = _embed(cfg, params, batch, mode)
    x = shardctx.constrain(x, "dp", "sp" if mode == "train" else None, None)
    B, S = x.shape[0], x.shape[1]
    lengths = None
    valid = None
    if mode in ("prefill", "decode"):
        if mode == "prefill":
            lengths = batch.get("lengths")
            if lengths is None:
                lengths = jnp.full((B,), S, jnp.int32)
            valid = jnp.arange(S, dtype=jnp.int32)[None] < lengths[:, None]
        else:
            lengths = cache["len"] + S                 # S new tokens
    positions = _default_positions(cfg, batch, x, mode, lengths)
    smax = 0 if pages is not None else cache_capacity(cache, cfg)
    ctx = blocks.Ctx(cfg=cfg, mode=mode, positions=positions, lengths=lengths,
                     valid=valid, smax=smax, mesh_axes=mesh_axes, paged=paged,
                     pages=pages)

    lead_aux, lead_caches = [], {}
    for i, blk in enumerate(cfg.lead):
        c = None if mode == "train" else cache["lead"][f"l{i}"]
        x, lead_caches[f"l{i}"], a = blocks.block_apply(
            blk, params["lead"][f"l{i}"], x, ctx.replace(cache=c))
        lead_aux.append(a)

    def run_period(h, p_params, p_cache, pctx):
        new_caches = {}
        aux = jnp.float32(0.0)
        for i, blk in enumerate(cfg.period):
            c = None if p_cache is None else p_cache[f"b{i}"]
            h, nc, a = blocks.block_apply(blk, p_params[f"b{i}"], h,
                                          pctx.replace(cache=c))
            new_caches[f"b{i}"] = nc
            aux = aux + a
        # pin the layer-to-layer carry: batch on dp; with sequence
        # parallelism on, activations are also sharded over `model` on S
        h = shardctx.constrain(h, "dp", "sp", None)
        return h, new_caches, aux

    def period_body(carry, xs):
        p_params, p_cache = xs
        h, new_caches, aux = run_period(carry, p_params, p_cache, ctx)
        return h, (new_caches, aux)

    def pool_body(carry, xs):
        # period i's layers address their blocks in the flattened pool
        h, pool = carry
        p_params, i = xs
        h, pool, aux = run_period(h, p_params, pool,
                                  ctx.replace(pages=pages.at(i * nb_pool)))
        return (h, pool), aux

    if mode == "train":
        body = lambda h, p: period_body(h, (p, None))
        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        x, (_, auxs) = jax.lax.scan(body, x, params["periods"])
        new_cache = None
    elif pages is not None:
        periods = cache["periods"]
        nb_pool = jax.tree.leaves(periods)[0].shape[1]
        flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), periods)
        (x, flat), auxs = jax.lax.scan(
            pool_body, (x, flat),
            (params["periods"], jnp.arange(cfg.num_periods, dtype=jnp.int32)))
        new_cache = dict(cache)
        new_cache["periods"] = jax.tree.map(
            lambda a, f: f.reshape(a.shape), periods, flat)
        if cfg.lead:
            new_cache["lead"] = lead_caches
    else:
        body = period_body
        x, (new_period_caches, auxs) = jax.lax.scan(
            body, x, (params["periods"], cache["periods"]))
        new_cache = dict(cache)
        new_cache["periods"] = new_period_caches
        if cfg.lead:
            new_cache["lead"] = lead_caches
    aux = jnp.sum(auxs)
    for a in lead_aux:
        aux = aux + a

    if cfg.tail:
        if new_cache is not None:
            new_cache["tail"] = dict(cache["tail"])
        for i, blk in enumerate(cfg.tail):
            c = None if new_cache is None else cache["tail"][f"t{i}"]
            x, nc, a = blocks.block_apply(blk, params["tail"][f"t{i}"], x,
                                          ctx.replace(cache=c))
            aux = aux + a
            if new_cache is not None:
                new_cache["tail"][f"t{i}"] = nc

    x = apply_norm(params["final_norm"], x, cfg.norm_eps)

    out = {"aux": aux}
    if mode == "train":
        out["hidden"] = x
    elif mode == "prefill":
        bidx = jnp.arange(B)
        out["last_hidden"] = x[bidx, jnp.clip(lengths - 1, 0, S - 1)]
        new_cache["len"] = lengths
        out["cache"] = new_cache
    else:
        out["hidden"] = x
        new_cache["len"] = lengths
        out["cache"] = new_cache
    return out


def cache_capacity(cache, cfg: cfgs.ModelConfig) -> int:
    """Positions a full (global-attention) layer's cache holds, read off a
    global layer so a windowed layer's ring is never taken for it; a model
    without one gives its first cache's length."""
    if cache is None:
        return 0
    for group, kinds, tag, axis in (("lead", cfg.lead, "l", 1),
                                    ("periods", cfg.period, "b", 2),
                                    ("tail", cfg.tail, "t", 1)):
        for i, blk in enumerate(kinds):
            if blk == cfgs.ATTN:
                return cache[group][f"{tag}{i}"]["k"].shape[axis]
    for k in cache.get("periods", {}).values():
        if "k" in k:
            return k["k"].shape[2]  # [P, B, Smax, KV, hd]
    for k in list(cache.get("tail", {}).values()) + list(
            cache.get("lead", {}).values()):
        if "k" in k:
            return k["k"].shape[1]
    return 0


# ---------------------------------------------------------------------------
# logits & loss
# ---------------------------------------------------------------------------


def unembed_w(cfg, params):
    if cfg.tie_embeddings:
        return params["embed"]["w"].T      # [D, V]
    return params["lm_head"]["w"]


def logits_of(cfg, params, hidden):
    """hidden [..., D] -> logits [..., V] (fp32)."""
    w = unembed_w(cfg, params)
    logits = jnp.einsum("...d,dv->...v", hidden, w,
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = softcap(logits, cfg.logit_softcap)
    return logits


def ce_loss_chunked(cfg, params, hidden, labels, loss_mask=None,
                    chunk: int = 512):
    """Causal LM loss without materialising [B,S,V].

    hidden: [B,S,D]; labels: [B,S] (already shifted by the caller: labels[t]
    is the target for hidden[t]).  Returns (mean_loss, token_count).
    """
    B, S, D = hidden.shape
    w = unembed_w(cfg, params)
    if loss_mask is None:
        loss_mask = jnp.ones((B, S), jnp.float32)
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        loss_mask = jnp.pad(loss_mask, ((0, 0), (0, pad)))
    nc = (S + pad) // chunk
    hs = jnp.moveaxis(hidden.reshape(B, nc, chunk, D), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
    ms = jnp.moveaxis(loss_mask.reshape(B, nc, chunk), 1, 0)

    def body(acc, xs):
        hc, lc, mc = xs
        logits = jnp.einsum("bcd,dv->bcv", hc, w,
                            preferred_element_type=jnp.float32)
        # vocab-sharded logits; never replicate the [B,chunk,V] tensor
        logits = shardctx.constrain(logits, "dp", None, "tp")
        if cfg.logit_softcap:
            logits = softcap(logits, cfg.logit_softcap)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (logz - tgt) * mc
        return (acc[0] + jnp.sum(nll), acc[1] + jnp.sum(mc)), None

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ls, ms))
    return total / jnp.maximum(count, 1.0), count
