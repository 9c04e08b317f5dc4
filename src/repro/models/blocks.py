"""Transformer / recurrent block implementations.

Every block implements three entry points behind one interface:

    block_init(blk, key, cfg, dtype)            -> params
    block_cache_init(blk, cfg, batch, smax)     -> cache (decode state)
    block_apply(blk, params, x, ctx)            -> (y, new_cache, aux_loss)

``ctx.mode`` is one of "train" (no cache), "prefill" (full sequence, writes
cache), "decode" (single-token step against cache).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs import base as cfgs
from repro.models import attention as attn
from repro.models import shardctx
from repro.models.common import (apply_mlp, apply_norm, apply_rope, dense_init,
                                 group_norm_heads, linear, mlp_init, norm_init)


@dataclasses.dataclass
class Ctx:
    cfg: cfgs.ModelConfig
    mode: str                       # train | prefill | decode
    positions: Any                  # [B,S] int32 or [B,S,3] (M-RoPE)
    lengths: Optional[Any] = None   # [B] valid tokens incl. current step
    valid: Optional[Any] = None     # [B,S] bool — pad mask for prefill
    cache: Any = None               # this block's cache slice
    smax: int = 0                   # KV-cache capacity
    mesh_axes: Any = None           # (dp_axes, tp_axis) names or None
    paged: bool = False             # decode caches are full [B, Smax] views
                                    # of a paged pool for every attention
                                    # layer, windowed ones too (no rings)
    pages: Optional["Pages"] = None   # decode caches are the paged pool
                                      # itself, written and read in place

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class Pages:
    """A decode-mode chunk over a paged KV pool, in place (``lm.apply(...,
    pages=)``): each attention layer's cache is its pool leaf ``{"kv":
    [N, bs, 2 KV, hd]}`` (``inference/paged_kv.py``), into which the layer
    writes its chunk's keys and values, then attends through the tables.

    A scanned period's leaf is the periods' pool flattened to
    ``[P * NB, ...]``, and ``base`` the first block of the layer's own
    ``NB``; the tables, the write blocks and ``base`` index the leaf."""
    tables: Any      # int32 [B, nb]: each row's pool blocks, in order
    blk: Any         # int32 [B, C]: each new token's block; -1 = not written
    off: Any         # int32 [B, C]: its offset in the block
    kv_lens: Any     # int32 [B]: positions each row's queries read
    impl: str = "reference"          # attention.paged_attention's
    base: Any = 0                    # int32 scalar, added to block ids

    def at(self, base) -> "Pages":
        return dataclasses.replace(self, base=base)


# ===========================================================================
# Attention block (global or sliding-window), GQA + RoPE (+ qk-norm, M-RoPE)
# ===========================================================================


def attn_init(key, cfg: cfgs.ModelConfig, dtype):
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p = {
        "wq": dense_init(ks[0], d, cfg.q_dim, dtype),
        "wk": dense_init(ks[1], d, cfg.kv_dim, dtype),
        "wv": dense_init(ks[2], d, cfg.kv_dim, dtype),
        "wo": dense_init(ks[3], cfg.q_dim, d, dtype),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dtype)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((cfg.head_dim,), dtype)}
        p["k_norm"] = {"scale": jnp.ones((cfg.head_dim,), dtype)}
    if cfg.attn_out_gate:
        p["wgate"] = dense_init(ks[4], d, cfg.q_dim, dtype)
    return p


def _qkv(params, x, cfg: cfgs.ModelConfig, positions, rope: bool = True):
    B, S, _ = x.shape
    q = linear(x, params["wq"], params.get("bq")).reshape(
        B, S, cfg.num_heads, cfg.head_dim)
    k = linear(x, params["wk"], params.get("bk")).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim)
    v = linear(x, params["wv"], params.get("bv")).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = apply_norm(params["q_norm"], q, cfg.norm_eps)
        k = apply_norm(params["k_norm"], k, cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    q = shardctx.constrain(q, "dp", None, "tp", None)
    k = shardctx.constrain(k, "dp", None, None, None)
    v = shardctx.constrain(v, "dp", None, None, None)
    return q, k, v


def _padded_heads(cfg: cfgs.ModelConfig) -> int:
    """Zero-pad q heads up to a multiple of the TP axis when H doesn't
    divide it (qwen2-vl: 28 heads on a 16-way axis -> 32).  Padding is
    activation-level: the extra heads' wo rows are zero, so the output is
    exact; the win is 16-way head sharding instead of fully replicated
    attention (observed 16x redundant attention traffic otherwise)."""
    from repro.models import shardctx as _sc
    if not _sc.enabled():
        return cfg.num_heads
    mesh, _, tp = _sc.mesh_info()
    t = mesh.shape[tp]
    H = cfg.num_heads
    if H % t == 0:
        return H
    Hp = ((H + t - 1) // t) * t
    if Hp % cfg.num_kv_heads != 0:     # GQA grouping must survive
        return H
    return Hp


def attn_apply(params, x, ctx: Ctx, *, window: int):
    cfg = ctx.cfg
    B, S, _ = x.shape
    rope = cfg.use_rope and (bool(window) or not cfg.rope_local_only)
    q, k, v = _qkv(params, x, cfg, ctx.positions, rope)
    Hp = _padded_heads(cfg)
    if Hp != cfg.num_heads:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Hp - cfg.num_heads), (0, 0)))
        q = shardctx.constrain(q, "dp", None, "tp", None)
    new_cache = ctx.cache
    if ctx.mode == "decode" and ctx.pages is not None:
        pg = ctx.pages
        pool = ctx.cache["kv"]
        n = pool.shape[0]
        dst = jnp.where(pg.blk >= 0, pg.blk + pg.base, n)      # n: dropped
        pool = pool.at[dst, pg.off].set(
            interleave_kv(k, v).astype(pool.dtype), mode="drop")
        out = attn.paged_attention(q, pool, pg.tables + pg.base, pg.kv_lens,
                                   impl=pg.impl, window=window)
        new_cache = {"kv": pool}
    elif ctx.mode == "decode":
        if window and not ctx.paged:
            pos = ctx.lengths - 1                       # absolute position
            ck, cv = attn.write_kv_ring(ctx.cache["k"], ctx.cache["v"],
                                        k, v, pos, window)
            out = attn.ring_decode_attention(q, ck, cv, pos, window)
        elif (S == 1 and not window
              and attn.seq_sharded_decode_ready(ctx.cache["k"])):
            # seq-sharded cache: shard-local write + logsumexp-combined
            # partial attention (kills the scatter-induced cache all-gather)
            out, ck, cv = attn.sharded_cache_decode(
                q, ctx.cache["k"], ctx.cache["v"], k, v, ctx.lengths)
        else:
            start = ctx.lengths - S
            ck, cv = attn.write_kv(ctx.cache["k"], ctx.cache["v"], k, v, start)
            out = attn.decode_attention(q, ck, cv, ctx.lengths, window=window)
        new_cache = {"k": ck, "v": cv}
    else:
        if window:
            out = attn.local_attention(q, k, v, window=window)
        else:
            out = attn.causal_attention(q, k, v)
        if ctx.mode == "prefill":
            if window:
                # Ring buffer = the last W *valid* tokens; slot j holds the
                # largest valid position congruent to j (mod W).  Gather
                # formulation keeps the scatter deterministic under padding.
                W = window
                lens = (ctx.lengths if ctx.lengths is not None
                        else jnp.full((B,), S, jnp.int32))
                q_last = lens[:, None] - 1                       # [B,1]
                j = jnp.arange(W, dtype=jnp.int32)[None]         # [1,W]
                src = q_last - jnp.mod(q_last - j, W)            # [B,W]
                ok = (src >= 0)[..., None, None]
                srcc = jnp.clip(src, 0, S - 1)
                bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
                ring_k = jnp.where(ok, k[bidx, srcc], 0).astype(k.dtype)
                ring_v = jnp.where(ok, v[bidx, srcc], 0).astype(v.dtype)
                new_cache = {"k": ring_k, "v": ring_v}
            else:
                ck = jnp.zeros((B, ctx.smax) + k.shape[2:], k.dtype)
                cv = jnp.zeros((B, ctx.smax) + v.shape[2:], v.dtype)
                ck = jax.lax.dynamic_update_slice_in_dim(ck, k, 0, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cv, v, 0, axis=1)
                new_cache = {"k": ck, "v": cv}
    out = out.reshape(B, S, Hp * cfg.head_dim)
    if cfg.attn_out_gate:
        gate = jax.nn.sigmoid(linear(x, params["wgate"]).astype(jnp.float32))
        if Hp != cfg.num_heads:             # padded heads are zeroed by wo
            gate = jnp.pad(gate, ((0, 0), (0, 0), (0, out.shape[-1]
                                                   - gate.shape[-1])))
        out = out * gate.astype(out.dtype)
    wo = params["wo"]
    if Hp != cfg.num_heads:                 # zero rows for padded heads
        wo = jnp.pad(wo, ((0, (Hp - cfg.num_heads) * cfg.head_dim), (0, 0)))
    return linear(out, wo, params.get("bo")), new_cache


def interleave_kv(k, v):
    """k, v: [..., KV, hd] -> [..., 2 KV, hd], key head h at 2h and value
    head h at 2h + 1: the paged pool's head layout."""
    kv = jnp.stack([k, v], axis=-2)                        # [..., KV, 2, hd]
    return kv.reshape(k.shape[:-2] + (2 * k.shape[-2], k.shape[-1]))


def attn_cache_init(cfg: cfgs.ModelConfig, batch: int, smax: int, *,
                    window: int, dtype):
    cap = window if window else smax
    shape = (batch, cap, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ===========================================================================
# MoE MLP: token-choice top-k.  Train mode keeps a per-expert capacity (EP
# over the `model` mesh axis); inference is dropless, each (token, expert)
# pair sorted by expert through one grouped matmul.
# ===========================================================================

# The dropless expert layer's grouped matmuls are jax.lax.ragged_dot: on a
# TPU v5e, at a 256-token prefill step's shapes (2,048 pairs over 128
# experts of 2048 x 1024), XLA's ragged dot took 5.4 ms for one layer's
# experts against 18.0 ms for megablox's Pallas gmm.


def moe_init(key, cfg: cfgs.ModelConfig, dtype):
    m = cfg.moe
    ks = jax.random.split(key, 5)
    E = m.padded_num_experts
    d, f = cfg.d_model, m.expert_d_ff

    def stack(k, din, dout):
        # one key per expert, as drawn one by one (vmap of the PRNG is the
        # per-key draw), without E separate ops in the init program
        return jax.vmap(lambda kk: dense_init(kk, din, dout, dtype))(
            jax.random.split(k, E))

    p = {
        "router": {"w": dense_init(ks[0], d, E, jnp.float32)},
        "experts": {
            "wi": stack(ks[1], d, f),
            "wg": stack(ks[2], d, f),
            "wo": stack(ks[3], f, d),
        },
    }
    if m.selection_bias:
        # trained models learn it (load balancing); zeros would leave the
        # selection-only path unexercised, so it is drawn from the seed
        p["router"]["bias"] = 0.1 * jax.random.normal(
            jax.random.fold_in(ks[0], 1), (E,), jnp.float32)
    if m.num_shared_experts:
        p["shared"] = mlp_init(ks[4], d, m.shared_d_ff, dtype, cfg.use_bias)
    return p


def _select(router, x2d, m: cfgs.MoEConfig):
    """x2d: [T,D] -> (scores [T,E], top-k expert ids [T,k], their gates
    [T,k]), fp32.  Scores are the softmax over experts or each expert's
    sigmoid; the selection bias, if any, enters the choice of the top k
    only; gates are the chosen scores over their sum, times
    ``route_scale``.  The router's product runs at full float32
    precision (the TPU's default would round its inputs to bfloat16), so
    near-tied experts are chosen as a float32 model chooses them."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), router["w"],
                        precision=jax.lax.Precision.HIGHEST)
    E = m.padded_num_experts
    if E > m.num_experts:                     # mask padding experts
        pad_mask = jnp.arange(E) < m.num_experts
        logits = jnp.where(pad_mask[None], logits, -1e30)
    if m.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if m.selection_bias:
        _, topi = jax.lax.top_k(scores + router["bias"][None],
                                m.num_experts_per_tok)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    else:
        topv, topi = jax.lax.top_k(scores, m.num_experts_per_tok)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    if m.route_scale != 1.0:
        topv = topv * m.route_scale
    return scores, topi, topv


def _route(router, x2d, m: cfgs.MoEConfig):
    """x2d: [T,D] -> top-k gates scattered to [T,E] (fp32), aux loss."""
    scores, topi, topv = _select(router, x2d, m)
    gates = jnp.zeros_like(scores)
    gates = gates.at[jnp.arange(x2d.shape[0])[:, None], topi].set(topv)
    # Switch-style load balance aux loss (over true experts only)
    frac_tokens = jnp.mean((gates > 0).astype(jnp.float32), axis=0)
    frac_probs = jnp.mean(scores, axis=0)
    aux = m.num_experts * jnp.sum(frac_tokens * frac_probs)
    return gates, aux * m.router_aux_loss


def _moe_local(x2d, gates_loc, wi, wg, wo, capacity: int):
    """Run tokens through a local slice of experts.

    x2d: [T,D]; gates_loc: [T,E_loc]; wi/wg: [E_loc,D,F]; wo: [E_loc,F,D].
    Per expert, up to ``capacity`` tokens are selected by gate priority
    (overflow dropped, matching capacity-factor semantics).
    Returns [T,D] contribution of the local experts.
    """
    T, D = x2d.shape
    E_loc = wi.shape[0]
    # top-capacity tokens per expert, priority = gate weight (stable ties)
    prio = jnp.swapaxes(gates_loc, 0, 1)                   # [E_loc, T]
    _, idx = jax.lax.top_k(prio, min(capacity, T))         # [E_loc, C]
    tok = x2d[idx]                                         # [E_loc, C, D]
    g = jnp.take_along_axis(jnp.swapaxes(gates_loc, 0, 1), idx, axis=1)
    valid = g > 0                                          # [E_loc, C]
    h = jnp.einsum("ecd,edf->ecf", tok, wi)
    gate = jnp.einsum("ecd,edf->ecf", tok, wg)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * h
    out = jnp.einsum("ecf,efd->ecd", h, wo)                # [E_loc, C, D]
    out = out * (g * valid)[..., None].astype(out.dtype)
    y = jnp.zeros((T, D), out.dtype)
    y = y.at[idx.reshape(-1)].add(out.reshape(-1, D), mode="drop")
    return y


def _capacity(m: cfgs.MoEConfig, t_loc: int) -> int:
    """Per-expert token capacity in train mode, from the capacity factor
    (overflow dropped by gate priority).  Inference is dropless and has
    none (``_moe_grouped``)."""
    import math
    return min(t_loc, max(1, math.ceil(
        m.num_experts_per_tok * t_loc * m.capacity_factor / m.num_experts)))


def _grouped(lhs, rhs, group_sizes):
    """Row block g of lhs (rows sorted by group) times rhs[g], in lhs's
    dtype, accumulated in float32."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


def _moe_grouped(x2d, topi, topv, experts, first=0):
    """Dropless: every (token, expert) pair, sorted by expert, through one
    grouped matmul per projection, then unsorted and summed with the gates.

    x2d: [T,D]; topi/topv: [T,k] expert ids and gates; experts: stacked
    wi/wg [E,D,F], wo [E,F,D], the experts ``first`` .. ``first + E - 1``
    (a shard's slice); pairs routed elsewhere sort last, outside every
    group, and add nothing.  Returns [T,D] in x2d's dtype.  The work
    scales with k per token, not with the number of experts."""
    T, D = x2d.shape
    k = topi.shape[1]
    E = experts["wi"].shape[0]
    local = topi.reshape(-1) - first                       # [T*k]
    mine = (local >= 0) & (local < E)
    key = jnp.where(mine, local, E)
    order = jnp.argsort(key, stable=True)
    tok = order // k                                       # owning token
    sizes = jnp.bincount(key, length=E + 1)[:E].astype(jnp.int32)
    xs = x2d[tok]                                          # [T*k, D]
    h = _grouped(xs, experts["wi"], sizes)
    g = _grouped(xs, experts["wg"], sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    out = _grouped(h, experts["wo"], sizes)                # [T*k, D]
    w = topv.reshape(-1)[order]
    # rows past the groups are not computed: select, never multiply, them
    part = jnp.where(mine[order][:, None],
                     out.astype(jnp.float32) * w[:, None], 0.0)
    y = jnp.zeros((T, D), jnp.float32).at[tok].add(part)
    return y.astype(x2d.dtype)


def moe_apply(params, x, ctx: Ctx):
    cfg = ctx.cfg
    m = cfg.moe
    B, S, D = x.shape
    x2d = x.reshape(B * S, D)
    E = m.padded_num_experts
    mesh_axes = ctx.mesh_axes
    if mesh_axes is not None:
        # gate: tokens must divide the dp axes and experts the tp axis
        _mesh, _dp, _tp = mesh_axes
        _dp = _dp if isinstance(_dp, tuple) else (_dp,)
        dp_size = 1
        for a in _dp:
            dp_size *= _mesh.shape[a]
        if (B * S) % dp_size != 0 or E % _mesh.shape[_tp] != 0:
            mesh_axes = None
    if mesh_axes is None and ctx.mode != "train":
        with jax.named_scope("moe.route"):
            _, topi, topv = _select(params["router"], x2d, m)
        with jax.named_scope("moe.experts"):
            y2d = _moe_grouped(x2d, topi, topv, params["experts"])
        aux = jnp.float32(0.0)
    elif mesh_axes is not None:
        mesh, dp_axes, tp_axis = mesh_axes
        tp = mesh.shape[tp_axis]
        E_loc = E // tp
        P = jax.sharding.PartitionSpec
        if ctx.mode == "train":
            gates, aux = _route(params["router"], x2d, m)
            route = (gates,)
        else:                         # dropless: each shard its own pairs
            _, topi, topv = _select(params["router"], x2d, m)
            route, aux = (topi, topv), jnp.float32(0.0)

        def local_fn(xl, *rest):
            *rl, wi, wg, wo = rest
            off = jax.lax.axis_index(tp_axis) * E_loc
            if ctx.mode != "train":
                y = _moe_grouped(xl, *rl, {"wi": wi, "wg": wg, "wo": wo},
                                 first=off)
            else:
                g_slice = jax.lax.dynamic_slice_in_dim(rl[0], off, E_loc,
                                                       axis=1)
                cap = _capacity(m, xl.shape[0])
                y = _moe_local(xl, g_slice, wi, wg, wo, cap)
            return jax.lax.psum(y, tp_axis)

        dp = dp_axes if isinstance(dp_axes, tuple) else (dp_axes,)
        y2d = jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=((P(dp, None),) * (1 + len(route))
                      + (P(tp_axis, None, None),) * 3),
            out_specs=P(dp, None),
            check_vma=False,
        )(x2d, *route, params["experts"]["wi"], params["experts"]["wg"],
          params["experts"]["wo"])
    else:
        gates, aux = _route(params["router"], x2d, m)
        cap = _capacity(m, x2d.shape[0])
        y2d = _moe_local(x2d, gates, params["experts"]["wi"],
                         params["experts"]["wg"], params["experts"]["wo"], cap)
    y = y2d.reshape(B, S, D).astype(x.dtype)
    if m.num_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + apply_mlp(params["shared"], x)
    return y, aux


# ===========================================================================
# RG-LRU recurrent block (RecurrentGemma / Griffin)
# ===========================================================================

_RG_C = 8.0  # decay sharpness constant from the Griffin paper


def rglru_init(key, cfg: cfgs.ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    d, w = cfg.d_model, cfg.lru_width
    # a_param initialised so that a = sigmoid(a_param)^c is in (0.9, 0.999)
    a0 = jnp.linspace(2.0, 6.0, w).astype(jnp.float32)
    return {
        "in_x": dense_init(ks[0], d, w, dtype),
        "in_gate": dense_init(ks[1], d, w, dtype),
        "conv_w": (jax.random.normal(ks[2], (cfg.conv1d_width, w), jnp.float32)
                   * 0.02).astype(dtype),
        "conv_b": jnp.zeros((w,), dtype),
        "a_param": a0,
        "i_gate_w": jnp.ones((w,), jnp.float32),
        "i_gate_b": jnp.zeros((w,), jnp.float32),
        "r_gate_w": jnp.ones((w,), jnp.float32),
        "r_gate_b": jnp.zeros((w,), jnp.float32),
        "out": dense_init(ks[3], w, d, dtype),
    }


def _rglru_coeffs(params, u):
    """u: [...,W] conv output -> (a, b) of h_t = a*h + b (fp32)."""
    uf = u.astype(jnp.float32)
    i_gate = jax.nn.sigmoid(uf * params["i_gate_w"] + params["i_gate_b"])
    r_gate = jax.nn.sigmoid(uf * params["r_gate_w"] + params["r_gate_b"])
    log_a_base = jax.nn.log_sigmoid(params["a_param"])       # [W]
    log_a = _RG_C * r_gate * log_a_base                      # [...,W] (<0)
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b = mult * (i_gate * uf)
    return a, b


def _causal_conv(params, x, prev):
    """Depthwise causal conv1d. x: [B,S,W]; prev: [B,cw-1,W] history."""
    cw = params["conv_w"].shape[0]
    xp = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * params["conv_w"][cw - 1 - i]
            for i in range(cw))
    return y + params["conv_b"]


def rglru_apply(params, x, ctx: Ctx):
    cfg = ctx.cfg
    B, S, _ = x.shape
    u = linear(x, params["in_x"])                            # [B,S,W]
    gate = linear(x, params["in_gate"])
    cache = ctx.cache
    if ctx.mode == "decode":
        prev = cache["conv"]
        h0 = cache["h"]
    else:
        cw = params["conv_w"].shape[0]
        prev = jnp.zeros((B, cw - 1, u.shape[-1]), u.dtype)
        h0 = jnp.zeros((B, u.shape[-1]), jnp.float32)
    uc = _causal_conv(params, u, prev)
    a, b = _rglru_coeffs(params, uc)
    if ctx.mode == "prefill" and ctx.valid is not None:
        # pad positions perform an identity state update (a=1, b=0) so the
        # final carried state equals the state at the last valid token
        vm = ctx.valid[..., None]
        a = jnp.where(vm, a, 1.0)
        b = jnp.where(vm, b, 0.0)

    if ctx.mode == "decode":
        assert S == 1
        h = a[:, 0] * h0 + b[:, 0]                           # [B,W]
        hs = h[:, None]
        new_cache = {"h": h,
                     "conv": jnp.concatenate([prev, u], axis=1)[:, 1:]}
    else:
        def step(h, ab):
            a_t, b_t = ab
            h = a_t * h + b_t
            return h, h
        hT, hs = jax.lax.scan(step, h0,
                              (jnp.moveaxis(a, 1, 0), jnp.moveaxis(b, 1, 0)))
        hs = jnp.moveaxis(hs, 0, 1)                          # [B,S,W]
        new_cache = ctx.cache
        if ctx.mode == "prefill":
            # conv history = the last (cw-1) *valid* inputs per sequence
            cw = params["conv_w"].shape[0]
            lens = (ctx.lengths if ctx.lengths is not None
                    else jnp.full((B,), S, jnp.int32))
            idx = lens[:, None] - (cw - 1) + jnp.arange(cw - 1)[None]   # [B,cw-1]
            ok = (idx >= 0)[..., None]
            idxc = jnp.clip(idx, 0, S - 1)
            bidx = jnp.arange(B)[:, None]
            conv_hist = jnp.where(ok, u[bidx, idxc], 0).astype(u.dtype)
            new_cache = {"h": hT, "conv": conv_hist}
    y = hs.astype(x.dtype) * jax.nn.gelu(gate.astype(jnp.float32)).astype(x.dtype)
    return linear(y, params["out"]), new_cache


def rglru_cache_init(cfg: cfgs.ModelConfig, batch: int, dtype):
    w = cfg.lru_width
    return {"h": jnp.zeros((batch, w), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv1d_width - 1, w), dtype)}


# ===========================================================================
# RWKV-6 "Finch" block (time-mix + channel-mix)
# ===========================================================================


def rwkv_init(key, cfg: cfgs.ModelConfig, dtype):
    ks = jax.random.split(key, 10)
    d, f = cfg.d_model, cfg.d_ff
    H = d // cfg.rwkv_head_size
    hd = cfg.rwkv_head_size
    p = {
        "tmix": {
            "mu_r": jnp.full((d,), 0.5, dtype), "mu_k": jnp.full((d,), 0.5, dtype),
            "mu_v": jnp.full((d,), 0.5, dtype), "mu_g": jnp.full((d,), 0.5, dtype),
            "mu_w": jnp.full((d,), 0.5, dtype),
            "wr": dense_init(ks[0], d, d, dtype), "wk": dense_init(ks[1], d, d, dtype),
            "wv": dense_init(ks[2], d, d, dtype), "wg": dense_init(ks[3], d, d, dtype),
            "ww": dense_init(ks[4], d, d, dtype, scale=0.1),
            "wo": dense_init(ks[5], d, d, dtype),
            "w0": jnp.linspace(-6.0, -1.0, d).astype(jnp.float32),
            "u": (jax.random.normal(ks[6], (H, hd), jnp.float32) * 0.1),
            "gn_scale": jnp.ones((d,), jnp.float32),
            "gn_bias": jnp.zeros((d,), jnp.float32),
        },
        "cmix": {
            "mu_k": jnp.full((d,), 0.5, dtype),
            "wk": dense_init(ks[7], d, f, dtype),
            "wv": dense_init(ks[8], f, d, dtype),
        },
    }
    return p


def _token_shift(x, prev):
    """x: [B,S,D]; prev: [B,D] last token of the previous segment."""
    return jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)


def _wkv_scan(r, k, v, w, u, s0):
    """RWKV6 recurrence.  r,k,v,w: [B,S,H,hd] (w in (0,1)); u: [H,hd];
    s0: [B,H,hd,hd] fp32.  Returns (o: [B,S,H,hd], sT)."""
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))

    def step(s, rkvw):
        r_t, k_t, v_t, w_t = rkvw                       # [B,H,hd]
        kv = k_t[..., :, None] * v_t[..., None, :]      # [B,H,hd,hd]
        s_att = s + u[None, :, :, None] * kv
        o_t = jnp.einsum("bhi,bhij->bhj", r_t, s_att)
        s = w_t[..., :, None] * s + kv
        return s, o_t

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (rf, kf, vf, wf))
    sT, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1), sT


def rwkv_apply(params, x, ctx: Ctx):
    """Full RWKV block: x + tmix(ln1(x)), then + cmix(ln2(.)).

    ``params`` is the whole block param dict (needs ln1/ln2).  Token-shift
    states are the last *normed* tokens of each stream (so that decode
    continues exactly where prefill left off).
    """
    cfg = ctx.cfg
    B, S, D = x.shape
    H = D // cfg.rwkv_head_size
    hd = cfg.rwkv_head_size
    tm = params["rwkv"]["tmix"]
    cm = params["rwkv"]["cmix"]
    cache = ctx.cache
    if ctx.mode == "decode":
        prev_t, prev_c, s0 = cache["shift_t"], cache["shift_c"], cache["s"]
    else:
        prev_t = jnp.zeros((B, D), x.dtype)
        prev_c = jnp.zeros((B, D), x.dtype)
        s0 = jnp.zeros((B, H, hd, hd), jnp.float32)

    # ---- time-mix ----
    h1 = apply_norm(params["ln1"], x, cfg.norm_eps)
    xx = _token_shift(h1, prev_t)

    def mix(mu):
        return h1 + mu * (xx - h1)

    r = linear(mix(tm["mu_r"]), tm["wr"]).reshape(B, S, H, hd)
    k = linear(mix(tm["mu_k"]), tm["wk"]).reshape(B, S, H, hd)
    v = linear(mix(tm["mu_v"]), tm["wv"]).reshape(B, S, H, hd)
    g = linear(mix(tm["mu_g"]), tm["wg"])
    decay_raw = tm["w0"] + linear(mix(tm["mu_w"]), tm["ww"]).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(decay_raw)).reshape(B, S, H, hd)
    if ctx.mode == "prefill" and ctx.valid is not None:
        # pads: decay 1, no kv injection -> state frozen at last valid token
        vm = ctx.valid[:, :, None, None]
        w = jnp.where(vm, w, 1.0)
        k = jnp.where(vm, k, 0.0).astype(k.dtype)

    o, sT = _wkv_scan(r, k, v, w, tm["u"], s0)
    o = group_norm_heads(o, tm["gn_scale"], tm["gn_bias"]).astype(x.dtype)
    o = o * jax.nn.silu(g.astype(jnp.float32)).astype(o.dtype)
    x2 = x + linear(o, tm["wo"])

    # ---- channel-mix ----
    h2 = apply_norm(params["ln2"], x2, cfg.norm_eps)
    xx2 = _token_shift(h2, prev_c)
    zk = h2 + cm["mu_k"] * (xx2 - h2)
    h = jnp.square(jax.nn.relu(linear(zk, cm["wk"]).astype(jnp.float32)))
    y2 = linear(h.astype(x.dtype), cm["wv"])
    out = x2 + y2

    new_cache = ctx.cache
    if ctx.mode in ("prefill", "decode"):
        if ctx.mode == "prefill" and ctx.lengths is not None:
            bidx = jnp.arange(B)
            last = jnp.clip(ctx.lengths - 1, 0, S - 1)
            st, sc = h1[bidx, last], h2[bidx, last]
        else:
            st, sc = h1[:, -1], h2[:, -1]
        new_cache = {"s": sT, "shift_t": st, "shift_c": sc}
    return out, new_cache


def rwkv_cache_init(cfg: cfgs.ModelConfig, batch: int, dtype):
    D = cfg.d_model
    H = D // cfg.rwkv_head_size
    hd = cfg.rwkv_head_size
    return {"s": jnp.zeros((batch, H, hd, hd), jnp.float32),
            "shift_t": jnp.zeros((batch, D), dtype),
            "shift_c": jnp.zeros((batch, D), dtype)}


# ===========================================================================
# dispatch table
# ===========================================================================


def block_init(blk: str, key, cfg: cfgs.ModelConfig, dtype,
               dense: bool = False):
    """``dense``: a dense MLP of width d_ff even where ``cfg.moe`` is set
    (the leading layers of an MoE model)."""
    k1, k2, k3 = jax.random.split(key, 3)
    if blk in (cfgs.ATTN, cfgs.LOCAL_ATTN):
        p = {"ln1": norm_init(cfg.d_model, dtype, cfg.use_layernorm),
             "ln2": norm_init(cfg.d_model, dtype, cfg.use_layernorm),
             "attn": attn_init(k1, cfg, dtype)}
        if cfg.sandwich_norm:
            p["post_attn_norm"] = norm_init(cfg.d_model, dtype,
                                            cfg.use_layernorm)
            p["post_mlp_norm"] = norm_init(cfg.d_model, dtype,
                                           cfg.use_layernorm)
        if cfg.moe is not None and not dense:
            p["moe"] = moe_init(k2, cfg, dtype)
        else:
            p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, dtype, cfg.use_bias)
        return p
    if blk == cfgs.RGLRU:
        return {"ln1": norm_init(cfg.d_model, dtype, cfg.use_layernorm),
                "ln2": norm_init(cfg.d_model, dtype, cfg.use_layernorm),
                "rec": rglru_init(k1, cfg, dtype),
                "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype, cfg.use_bias)}
    if blk == cfgs.RWKV:
        return {"ln1": norm_init(cfg.d_model, dtype, True),
                "ln2": norm_init(cfg.d_model, dtype, True),
                "rwkv": rwkv_init(k1, cfg, dtype)}
    raise ValueError(blk)


def block_cache_init(blk: str, cfg: cfgs.ModelConfig, batch: int, smax: int,
                     dtype, paged: bool = False):
    """``paged``: every attention layer's cache is a full [B, smax] one,
    windowed layers too (the paged pool's template); else a windowed
    layer keeps a ring of its last ``attention_window`` positions."""
    if blk == cfgs.ATTN:
        return attn_cache_init(cfg, batch, smax, window=0, dtype=dtype)
    if blk == cfgs.LOCAL_ATTN:
        return attn_cache_init(cfg, batch, smax,
                               window=0 if paged else cfg.attention_window,
                               dtype=dtype)
    if blk == cfgs.RGLRU:
        return rglru_cache_init(cfg, batch, dtype)
    if blk == cfgs.RWKV:
        return rwkv_cache_init(cfg, batch, dtype)
    raise ValueError(blk)


def block_apply(blk: str, params, x, ctx: Ctx):
    cfg = ctx.cfg
    aux = jnp.float32(0.0)
    if blk in (cfgs.ATTN, cfgs.LOCAL_ATTN):
        window = cfg.attention_window if blk == cfgs.LOCAL_ATTN else 0
        h1 = apply_norm(params["ln1"], x, cfg.norm_eps)
        a_out, new_cache = attn_apply(params["attn"], h1, ctx, window=window)
        if cfg.parallel_block:
            if "moe" in params:
                m_out, aux = moe_apply(params["moe"], h1, ctx)
            else:
                m_out = apply_mlp(params["mlp"], h1)
            y = x + a_out + m_out
        else:
            if cfg.sandwich_norm:
                a_out = apply_norm(params["post_attn_norm"], a_out,
                                   cfg.norm_eps)
            x = x + a_out
            h2 = apply_norm(params["ln2"], x, cfg.norm_eps)
            if "moe" in params:
                m_out, aux = moe_apply(params["moe"], h2, ctx)
            else:
                m_out = apply_mlp(params["mlp"], h2)
            if cfg.sandwich_norm:
                m_out = apply_norm(params["post_mlp_norm"], m_out,
                                   cfg.norm_eps)
            y = x + m_out
        return y, new_cache, aux
    if blk == cfgs.RGLRU:
        h1 = apply_norm(params["ln1"], x, cfg.norm_eps)
        r_out, new_cache = rglru_apply(params["rec"], h1, ctx)
        x = x + r_out
        h2 = apply_norm(params["ln2"], x, cfg.norm_eps)
        y = x + apply_mlp(params["mlp"], h2)
        return y, new_cache, aux
    if blk == cfgs.RWKV:
        # rwkv_apply handles norms, residuals and token-shift state itself
        out, new_cache = rwkv_apply(params, x, ctx)
        return out, new_cache, aux
    raise ValueError(blk)
