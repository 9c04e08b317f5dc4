"""Plain float32 Qwen3 forward, and its lower-precision controls.

The published architecture (Qwen3 ``config.json``): token embedding;
per layer RMSNorm -> GQA attention with per-head RMSNorm on q and k
(qk-norm), half-split RoPE at ``rope_theta``, causal softmax -> residual
-> RMSNorm -> SwiGLU MLP (down(silu(gate(x)) * up(x))) -> residual; final
RMSNorm and an untied head.  Everything is float32 matrix arithmetic at
``Precision.HIGHEST``: no kernel, no cache, no batching across requests.

Weights are drawn from ``--seed`` here, by the same stream the engine's
initialiser draws them from (``jax.random`` key splits, a normal scaled by
0.02 for the embedding and head and by 1/sqrt(fan_in) for projections,
rounded to the served bfloat16, norms at 1), one layer at a time, so the
reference takes nothing the program made and fits beside nothing.

``controls`` adds streams beside the reference: the same forward with
every matrix product one precision step below the served bfloat16 --
``int8`` (weights per output channel, activations per token, symmetric;
int32 accumulation) or ``fp8`` (float8_e4m3fn, scaled the same way).
The check's limits are set so that a control fails them.

Each sequence is padded on the right to a power-of-two length and run by
itself; causal attention keeps padding out of every real position.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    eps: float
    theta: float

    @classmethod
    def of(cls, conf: dict) -> "Shape":
        assert conf["model_type"] == "qwen3" and conf["hidden_act"] == "silu"
        assert not conf["tie_word_embeddings"] and not conf["attention_bias"]
        return cls(conf["num_hidden_layers"], conf["hidden_size"],
                   conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"], conf["intermediate_size"],
                   conf["vocab_size"], float(conf["rms_norm_eps"]),
                   float(conf["rope_theta"]))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def _dense(key, d_in, d_out):
    return _normal(key, (d_in, d_out), 1.0 / math.sqrt(d_in))


def top_keys(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed), 6)


def layer_keys(seed: int, layers: int):
    return jax.random.split(jax.random.split(top_keys(seed)[3], 1)[0],
                            layers)


def make_layer(key, s: Shape) -> Dict[str, jax.Array]:
    k_attn, k_mlp, _ = jax.random.split(key, 3)
    ka = jax.random.split(k_attn, 8)
    km = jax.random.split(k_mlp, 3)
    q_dim, kv_dim = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return {"wq": _dense(ka[0], s.d, q_dim), "wk": _dense(ka[1], s.d, kv_dim),
            "wv": _dense(ka[2], s.d, kv_dim), "wo": _dense(ka[3], q_dim, s.d),
            "up": _dense(km[0], s.d, s.d_ff), "gate": _dense(km[1], s.d, s.d_ff),
            "down": _dense(km[2], s.d_ff, s.d)}


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta):
    """x: [S, H, hd], half-split rotation at positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def quantize(x, axis):
    """Symmetric int8 along ``axis`` (the contracted one); returns (q, scale)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def mm_f32(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HI)


def mm_int8(x, wq):
    """x: [S, k] float32; wq: (int8 [k, n], scale [1, n])."""
    q, ws = wq
    xq, xs = quantize(x, axis=-1)
    acc = jax.lax.dot_general(xq, q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


FP8_MAX = 448.0          # largest finite float8_e4m3fn


def to_fp8(x, axis):
    """Scaled to float8_e4m3fn along ``axis`` and back: (values, scale)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32), scale


def mm_fp8(x, wq):
    """One bfloat16 pass: float8_e4m3fn values (4 significant bits) are
    bfloat16 values, so the products are exact and sum in float32."""
    q, ws = wq
    xq, xs = to_fp8(x, axis=-1)
    return jnp.matmul(xq, q, precision=jax.lax.Precision.DEFAULT) * xs * ws


LOWER = {"int8": (lambda w: quantize(w, axis=0), mm_int8),
         "fp8": (lambda w: to_fp8(w, axis=0), mm_fp8)}


def _layer(x, w, s: Shape, mm):
    """One decoder layer on one sequence x: [S, d] float32."""
    S = x.shape[0]
    h = rms_norm(x, s.eps)
    q = mm(h, w["wq"]).reshape(S, s.heads, s.head_dim)
    k = mm(h, w["wk"]).reshape(S, s.kv_heads, s.head_dim)
    v = mm(h, w["wv"]).reshape(S, s.kv_heads, s.head_dim)
    q, k = rope(rms_norm(q, s.eps), s.theta), rope(rms_norm(k, s.eps), s.theta)
    g = s.heads // s.kv_heads
    qg = q.reshape(S, s.kv_heads, g, s.head_dim)
    logits = jnp.einsum("qcgd,scd->cgqs", qg, k, precision=HI)
    logits = logits / math.sqrt(s.head_dim)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = jnp.einsum("cgqs,scd->qcgd", p, v, precision=HI)
    x = x + mm(o.reshape(S, s.heads * s.head_dim), w["wo"])
    h = rms_norm(x, s.eps)
    return x + mm(jax.nn.silu(mm(h, w["gate"])) * mm(h, w["up"]), w["down"])


def _apply_layer(w, xs, s: Shape, controls: tuple):
    """xs: {"ref": [S, d], <control>: [S, d], ...}, one sequence."""
    with jax.default_matmul_precision("highest"):
        out = {"ref": _layer(xs["ref"], w, s, mm_f32)}
        for name in controls:
            lower, mm = LOWER[name]
            wq = {k: lower(v.astype(jnp.float32)) for k, v in w.items()}
            out[name] = _layer(xs[name], wq, s, mm)
        return out


def _readout(head, hs, tok, s: Shape, yes: int, no: int, controls: tuple):
    """Statistics of the logits at gathered positions: hs[stream] [P, d]."""
    with jax.default_matmul_precision("highest"):
        r = mm_f32(rms_norm(hs["ref"], s.eps), head)
        out = {"ref_t": jnp.take_along_axis(r, tok[:, None], 1)[:, 0],
               "ref_max": jnp.max(r, -1),
               "ref_lse": jax.nn.logsumexp(r, -1),
               "ref_yes": r[:, yes], "ref_no": r[:, no]}
        for name in controls:
            lower, mm = LOWER[name]
            c = mm(rms_norm(hs[name], s.eps), lower(head.astype(jnp.float32)))
            pick = jnp.argmax(c, -1)
            out.update({
                f"{name}_pick_ref": jnp.take_along_axis(
                    r, pick[:, None], 1)[:, 0],
                f"{name}_t": jnp.take_along_axis(c, tok[:, None], 1)[:, 0],
                f"{name}_lse": jax.nn.logsumexp(c, -1),
                f"{name}_yes": c[:, yes], f"{name}_no": c[:, no]})
        return out


@dataclasses.dataclass
class Probe:
    """Read the logits at ``positions`` of ``tokens``; ``targets`` are the
    ids whose logits are read there (a served or label token)."""
    tokens: List[int]
    positions: List[int]
    targets: List[int]


def _bucket(n: int, lo: int = 64) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _rows(x, positions):
    return x[positions]


def run(conf: dict, seed: int, probes: Sequence[Probe], *, yes: int, no: int,
        controls: Sequence[str] = (), max_rows: int = 64
        ) -> List[Dict[str, np.ndarray]]:
    """One readout dict per probe (arrays over its positions).

    Every program here has a shape that the probes' lengths alone fix --
    a sequence is its power-of-two length bucket, a probe's positions a
    power-of-two count, a readout ``max_rows`` rows -- so the compile cache
    holds them all after a few runs, whichever requests a seed samples."""
    s = Shape.of(conf)
    controls = tuple(controls)
    make_layer_j = jax.jit(make_layer, static_argnums=1)
    apply_j = jax.jit(_apply_layer, static_argnums=(2, 3))
    readout_j = jax.jit(_readout, static_argnums=(3, 4, 5, 6))
    rows_j = jax.jit(_rows)
    embed = jax.jit(lambda k: _normal(k, (s.vocab, s.d), 0.02))(
        top_keys(seed)[0])
    take_j = jax.jit(lambda e, ids: e[ids].astype(jnp.float32))
    hidden = []
    for p in probes:
        ids = np.zeros((_bucket(len(p.tokens)),), np.int32)
        ids[:len(p.tokens)] = p.tokens
        x = take_j(embed, ids)
        hidden.append({name: x for name in ("ref",) + controls})
    del embed
    keys = layer_keys(seed, s.layers)
    for layer in range(s.layers):
        w = make_layer_j(keys[layer], s)
        hidden = [apply_j(w, x, s, controls) for x in hidden]
        del w
    head = jax.jit(lambda k: _normal(k, (s.d, s.vocab), 0.02))(
        top_keys(seed)[1])
    # each probe's positions, gathered and padded to a power of two
    picked = {name: [] for name in ("ref",) + controls}
    toks, owner = [], []
    for i, (p, x) in enumerate(zip(probes, hidden)):
        pos = np.zeros((_bucket(len(p.positions), lo=1),), np.int32)
        pos[:len(p.positions)] = p.positions
        for name in picked:
            picked[name].append(
                np.asarray(rows_j(x[name], pos))[:len(p.positions)])
        toks += list(p.targets)
        owner += [i] * len(p.positions)
    del hidden
    picked = {k: np.concatenate(v) for k, v in picked.items()}
    got: Dict[str, List[np.ndarray]] = {}
    for a in range(0, len(toks), max_rows):
        n = min(max_rows, len(toks) - a)
        rows = {k: np.zeros((max_rows, s.d), np.float32)
                for k in picked}
        for k, v in picked.items():
            rows[k][:n] = v[a:a + n]
        tk = np.zeros((max_rows,), np.int32)
        tk[:n] = toks[a:a + n]
        part = readout_j(head, rows, tk, s, yes, no, controls)
        for k, v in part.items():
            got.setdefault(k, []).append(np.asarray(v, np.float64)[:n])
    flat = {k: np.concatenate(v) for k, v in got.items()}
    owner = np.asarray(owner)
    return [{k: v[owner == i] for k, v in flat.items()}
            for i in range(len(probes))]
