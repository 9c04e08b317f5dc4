"""Qwen3 for the benchmark: the program's configuration, the counts of
the work a request needs, and a plain float32 forward.

The harness loads this module by path for every configuration whose
``model_type`` is ``qwen3``, and reads ``model_config``, ``Shape.of``
(with its counts) and ``run``; what every architecture shares is in
``common.py``.

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
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common
from chipbench.reference.common import HI, Probe, rms_norm, streams

BF16 = 2


def model_config(conf: dict, name: str):
    """The program's ModelConfig for a Qwen3 ``config.json``."""
    from repro.configs.base import ATTN, ModelConfig
    if conf["hidden_act"] != "silu":
        raise ValueError(f"{name}: not a Qwen3 configuration")
    return ModelConfig(
        name=name, family="dense", num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qk_norm=True, use_bias=conf["attention_bias"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=conf["tie_word_embeddings"], period=(ATTN,),
        dtype=conf["torch_dtype"])


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes, and the operations and bytes of the work a request needs
    through them (``flops.py`` adds them up per request).  Each layer sees
    every prompt and fed-back token, attends causally over the positions
    before each token, and the head is counted only for the rows a request
    reads."""
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
        assert conf["hidden_act"] == "silu"
        assert not conf["tie_word_embeddings"] and not conf["attention_bias"]
        return cls(conf["num_hidden_layers"], conf["hidden_size"],
                   conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"], conf["intermediate_size"],
                   conf["vocab_size"], float(conf["rms_norm_eps"]),
                   float(conf["rope_theta"]))

    def dense_per_token(self) -> float:
        """Projection and MLP FLOPs of one token through one layer."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return (2.0 * self.d * (q + 2 * kv) + 2.0 * q * self.d
                + 6.0 * self.d * self.d_ff)

    def attention(self, start: int, n: int) -> float:
        """Score and value FLOPs of tokens at positions start..start+n-1 of
        one layer, each attending to every position up to its own."""
        keys = n * start + n * (n + 1) / 2.0
        return 4.0 * self.heads * self.head_dim * keys

    def extend(self, start: int, n: int) -> float:
        """FLOPs of n tokens after ``start`` cached ones, through every
        layer."""
        return self.layers * (n * self.dense_per_token()
                              + self.attention(start, n))

    def head_rows(self, rows: int) -> float:
        """FLOPs of ``rows`` rows of the head (one vocabulary entry's logit
        at one position each)."""
        return 2.0 * self.d * rows

    def decode_attention(self, length: int):
        """(FLOPs, bytes) of one flash-decode call for one sequence whose
        cache holds ``length`` valid positions, over every layer: q.k and
        p.v over the valid keys, and the bfloat16 K and V those keys
        need."""
        flops = 4.0 * self.heads * self.head_dim * length * self.layers
        nbytes = (2.0 * length * self.kv_heads * self.head_dim * BF16
                  * self.layers)
        return flops, nbytes


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


def rope(x, theta):
    """x: [S, H, hd], half-split rotation at positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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
    return streams(lambda x, w, mm: _layer(x, w, s, mm), w, xs, controls)


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
    embed = jax.jit(lambda k: _normal(k, (s.vocab, s.d), 0.02))(
        top_keys(seed)[0])
    hidden = common.inputs(embed, probes, controls)
    del embed
    keys = layer_keys(seed, s.layers)
    for layer in range(s.layers):
        w = make_layer_j(keys[layer], s)
        hidden = [apply_j(w, x, s, controls) for x in hidden]
        del w
    picked = common.gather(hidden, probes)
    del hidden
    head = jax.jit(lambda k: _normal(k, (s.d, s.vocab), 0.02))(
        top_keys(seed)[1])
    return common.read_out(head, picked, probes, eps=s.eps, yes=yes, no=no,
                           controls=controls, max_rows=max_rows)
