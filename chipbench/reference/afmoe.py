"""AFMoE (Arcee Trinity) for the benchmark: the program's configuration, the
counts of the work a request needs, and a plain float32 forward.

The harness loads this module by path for every configuration whose
``model_type`` is ``afmoe``, and reads ``model_config``, ``Shape.of``
(with its counts) and ``run``; what every architecture shares is in
``common.py``.

The architecture, as the public AFMoE modelling code (``AfmoeForCausalLM``)
computes it, with the sizes and constants of ``config.json``:

* embedding ``h = E[tok] * sqrt(d)`` (``mup_enabled``);
* per layer, sandwich norms (four RMSNorms N1..N4 at ``rms_norm_eps``):
  ``h = h + N2(Attn(N1(h)))``, then ``h = h + N4(FFN(N3(h)))``;
* attention: ``q = RMSNorm_hd(x Wq)``, ``k = RMSNorm_hd(x Wk)`` (per-head
  qk-norm), ``v = x Wv``; half-split RoPE at ``rope_theta`` on q and k in
  the ``sliding_attention`` layers only (``full_attention`` layers use no
  positional encoding); causal softmax over GQA groups, a sliding layer's
  token at position t seeing positions (t - sliding_window, t]; output
  gate ``o = (softmax(q k^T / sqrt(hd)) v) * sigmoid(x Wg)``, then ``o Wo``;
* FFN of the first ``num_dense_layers`` layers: SwiGLU
  ``down(silu(gate(x)) * up(x))`` of width ``intermediate_size``;
* FFN of the others: ``s = sigmoid(x Wr)`` (float32) over
  ``num_experts`` experts; the top ``num_experts_per_tok`` of ``s + b``
  (``b`` the per-expert ``expert_bias``, used for the selection only);
  gates the chosen ``s`` over their sum (``route_norm``) times
  ``route_scale``; ``y = sum_e gate_e SwiGLU_e(x) + SwiGLU_shared(x)``,
  experts and the shared one of width ``moe_intermediate_size``; one
  expert group, so group-limited routing is the identity;
* final RMSNorm and an untied head.

Departures: the norms' scales are ones and ``b`` is drawn from the seed,
as the program's initialiser makes them (random weights); each is listed
under the configuration's ``assumed``.  Everything is float32 matrix
arithmetic at ``Precision.HIGHEST``: no kernel, no cache, no batching
across requests.  Attention is computed in blocks of query rows, so an
8k-token probe fits; the experts run one by one over the tokens routed to
each, gathered on the host.

Weights are drawn from ``--seed`` here, by the same stream the engine's
initialiser draws them from (``jax.random`` key splits: a normal scaled
by 0.02 for the embedding and head and by 1/sqrt(fan_in) for
projections, rounded to the served bfloat16; the router in float32; the
expert bias 0.1 times a normal; norms at 1), so the reference takes
nothing the program made.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common
from chipbench.reference.common import HI, LOWER, Probe, mm_f32, rms_norm

BF16 = 2
SLIDING = "sliding_attention"
QUERY_BLOCK = 512            # query rows per attention block
GROUP_TOKENS = 32768         # rows of sequences one expert pass serves


def _layer_types(conf: dict) -> Tuple[str, ...]:
    types = tuple(conf["layer_types"])
    if len(types) != conf["num_hidden_layers"]:
        raise ValueError("layer_types must give every layer")
    return types


def _check(conf: dict, name: str = "") -> None:
    if (conf["hidden_act"] != "silu" or conf["score_func"] != "sigmoid"
            or conf["tie_word_embeddings"] or not conf["mup_enabled"]
            or conf["rope_scaling"] is not None or not conf["route_norm"]
            or conf["num_expert_groups"] != 1 or conf["n_group"] != 1):
        raise ValueError(f"{name}: not an AFMoE configuration this module "
                         f"computes")


def _pattern(conf: dict):
    """(lead, period, tail) of the layer kinds: the dense layers, then
    whole periods of ``global_attn_every_n_layers``, then the rest."""
    from repro.configs.base import ATTN, LOCAL_ATTN
    kinds = tuple(LOCAL_ATTN if t == SLIDING else ATTN
                  for t in _layer_types(conf))
    nd, p = conf["num_dense_layers"], conf["global_attn_every_n_layers"]
    rest = kinds[nd:]
    period = rest[:p]
    n = 0
    while rest[n * p:(n + 1) * p] == period and (n + 1) * p <= len(rest):
        n += 1
    return kinds[:nd], period, rest[n * p:]


def model_config(conf: dict, name: str):
    """The program's ModelConfig for an AFMoE ``config.json``."""
    from repro.configs.base import ModelConfig, MoEConfig
    _check(conf, name)
    lead, period, tail = _pattern(conf)
    return ModelConfig(
        name=name, family="moe", num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qk_norm=True, rope_theta=float(conf["rope_theta"]),
        attention_window=conf["sliding_window"], attn_out_gate=True,
        rope_local_only=True, norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=False, scale_embedding=True, sandwich_norm=True,
        lead=lead, period=period, tail=tail,
        moe=MoEConfig(
            num_experts=conf["num_experts"],
            num_experts_per_tok=conf["num_experts_per_tok"],
            expert_d_ff=conf["moe_intermediate_size"],
            num_shared_experts=conf["num_shared_experts"],
            shared_d_ff=(conf["num_shared_experts"]
                         * conf["moe_intermediate_size"]),
            score_func="sigmoid", selection_bias=True,
            route_scale=float(conf["route_scale"])),
        dtype=conf.get("torch_dtype", "bfloat16"))


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes, and the operations and bytes of the work a request needs
    through them (``flops.py`` adds them up per request).  Each layer sees
    every prompt and fed-back token; a full layer attends causally over
    every position before each token, a sliding one over the last
    ``window`` of them; a dense layer runs its MLP, an MoE layer its
    router, ``top_k`` routed experts and the shared expert per token; the
    head is counted only for the rows a request reads."""
    types: Tuple[str, ...]
    dense_layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    experts: int
    top_k: int
    expert_d_ff: int
    shared_d_ff: int
    vocab: int
    window: int
    eps: float
    theta: float
    route_scale: float

    @classmethod
    def of(cls, conf: dict) -> "Shape":
        _check(conf)
        return cls(_layer_types(conf), conf["num_dense_layers"],
                   conf["hidden_size"], conf["num_attention_heads"],
                   conf["num_key_value_heads"], conf["head_dim"],
                   conf["intermediate_size"], conf["num_experts"],
                   conf["num_experts_per_tok"], conf["moe_intermediate_size"],
                   conf["num_shared_experts"] * conf["moe_intermediate_size"],
                   conf["vocab_size"], conf["sliding_window"],
                   float(conf["rms_norm_eps"]), float(conf["rope_theta"]),
                   float(conf["route_scale"]))

    @property
    def layers(self) -> int:
        return len(self.types)

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    def proj_per_token(self) -> float:
        """q, k, v, gate and output projections of one token, one layer."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return 2.0 * self.d * (q + 2 * kv) + 2.0 * q * self.d + 2.0 * self.d * q

    def ffn_per_token(self, dense: bool) -> float:
        if dense:
            return 6.0 * self.d * self.d_ff
        return (2.0 * self.d * self.experts
                + 6.0 * self.d * (self.top_k * self.expert_d_ff
                                  + self.shared_d_ff))

    def keys(self, start: int, n: int, sliding: bool) -> float:
        """Positions attended by tokens start..start+n-1 of one layer."""
        full = n * start + n * (n + 1) / 2.0
        if not sliding or start + n <= self.window:
            return full
        # positions t >= window - 1 see window keys, not t + 1
        over = max(start, self.window - 1)
        m = start + n - over                 # tokens at or past the cap
        capped = (over + 1 + start + n) * m / 2.0
        return full - capped + m * self.window

    def attention(self, start: int, n: int, sliding: bool) -> float:
        return 4.0 * self.heads * self.head_dim * self.keys(start, n, sliding)

    def extend(self, start: int, n: int) -> float:
        """FLOPs of n tokens after ``start`` cached ones, through every
        layer."""
        total = 0.0
        for i, t in enumerate(self.types):
            total += n * (self.proj_per_token()
                          + self.ffn_per_token(i < self.dense_layers))
            total += self.attention(start, n, t == SLIDING)
        return total

    def head_rows(self, rows: int) -> float:
        """FLOPs of ``rows`` rows of the head (one vocabulary entry's logit
        at one position each)."""
        return 2.0 * self.d * rows

    def decode_attention(self, length: int):
        """(FLOPs, bytes) of one flash-decode call for one sequence whose
        cache holds ``length`` valid positions, over every layer: q.k and
        p.v over the keys each layer reads (the last ``window`` in a
        sliding layer), and the bfloat16 K and V of those keys."""
        keys = sum(min(length, self.window) if t == SLIDING else length
                   for t in self.types)
        flops = 4.0 * self.heads * self.head_dim * keys
        nbytes = 2.0 * keys * self.kv_heads * self.head_dim * BF16
        return flops, nbytes

    def expert_flops(self, pairs: float) -> float:
        """FLOPs of the routed experts' SwiGLU for ``pairs`` (token,
        expert) pairs of one layer: three d x expert_d_ff products each."""
        return 6.0 * self.d * self.expert_d_ff * pairs

    def expert_bytes(self) -> float:
        """Bytes of every routed expert's bfloat16 weights of one layer:
        what a step that routes a token to each expert reads at least."""
        return 3.0 * self.experts * self.d * self.expert_d_ff * BF16


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def _normal(key, shape, std, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _dense(key, d_in, d_out, dtype=jnp.bfloat16):
    return _normal(key, (d_in, d_out), 1.0 / math.sqrt(d_in), dtype)


def top_keys(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed), 6)


def layer_key(seed: int, conf: dict, layer: int):
    """The key the engine's initialiser gives ``layer``: the leading
    layers' from the lead stream, the periods' (period block b, repetition
    r) from the stacked stream, the rest from the tail stream."""
    lead, period, tail = _pattern(conf)
    top = top_keys(seed)
    if layer < len(lead):
        return jax.random.split(top[5], len(lead))[layer]
    j = layer - len(lead)
    n = (len(_layer_types(conf)) - len(lead) - len(tail)) // len(period)
    if j < n * len(period):
        r, b = divmod(j, len(period))
        return jax.random.split(jax.random.split(top[3], len(period))[b],
                                n)[r]
    return jax.random.split(top[4], len(tail))[j - n * len(period)]


def make_layer(key, s: Shape, dense: bool) -> Dict[str, jax.Array]:
    k_attn, k_ffn, _ = jax.random.split(key, 3)
    ka = jax.random.split(k_attn, 8)
    q_dim, kv_dim = s.heads * s.head_dim, s.kv_heads * s.head_dim
    w = {"wq": _dense(ka[0], s.d, q_dim), "wk": _dense(ka[1], s.d, kv_dim),
         "wv": _dense(ka[2], s.d, kv_dim), "wo": _dense(ka[3], q_dim, s.d),
         "wgate": _dense(ka[4], s.d, q_dim)}
    if dense:
        km = jax.random.split(k_ffn, 3)
        w.update(up=_dense(km[0], s.d, s.d_ff), gate=_dense(km[1], s.d, s.d_ff),
                 down=_dense(km[2], s.d_ff, s.d))
        return w
    km = jax.random.split(k_ffn, 5)
    ks = jax.random.split(km[4], 3)
    w.update(router=_dense(km[0], s.d, s.experts, jnp.float32),
             s_up=_dense(ks[0], s.d, s.shared_d_ff),
             s_gate=_dense(ks[1], s.d, s.shared_d_ff),
             s_down=_dense(ks[2], s.shared_d_ff, s.d))
    return w


def make_experts(key, s: Shape) -> Dict[str, jax.Array]:
    """The routed experts of an MoE layer, stacked [E, ...], one key each."""
    km = jax.random.split(jax.random.split(key, 3)[1], 5)

    def stack(k, d_in, d_out):
        return jax.vmap(lambda kk: _dense(kk, d_in, d_out))(
            jax.random.split(k, s.experts))
    return {"up": stack(km[1], s.d, s.expert_d_ff),
            "gate": stack(km[2], s.d, s.expert_d_ff),
            "down": stack(km[3], s.expert_d_ff, s.d)}


def expert_bias(key, s: Shape):
    km0 = jax.random.split(jax.random.split(key, 3)[1], 5)[0]
    return 0.1 * jax.random.normal(jax.random.fold_in(km0, 1), (s.experts,),
                                   jnp.float32)


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


def attention(q, k, v, window: int):
    """Causal softmax attention in blocks of query rows.  q: [S, H, hd];
    k, v: [S, KV, hd]; ``window`` 0 for a full layer."""
    S, H, hd = q.shape
    kvh = k.shape[1]
    g = H // kvh
    qb = min(QUERY_BLOCK, S)
    qs = q.reshape(S // qb, qb, kvh, g, hd)
    kpos = jnp.arange(S)[None, :]

    def block(args):
        i, qi = args
        logits = jnp.einsum("qcgd,scd->cgqs", qi, k,
                            precision=HI) / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)[:, None]
        seen = kpos <= qpos
        if window:
            seen &= kpos > qpos - window
        p = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
        return jnp.einsum("cgqs,scd->qcgd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(S // qb), qs))
    return out.reshape(S, H * hd)


def _attend(x, w, s: Shape, window: int, mm):
    """x + N2(Attn(N1(x))) on one sequence x: [S, d] float32."""
    S = x.shape[0]
    h = rms_norm(x, s.eps)
    q = rms_norm(mm(h, w["wq"]).reshape(S, s.heads, s.head_dim), s.eps)
    k = rms_norm(mm(h, w["wk"]).reshape(S, s.kv_heads, s.head_dim), s.eps)
    v = mm(h, w["wv"]).reshape(S, s.kv_heads, s.head_dim)
    if window:
        q, k = rope(q, s.theta), rope(k, s.theta)
    o = attention(q, k, v, window) * jax.nn.sigmoid(mm(h, w["wgate"]))
    return x + rms_norm(mm(o, w["wo"]), s.eps)


def _swiglu(x, up, gate, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _dense_layer(x, w, s: Shape, window: int, mm):
    x = _attend(x, w, s, window, mm)
    h = rms_norm(x, s.eps)
    return x + rms_norm(_swiglu(h, w["up"], w["gate"], w["down"], mm), s.eps)


def _moe_front(x, w, s: Shape, window: int, mm):
    """The attention sublayer, then what the experts need: the FFN's input
    N3(x), the router's sigmoid scores and the shared expert's output."""
    x = _attend(x, w, s, window, mm)
    u = rms_norm(x, s.eps)
    return {"x": x, "u": u, "scores": jax.nn.sigmoid(mm(u, w["router"])),
            "shared": _swiglu(u, w["s_up"], w["s_gate"], w["s_down"], mm)}


def _route(scores, bias, top_k: int, scale: float):
    """Top-k of scores + bias; gates the chosen scores over their sum,
    times ``scale``."""
    _, idx = jax.lax.top_k(scores + bias[None], top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, gates / jnp.sum(gates, -1, keepdims=True) * scale


def _front(w, xs, bias, s: Shape, window: int, dense: bool, controls: tuple):
    """One layer (dense) or its part before the routed experts (MoE) on
    every stream of one sequence."""
    if dense:
        return common.streams(
            lambda x, w, mm: _dense_layer(x, w, s, window, mm), w, xs,
            controls)
    out = common.streams(lambda x, w, mm: _moe_front(x, w, s, window, mm),
                         w, xs, controls)
    for o in out.values():
        o["idx"], o["gates"] = _route(o.pop("scores"), bias, s.top_k,
                                      s.route_scale)
    return out


def _expert(acc, u, rows, gates, experts, e, stream: str):
    """acc[rows] += gates * SwiGLU_e(u[rows]) for one expert ``e``; rows
    past the end of ``u`` are padding.  A control lowers the expert's
    weights (per output channel) as it lowers every matrix."""
    with jax.default_matmul_precision("highest"):
        w = {k: jax.lax.dynamic_index_in_dim(v, e, keepdims=False)
             for k, v in experts.items()}
        if stream == "ref":
            mm = mm_f32
        else:
            lower, mm = LOWER[stream]
            w = {k: lower(v.astype(jnp.float32)) for k, v in w.items()}
        x = u[jnp.clip(rows, 0, u.shape[0] - 1)]
        y = _swiglu(x, w["up"], w["gate"], w["down"], mm) * gates[:, None]
        return acc.at[rows].add(y, mode="drop")


def _put(buf, x, at):
    return jax.lax.dynamic_update_slice_in_dim(buf, x, at, 0)


def _finish(x, acc, at, shared, eps: float):
    """x + N4(routed + shared), the routed part read from the group's
    accumulator at row ``at``."""
    routed = jax.lax.dynamic_slice_in_dim(acc, at, x.shape[0], 0)
    return x + rms_norm(routed + shared, eps)


def _experts_over(fronts, lengths, experts, stream: str, jitted, s: Shape):
    """The routed experts of one stream over a group of sequences, each
    expert once over the real tokens routed to it; returns each
    sequence's layer output.  The group's sequences sit in one buffer of
    ``GROUP_TOKENS`` rows, so every program here has a shape that the
    sequences' length buckets alone fix."""
    put_j, expert_j, finish_j = jitted
    u = jnp.zeros((GROUP_TOKENS, s.d), jnp.float32)
    starts, idx, gates, at = [], [], [], 0
    for f, n in zip(fronts, lengths):
        o = f[stream]
        u = put_j(u, o["u"], np.int32(at))
        i = np.array(o["idx"])
        i[n:] = -1                            # padding rows go nowhere
        idx.append(i)
        gates.append(np.asarray(o["gates"]))
        starts.append(at)
        at += o["u"].shape[0]
    idx, gates = np.concatenate(idx), np.concatenate(gates)
    flat = idx.reshape(-1)
    order = np.argsort(flat, kind="stable")
    bounds = np.searchsorted(flat[order], np.arange(s.experts + 1))
    acc = jnp.zeros((GROUP_TOKENS, s.d), jnp.float32)
    for e in range(s.experts):
        pairs = order[bounds[e]:bounds[e + 1]]
        if not pairs.size:
            continue
        width = common.bucket(pairs.size, lo=8)
        rows = np.full((width,), GROUP_TOKENS, np.int32)
        rows[:pairs.size] = pairs // s.top_k
        g = np.zeros((width,), np.float32)
        g[:pairs.size] = gates.reshape(-1)[pairs]
        acc = expert_j(acc, u, rows, g, experts, np.int32(e), stream)
    return [finish_j(f[stream]["x"], acc, np.int32(at0), f[stream]["shared"],
                     s.eps) for f, at0 in zip(fronts, starts)]


def _groups(sizes: Sequence[int]):
    """Consecutive (start, end) runs of sequences whose padded sizes fill
    at most ``GROUP_TOKENS`` rows: one expert pass serves each, and only
    its layer inputs are held beside every sequence's states."""
    start = 0
    while start < len(sizes):
        end, rows = start, 0
        while end < len(sizes) and rows + sizes[end] <= GROUP_TOKENS:
            rows += sizes[end]
            end += 1
        yield start, end
        start = end


def run(conf: dict, seed: int, probes: Sequence[Probe], *, yes: int, no: int,
        controls: Sequence[str] = (), max_rows: int = 64
        ) -> List[Dict[str, np.ndarray]]:
    """One readout dict per probe (arrays over its positions).

    Sequences are padded to power-of-two length buckets and readouts run
    ``max_rows`` rows at a time, as ``common`` does; an expert runs over a
    power-of-two count of rows.  So the compile cache holds every program
    after a few runs, whichever requests a seed samples."""
    s = Shape.of(conf)
    controls = tuple(controls)
    streams = ("ref",) + controls
    make_layer_j = jax.jit(make_layer, static_argnums=(1, 2))
    make_experts_j = jax.jit(make_experts, static_argnums=1)
    front_j = jax.jit(_front, static_argnums=(3, 4, 5, 6))
    jitted = (jax.jit(_put, donate_argnums=0),
              jax.jit(_expert, static_argnums=6, donate_argnums=0),
              jax.jit(_finish, static_argnums=4))
    scale_j = jax.jit(lambda x: x * math.sqrt(s.d))
    embed = jax.jit(lambda k: _normal(k, (s.vocab, s.d), 0.02))(
        top_keys(seed)[0])
    hidden = [{k: scale_j(v) for k, v in x.items()}
              for x in common.inputs(embed, probes, controls)]
    del embed
    lengths = [len(p.tokens) for p in probes]
    sizes = [common.bucket(n) for n in lengths]
    if max(sizes) > GROUP_TOKENS:
        raise ValueError(f"a {max(sizes)}-token probe outgrows the "
                         f"{GROUP_TOKENS}-row expert pass")
    for layer, kind in enumerate(s.types):
        key = layer_key(seed, conf, layer)
        dense = layer < s.dense_layers
        window = s.window if kind == SLIDING else 0
        w = make_layer_j(key, s, dense)
        if dense:
            for i, x in enumerate(hidden):      # one probe's states at a time
                hidden[i] = front_j(w, x, None, s, window, True, controls)
            del w
            continue
        bias, experts = expert_bias(key, s), make_experts_j(key, s)
        for start, end in _groups(sizes):
            fronts = []
            for i in range(start, end):
                fronts.append(front_j(w, hidden[i], bias, s, window, False,
                                      controls))
                hidden[i] = {}
            for name in streams:
                outs = _experts_over(fronts, lengths[start:end], experts,
                                     name, jitted, s)
                for i, (f, o) in enumerate(zip(fronts, outs)):
                    hidden[start + i][name] = o
                    del f[name]
            del fronts
        del w, experts
    picked = common.gather(hidden, probes)
    del hidden
    head = jax.jit(lambda k: _normal(k, (s.d, s.vocab), 0.02))(
        top_keys(seed)[1])
    return common.read_out(head, picked, probes, eps=s.eps, yes=yes, no=no,
                           controls=controls, max_rows=max_rows)
