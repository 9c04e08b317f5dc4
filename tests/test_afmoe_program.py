"""The program pieces an AFMoE model (Trinity) brings: the sigmoid router
with a selection-only bias, the dropless grouped expert layer, sliding
windows over a full (paged) cache, the flash-decode kernel's window, and
the window counters of the continuous batcher; plus a pin of what the
dense Qwen3 steps compile to."""
import dataclasses
import hashlib
import json
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cfgs
from repro.inference.backend import SCORE, Request
from repro.inference.engine import JaxInferenceEngine
from repro.kernels.decode_attention.kernel import decode_attention_kernel
from repro.kernels.decode_attention.ops import flash_decode
from repro.models import attention, blocks, model_zoo

REPO = Path(__file__).resolve().parents[1]


def _rand(key, shape, scale=1.0):
    return jax.random.normal(key, shape, jnp.float32) * scale


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------


def _moe_cfg(**kw):
    m = cfgs.MoEConfig(num_experts=8, num_experts_per_tok=2, expert_d_ff=16,
                       score_func="sigmoid", selection_bias=True,
                       route_scale=2.826)
    return dataclasses.replace(m, **kw)


def test_selection_bias_moves_the_choice_not_the_gates():
    m = _moe_cfg()
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = _rand(ks[0], (64, 32))
    router = {"w": _rand(ks[1], (32, 8), 32 ** -0.5),
              "bias": jnp.zeros((8,), jnp.float32)}
    scores, idx0, g0 = blocks._select(router, x, m)
    # a bias that lifts expert 5 far above the rest: it is chosen for every
    # token, with the gate its own score gives, not one the bias lifts
    router["bias"] = jnp.zeros((8,), jnp.float32).at[5].set(10.0)
    _, idx1, g1 = blocks._select(router, x, m)
    assert bool(jnp.all(jnp.any(idx1 == 5, axis=-1)))
    assert not bool(jnp.all(idx0 == idx1))
    chosen = jnp.take_along_axis(scores, idx1, axis=-1)
    np.testing.assert_allclose(
        g1, chosen / chosen.sum(-1, keepdims=True) * 2.826, rtol=1e-6)
    for g in (g0, g1):                 # gates sum to route_scale
        np.testing.assert_allclose(g.sum(-1), 2.826, rtol=1e-6)
    np.testing.assert_allclose(scores, jax.nn.sigmoid(x @ router["w"]),
                               rtol=1e-5)


def test_softmax_router_is_unchanged():
    """Without the new options the router is the softmax top-k, its gates
    renormalized, as before."""
    m = cfgs.MoEConfig(num_experts=6, num_experts_per_tok=2, expert_d_ff=16,
                       padded_num_experts=8)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = _rand(ks[0], (16, 32))
    router = {"w": _rand(ks[1], (32, 8))}
    probs, idx, gates = blocks._select(router, x, m)
    logits = jnp.where(jnp.arange(8) < 6, x @ router["w"], -1e30)
    want_v, want_i = jax.lax.top_k(jax.nn.softmax(logits, -1), 2)
    np.testing.assert_array_equal(idx, want_i)
    np.testing.assert_allclose(gates, want_v / want_v.sum(-1, keepdims=True),
                               rtol=1e-6)
    assert bool(jnp.all(idx < 6))


# ---------------------------------------------------------------------------
# the dropless grouped expert layer
# ---------------------------------------------------------------------------


def _experts(key, E, d, f):
    ks = jax.random.split(key, 3)
    return {"wi": _rand(ks[0], (E, d, f), d ** -0.5),
            "wg": _rand(ks[1], (E, d, f), d ** -0.5),
            "wo": _rand(ks[2], (E, f, d), f ** -0.5)}


def _dense_experts(x, idx, gates, w):
    """Every expert on every token, masked: the plain definition."""
    h = jnp.einsum("td,edf->etf", x, w["wi"])
    g = jnp.einsum("td,edf->etf", x, w["wg"])
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * h, w["wo"])
    onehot = jax.nn.one_hot(idx, w["wi"].shape[0])        # [T, k, E]
    weight = jnp.einsum("tk,tke->et", gates, onehot)
    return jnp.einsum("et,etd->td", weight, y)


def test_grouped_experts_match_the_plain_definition():
    E, d, f, T, k = 8, 128, 128, 40, 2
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = _rand(ks[0], (T, d))
    w = _experts(ks[1], E, d, f)
    scores = jax.nn.sigmoid(_rand(ks[2], (T, E)))
    gates, idx = jax.lax.top_k(scores, k)
    got = blocks._moe_grouped(x, idx, gates, w)
    want = _dense_experts(x, idx, gates, w)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def _one_layer_moe(T=48):
    """A MoE layer whose bias sends every token to experts 2 and 5: far
    over a train-mode capacity of ceil(2*T*1.25/8) tokens an expert."""
    cfg = dataclasses.replace(cfgs.get_smoke_config("trinity-mini"),
                              dtype="float32")
    m = cfg.moe
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    p = blocks.moe_init(ks[0], cfg, jnp.float32)
    p["router"]["bias"] = jnp.zeros((m.num_experts,)).at[2].set(9).at[5].set(9)
    x = _rand(ks[1], (1, T, cfg.d_model))
    return cfg, p, x


def test_inference_is_dropless_and_train_keeps_its_capacity():
    cfg, p, x = _one_layer_moe()
    m = cfg.moe
    assert blocks._capacity(m, 4096) == 1280           # 2*4096*1.25/8
    x2d = x[0]
    _, idx, gates = blocks._select(p["router"], x2d, m)
    assert bool(jnp.all(jnp.sort(idx, -1) == jnp.array([2, 5])))
    want = (_dense_experts(x2d, idx, gates, p["experts"])
            + blocks.apply_mlp(p["shared"], x2d))
    pos = jnp.arange(x.shape[1])[None]
    for mode in ("prefill", "decode"):
        y, _ = blocks.moe_apply(p, x, blocks.Ctx(cfg, mode, pos))
        np.testing.assert_allclose(y[0], want, rtol=2e-4, atol=2e-4)
    y, _ = blocks.moe_apply(p, x, blocks.Ctx(cfg, "train", pos))
    assert float(jnp.max(jnp.abs(y[0] - want))) > 1e-2    # overflow dropped


def test_grouped_experts_split_over_shards_add_up():
    """Each expert-parallel shard runs its own slice of experts over the
    pairs routed to it; the shards' sums are the whole layer's."""
    E, d, f, T, k = 8, 64, 32, 24, 2
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = _rand(ks[0], (T, d))
    w = _experts(ks[1], E, d, f)
    gates, idx = jax.lax.top_k(jax.nn.sigmoid(_rand(ks[2], (T, E))), k)
    whole = blocks._moe_grouped(x, idx, gates, w)
    for shards in (2, 4):
        n = E // shards
        parts = [blocks._moe_grouped(
            x, idx, gates, {a: v[s * n:(s + 1) * n] for a, v in w.items()},
            first=s * n) for s in range(shards)]
        np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-5)


def test_mesh_path_is_dropless_in_inference():
    """With the shard context's mesh armed, inference runs the grouped
    experts inside the shard map and equals the unsharded layer."""
    from jax.sharding import Mesh
    cfg, p, x = _one_layer_moe()
    pos = jnp.arange(x.shape[1])[None]
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    want, _ = blocks.moe_apply(p, x, blocks.Ctx(cfg, "prefill", pos))
    with mesh:
        got, aux = blocks.moe_apply(
            p, x, blocks.Ctx(cfg, "prefill", pos,
                             mesh_axes=(mesh, ("data",), "model")))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(aux) == 0.0


def test_moe_chunked_prefill_equals_one_shot_prefill():
    """Dropless in every inference mode: the static path's one-shot prefill
    and the continuous path's 32-token chunks give one MoE model the same
    SCOREs.  In float32 they differ only by the order of sums; a prefill
    that dropped tokens over an expert's capacity moved them by ~1e-2."""
    cfg = dataclasses.replace(cfgs.get_smoke_config("qwen2-moe-a2.7b"),
                              dtype="float32")
    rng = np.random.default_rng(3)
    prompts = ["".join(chr(97 + c) for c in rng.integers(0, 26, n))
               for n in (12, 45, 70, 130)]
    got = {}
    for backend in ("static", "continuous"):
        eng = JaxInferenceEngine(cfg, max_seq=192, backend=backend, seed=4)
        reqs = [Request(p, cfg.name, SCORE, request_id=i + 1)
                for i, p in enumerate(prompts)]
        got[backend] = np.asarray([r.score for r in eng.submit_batch(reqs)])
    logit = {k: np.log(v) - np.log1p(-v) for k, v in got.items()}
    np.testing.assert_allclose(logit["static"], logit["continuous"],
                               atol=1e-5)


# ---------------------------------------------------------------------------
# sliding windows
# ---------------------------------------------------------------------------


def _one_layer(kind):
    return cfgs.ModelConfig(
        name="w", family="dense", num_layers=1, d_model=32, num_heads=4,
        num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=300, qk_norm=True,
        attention_window=8, attn_out_gate=True, rope_local_only=True,
        sandwich_norm=True, period=(kind,), dtype="float32")


@pytest.mark.parametrize("paged", [False, True])
def test_a_token_outside_the_window_reaches_only_the_full_layer(paged):
    """Changing the first token leaves a sliding layer's output at position
    20 (window 8) as it was and changes the full layer's; on the static
    path (train-mode forward) and on the paged path (a decode-mode chunk
    over a full cache, as the continuous batcher runs it)."""
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 24), 4, 260)
    other = toks.at[0, 0].set(toks[0, 0] + 1)
    for kind, same in ((cfgs.LOCAL_ATTN, True), (cfgs.ATTN, False)):
        model = model_zoo.build(_one_layer(kind))
        params = model.init_params(jax.random.PRNGKey(6))

        def out(t):
            if not paged:
                return model.apply(params, {"tokens": t}, mode="train",
                                   remat=False)["hidden"][0, 20]
            cache = model.init_cache(1, 32, paged=True)
            cache["len"] = jnp.zeros((1,), jnp.int32)
            pos = jnp.arange(24, dtype=jnp.int32)[None]
            return model.apply(params, {"tokens": t, "positions": pos},
                               mode="decode", cache=cache,
                               paged=True)["hidden"][0, 20]
        a, b = np.asarray(out(toks)), np.asarray(out(other))
        assert np.array_equal(a, b) == same, kind


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_flash_decode_window_reads_only_the_window(impl):
    B, Smax, H, KV, hd, W = 3, 1024, 4, 2, 64, 300
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = _rand(ks[0], (B, 1, H, hd))
    kc = _rand(ks[1], (B, Smax, KV, hd))
    vc = _rand(ks[2], (B, Smax, KV, hd))
    lengths = jnp.asarray([1000, 250, 640], jnp.int32)
    got = flash_decode(q, kc, vc, lengths, impl=impl, window=W)
    # the dense masked attention the model runs for a chunk of one token
    want = attention.decode_attention(q, kc, vc, lengths, window=W)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # what lies before each row's window does not reach it
    junk = kc.at[:, :300].set(100.0)
    again = flash_decode(q, junk, vc, lengths, impl=impl, window=W)
    np.testing.assert_allclose(again[0], got[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(again[2], got[2], rtol=1e-6, atol=1e-6)
    assert not np.allclose(again[1], got[1])     # row 1 sees position 0


# The jaxpr of the flash-decode kernel without a window, at proxy-8b's
# decode widths, as it was before the window was added: window=None
# builds the very kernel it did.
KERNEL_JAXPR_SHA256 = (
    "171cae51fa782a1ed85dc9341e167bf39a3cec52b29716e7c845bea555e9b4ad")


def test_flash_decode_without_a_window_is_the_kernel_it_was():
    B, H, KV, HD, S = 8, 32, 8, 128, 2048
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((B, H, HD), jnp.bfloat16), ((B, KV, S, HD), jnp.bfloat16),
        ((B, KV, S, HD), jnp.bfloat16), ((B,), jnp.int32))]
    text = str(jax.make_jaxpr(lambda q, k, v, n: decode_attention_kernel(
        q, k, v, n, interpret=False))(*args))
    assert hashlib.sha256(text.encode()).hexdigest() == KERNEL_JAXPR_SHA256


# ---------------------------------------------------------------------------
# the window counters
# ---------------------------------------------------------------------------


def test_window_counters_count_blocks_before_the_window():
    """One 100-token SCORE through 32-token chunks (block 32, window 8 in
    the five sliding layers of the smoke Trinity).  Steps start at
    positions 0, 32, 64, 96 and gather 1, 2, 4, 4 blocks; the first
    position their first token sees is -7, 25, 57, 89, so 0, 0, 1, 2
    blocks lie wholly before it."""
    eng = JaxInferenceEngine("trinity-mini", smoke=True, max_seq=128,
                             backend="continuous", seed=1)
    eng.submit_batch([Request("x" * 99, "trinity-mini", SCORE,
                              request_id=1)])
    st = eng.backend_stats()
    assert st["prefill_steps"] == 4
    assert st["window_blocks"] == 5 * (1 + 2 + 4 + 4)
    assert st["window_blocks_outside"] == 5 * (0 + 0 + 1 + 2)


def test_window_counters_stay_zero_without_sliding_layers():
    eng = JaxInferenceEngine("proxy-8b", smoke=True, max_seq=128,
                             backend="continuous", seed=1)
    eng.submit_batch([Request("x" * 99, "proxy-8b", SCORE, request_id=1)])
    st = eng.backend_stats()
    assert st["window_blocks"] == st["window_blocks_outside"] == 0


# ---------------------------------------------------------------------------
# Qwen3's steps are the programs they were
# ---------------------------------------------------------------------------

# sha256 of the StableHLO (no debug info) of qwen3-8b's continuous-batching
# steps at a 4-block gather, as lowered once the pool holds each layer's
# keys and values in one interleaved leaf: the prefill step writes and reads
# that pool in place (tests/test_paged_prefill.py holds it to the
# gather-run-scatter step it replaced), and the decode step gathers it
# with one ``jnp.take`` over the layers' blocks flattened, in "clip" mode
# (tests/test_tpu_compile.py holds that gather to no loop over blocks).
QWEN3_STEPS_SHA256 = {
    "prefill": "4c527458b0388b6475915118d68d5906002ca971277d488c8878a553a5c09a0a",
    "decode": "a5826cc8f8b82c6f4ec477b47b510fbf2595fa0cc7c80625969db7c1a03dd299",
}


def test_qwen3_steps_lower_to_the_programs_they_were():
    import importlib.util
    from repro.inference.continuous import ContinuousBatcher
    from repro.inference.paged_kv import PagedKVCache
    spec = importlib.util.spec_from_file_location(
        "qwen3_arch", REPO / "chipbench/reference/qwen3.py")
    qwen3 = importlib.util.module_from_spec(spec)
    sys.modules["qwen3_arch"] = qwen3       # its dataclasses look it up
    spec.loader.exec_module(qwen3)
    conf = json.loads((REPO / "chipbench/configs/qwen3-8b.json").read_text())
    model = model_zoo.build(qwen3.model_config(conf, "qwen3-8b"))
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    kv = PagedKVCache(model, block_size=32, num_blocks=2)
    pool = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape[:1] + (545,) + x.shape[2:], x.dtype), kv.pool)
    batcher = types.SimpleNamespace(model=model, kv=kv, decode_impl="auto",
                                    paged_impl="reference")

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    for name, fn, last in (("prefill", ContinuousBatcher._prefill_fn, 32),
                           ("decode", ContinuousBatcher._decode_fn, 1)):
        def step(*args, fn=fn):
            return fn(batcher, *args)
        step.__name__ = f"_{name}_fn"
        text = jax.jit(step, donate_argnums=(1,)).lower(
            params, pool, i32(8, 4), i32(8), i32(8), i32(8, last)).as_text(
                debug_info=False)
        assert (hashlib.sha256(text.encode()).hexdigest()
                == QWEN3_STEPS_SHA256[name]), name
