"""The AFMoE float32 reference against the engine, at a size the CPU holds.

A tiny Trinity: width 64, 4 query and 2 KV heads of 16, 8 experts (top-2)
plus a shared one, a window of 8, two leading dense layers and one period
(sliding, full, sliding, sliding).  The engine is the program's own
``JaxInferenceEngine``: SCOREs through one-shot static prefill (the
windowed layers' ring caches) and through chunked prefill into the paged
KV cache past the window, COMPLETE tokens through decode steps past the
window (``flash_decode``'s off-TPU reference with its window).

The parity runs the program in float32 over the very weights the
bfloat16 engine draws (cast up), so program and reference differ only in
the order of their sums.  In bfloat16 they cannot be held tight at this
size: the router picks 2 of 8 experts by their scores, and bfloat16
rounding flips near-tied picks, which moves a logit by ~0.1 (the served
bfloat16 engine read score gaps of 0.03-0.09 here).  The chip's check
holds the bfloat16 program to limits set against the lower-precision
controls.
"""
import dataclasses
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import correct, harness, tokenizer  # noqa: E402
from chipbench.reference import afmoe  # noqa: E402
from chipbench.tests import tiny  # noqa: E402
from chipbench.tests.test_chipbench_reference import _same_draws  # noqa: E402

TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512,
        "sliding_window": 8}
SEED = 2**31 + 11
# The float32 program against the float32 reference over the same
# weights: they read 2.4e-7 apart (sums in another order).  1e-4 leaves
# 400 times that, while a bfloat16 reference (activations rounded to
# 2^-9), a dropped window or a dropped output gate moves a logit by 1e-2
# or more (test_parity_catches_a_changed_model).
GAP_TOL = 1e-4


def conf(max_seq: int = 256) -> dict:
    c = json.loads((tiny.REPO / "chipbench/configs/trinity-mini.json")
                   .read_text())
    c.update(TINY, serving={"max_seq": max_seq})
    return c


def _prompts():
    rng = np.random.default_rng(1)
    return ["".join(chr(97 + c) for c in rng.integers(0, 26, n))
            for n in (5, 31, 40, 97, 150, 230)]


def _engine(c, backend, cfg=None, params=None):
    """A float32 engine serving ``cfg`` (the configuration's by default)
    over ``params`` (the bfloat16 engine's draws, cast up, by default)."""
    from repro.inference.engine import JaxInferenceEngine
    cfg = cfg or afmoe.model_config(dict(c, torch_dtype="float32"), "tiny")
    eng = JaxInferenceEngine(cfg, seed=SEED, device=jax.devices()[0],
                             max_seq=c["serving"]["max_seq"], backend=backend)
    if params is None:
        params = harness.build_engine(afmoe, c, "tiny", SEED,
                                      jax.devices()[0]).params
    eng.params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    return eng


def _serve(eng):
    from repro.inference.backend import COMPLETE, SCORE, Request
    rec = harness.Recorder(eng) if eng._batcher is not None else None
    reqs = [Request(p, "tiny", SCORE) for p in _prompts()]
    if rec is not None:
        reqs += [Request(p, "tiny", COMPLETE, max_tokens=12)
                 for p in _prompts()]
    for i, r in enumerate(reqs):
        r.request_id = i + 1
    out = eng.submit_batch(reqs)
    if rec is not None:
        return list(rec.served())
    return list(zip(reqs, out, [None] * len(out)))


def _gaps(c, served, controls=()):
    probes, owner = correct.probes(served, c["serving"]["max_seq"])
    reads = afmoe.run(c, SEED, probes, yes=tokenizer.YES_ID,
                      no=tokenizer.NO_ID, controls=controls)
    per = correct.gaps(served, owner, reads, controls)
    return (correct.numbers(per["served"], {}),
            {k: correct.numbers(per[k], {}) for k in controls})


@pytest.fixture(scope="module")
def bf16():
    c = conf()
    return c, harness.build_engine(afmoe, c, "tiny", SEED, jax.devices()[0])


def test_model_config_is_the_published_pattern():
    full = json.loads((tiny.REPO / "chipbench/configs/trinity-mini.json")
                      .read_text())
    pub = dict(full, num_hidden_layers=32,
               layer_types=full["published"]["layer_types"])
    from repro.configs import base
    cfg = afmoe.model_config(pub, "trinity-mini")
    assert cfg == dataclasses.replace(base.get_config("trinity-mini"),
                                      dtype="bfloat16")
    cut = afmoe.model_config(full, "trinity-mini")
    assert cut.block_pattern == (base.LOCAL_ATTN,) * 3 + (base.ATTN,) + (
        base.LOCAL_ATTN,) * 2
    assert cut.lead == (base.LOCAL_ATTN,) * 2 and cut.tail == ()
    assert cut.param_count() == 4_306_554_880


def test_weights_are_the_engines(bf16):
    c, eng = bf16
    s = afmoe.Shape.of(c)
    p = eng.params
    for layer in range(s.layers):
        key = afmoe.layer_key(SEED, c, layer)
        dense = layer < s.dense_layers
        w = afmoe.make_layer(key, s, dense)
        if dense:
            lp = p["lead"][f"l{layer}"]
        else:
            lp = jax.tree.map(lambda x: x[0],
                              p["periods"][f"b{layer - s.dense_layers}"])
        for k in ("wq", "wk", "wv", "wo", "wgate"):
            _same_draws(lp["attn"][k], w[k])
        if dense:
            got = {"up": lp["mlp"]["wi"], "gate": lp["mlp"]["wg"],
                   "down": lp["mlp"]["wo"]}
        else:
            m = lp["moe"]
            np.testing.assert_array_equal(m["router"]["w"], w["router"])
            np.testing.assert_allclose(m["router"]["bias"],
                                       afmoe.expert_bias(key, s), rtol=1e-6)
            ex = afmoe.make_experts(key, s)
            got = {"s_up": m["shared"]["wi"], "s_gate": m["shared"]["wg"],
                   "s_down": m["shared"]["wo"]}
            for k, name in (("wi", "up"), ("wg", "gate"), ("wo", "down")):
                _same_draws(m["experts"][k], ex[name])
        for k, v in got.items():
            _same_draws(v, w[k])
    top = afmoe.top_keys(SEED)
    _same_draws(p["embed"]["w"], afmoe._normal(top[0], (s.vocab, s.d), 0.02))
    _same_draws(p["lm_head"]["w"], afmoe._normal(top[1], (s.d, s.vocab), 0.02))


@pytest.fixture(scope="module")
def paged(bf16):
    """The float32 continuous engine's served SCOREs (chunked prefill past
    the window) and COMPLETE tokens (decode steps past the window)."""
    c, eng = bf16
    return _serve(_engine(c, "continuous", params=eng.params))


def test_one_shot_static_prefill_agrees_with_reference(bf16):
    c, eng = bf16
    got, _ = _gaps(c, _serve(_engine(c, "static", params=eng.params)))
    assert set(got) == {"score_gap"}
    assert got["score_gap"] <= GAP_TOL, got


def test_chunked_prefill_and_decode_past_the_window_agree(bf16, paged):
    c, _ = bf16
    assert max(len(r.prompt) for r, _, _ in paged) > 4 * c["sliding_window"]
    got, _ = _gaps(c, paged)
    assert set(got) == {"score_gap", "token_gap"}
    for name, value in got.items():
        assert value <= GAP_TOL, (name, value)


@pytest.mark.parametrize("change", ["bf16", "no_window", "no_gate"])
def test_parity_catches_a_changed_model(bf16, change):
    """The tolerance fails a bfloat16 program, a program whose sliding
    layers see every position, and one without the output gate."""
    c, eng = bf16
    cfg = afmoe.model_config(dict(c, torch_dtype="float32"), "tiny")
    params = eng.params
    if change == "bf16":
        served = _serve(eng)
    else:
        if change == "no_window":
            cfg = dataclasses.replace(cfg, attention_window=10**6)
        else:
            cfg = dataclasses.replace(cfg, attn_out_gate=False)
        served = _serve(_engine(c, "continuous", cfg, params))
    got, _ = _gaps(c, served)
    assert max(got.values()) > 10 * GAP_TOL, got


@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_lower_precision_control_reads_wider_gaps(bf16, paged, control):
    c, _ = bf16
    got, ctl = _gaps(c, paged, (control,))
    assert max(ctl[control].values()) > 10 * GAP_TOL, ctl


def test_counts_follow_the_window_and_the_experts():
    c = conf()
    s = afmoe.Shape.of(c)
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim

    def brute(start, n):
        total = 0.0
        for i, t in enumerate(s.types):
            dense = i < s.dense_layers
            ffn = (6 * s.d * s.d_ff if dense else
                   2 * s.d * s.experts
                   + 6 * s.d * s.expert_d_ff * (s.top_k + 1))
            for pos in range(start, start + n):
                keys = (min(pos + 1, s.window) if t == afmoe.SLIDING
                        else pos + 1)
                total += (2 * s.d * (2 * q + 2 * kv) + 2 * q * s.d + ffn
                          + 4 * q * keys)
        return total

    for start, n in ((0, 5), (0, 8), (3, 9), (7, 1), (20, 13), (0, 40)):
        assert s.extend(start, n) == pytest.approx(brute(start, n))
    flops, nbytes = s.decode_attention(20)
    keys = 5 * 8 + 20                      # five sliding layers, one full
    assert flops == 4 * q * keys
    assert nbytes == 2 * keys * kv * afmoe.BF16
    assert s.expert_flops(10) == 10 * 6 * s.d * s.expert_d_ff
    assert s.expert_bytes() == 3 * s.experts * s.d * s.expert_d_ff * 2
