"""The float32 reference against the engine, at a size the CPU holds.

The engine is the program's own ``JaxInferenceEngine`` on its continuous
backend: SCOREs through chunked prefill into the paged KV cache, COMPLETE
tokens through prefill and then decode steps over the same cache.  The
reference draws its weights from the seed by itself and runs the plain
forward pass.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import correct, harness, tokenizer  # noqa: E402
from chipbench.reference import qwen3  # noqa: E402
from chipbench.tests import tiny  # noqa: E402

SEEDS = (3, 2**31 + 5, 77)
# Gaps of the served bfloat16 engine from the float32 reference.  The
# engine rounds activations to bfloat16 (8 significant bits, 2^-9
# relative) at every projection of its two layers; the tiny model's
# logits stay below ~1 in size, so rounding moves them by a few 1e-3
# (up to 3.5e-3 over these seeds).  2^-7 = 7.8e-3 leaves twice that,
# while one wrong token, a dropped cache page or a stale position moves
# a logit by the logits' own spread, ~1e-1.
GAP_TOL = 2.0 ** -7
# A lower-precision control (int8 or fp8 matrix products, weights scaled
# per channel and activations per token) reads at
# least one of its gaps this many times wider than the served engine's,
# on every seed: the separation the chip's limits are set inside (a limit
# needs the control's reading at three times the program's or more).
CONTROL_RATIO = 3.0


@pytest.fixture(scope="module")
def served():
    """Per seed: the engine's served SCOREs and COMPLETE tokens of prompts
    that span several prefill chunks and cache pages."""
    from repro.inference.backend import COMPLETE, SCORE, Request
    out = {}
    conf = tiny.conf()
    rng = np.random.default_rng(0)
    prompts = ["".join(chr(97 + c) for c in rng.integers(0, 26, n))
               for n in (5, 31, 40, 97, 150, 230)]
    for seed in SEEDS:
        engine = harness.build_engine(qwen3, conf, "tiny", seed,
                                      jax.devices()[0])
        rec = harness.Recorder(engine)
        reqs = [Request(p, "tiny", SCORE) for p in prompts] + [
            Request(p, "tiny", COMPLETE, max_tokens=12) for p in prompts]
        for i, r in enumerate(reqs):
            r.request_id = i + 1
        engine.submit_batch(reqs)
        out[seed] = (engine, list(rec.served()))
    return conf, out


def test_weights_are_the_engines(served):
    conf, runs = served
    s = qwen3.Shape.of(conf)
    for seed, (engine, _) in runs.items():
        p = engine.params
        keys = qwen3.layer_keys(seed, s.layers)
        for i in range(s.layers):
            w = qwen3.make_layer(keys[i], s)
            got = {"wq": p["periods"]["b0"]["attn"]["wq"][i],
                   "wk": p["periods"]["b0"]["attn"]["wk"][i],
                   "wv": p["periods"]["b0"]["attn"]["wv"][i],
                   "wo": p["periods"]["b0"]["attn"]["wo"][i],
                   "up": p["periods"]["b0"]["mlp"]["wi"][i],
                   "gate": p["periods"]["b0"]["mlp"]["wg"][i],
                   "down": p["periods"]["b0"]["mlp"]["wo"][i]}
            for k, v in got.items():
                _same_draws(v, w[k])
        top = qwen3.top_keys(seed)
        _same_draws(p["embed"]["w"], qwen3._normal(top[0], (s.vocab, s.d), 0.02))
        _same_draws(p["lm_head"]["w"],
                    qwen3._normal(top[1], (s.d, s.vocab), 0.02))


def _same_draws(engine_w, ref_w):
    """The same random draws, rounded to bfloat16.  The engine draws all
    layers in one vmapped program and the reference one layer at a time;
    the compiler may fuse the normal's transform differently in the two,
    which moves a rare element (about 1 in 10^4) by one bfloat16 ulp."""
    a = np.asarray(engine_w, np.float32)
    b = np.asarray(ref_w, np.float32)
    assert a.shape == b.shape
    differ = a != b
    assert differ.mean() <= 1e-3
    ulp = np.abs(b[differ]) * 2.0 ** -7
    assert np.all(np.abs(a - b)[differ] <= ulp)


def _gaps(conf, seed, served, controls=()):
    probes, owner = correct.probes(served, conf["serving"]["max_seq"])
    reads = qwen3.run(conf, seed, probes, yes=tokenizer.YES_ID,
                      no=tokenizer.NO_ID, controls=controls)
    per = correct.gaps(served, owner, reads, controls)
    return (correct.numbers(per["served"], {}),
            {c: correct.numbers(per[c], {}) for c in controls})


def test_chunked_prefill_and_decode_agree_with_reference(served):
    conf, runs = served
    for seed, (_, items) in runs.items():
        got, _ = _gaps(conf, seed, items)
        assert set(got) == {"score_gap", "token_gap"}
        for name, value in got.items():
            assert value <= GAP_TOL, (seed, name, value)


@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_lower_precision_control_reads_wider_gaps(served, control):
    conf, runs = served
    for seed, (_, items) in runs.items():
        got, ctl = _gaps(conf, seed, items, (control,))
        ctl = ctl[control]
        assert any(ctl[n] >= CONTROL_RATIO * got[n] for n in got), (
            seed, got, ctl)
