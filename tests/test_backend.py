"""Continuous-batching decode backend: paged KV cache, per-step admission,
parity with the static path (results must be byte-identical), and the
satellite regressions (latency attribution, decode jit bucketing, result
ordering for duplicate / unknown request ids)."""
import copy

import numpy as np
import pytest

from repro.inference import tokenizer as tok
from repro.inference.api import make_engine_client
from repro.inference.backend import (COMPLETE, SCORE, EngineFailure, Request,
                                     Result)
from repro.inference.continuous import ContinuousBatcher, _Seq, supports
from repro.inference.engine import JaxInferenceEngine
from repro.inference.paged_kv import OutOfBlocks, PagedKVCache
from repro.configs import base as cfgs


@pytest.fixture(scope="module")
def static_engine():
    return JaxInferenceEngine("proxy-8b", smoke=True, max_seq=192,
                              backend="static", seed=0)


@pytest.fixture(scope="module")
def cont_engine():
    return JaxInferenceEngine("proxy-8b", smoke=True, max_seq=192,
                              backend="continuous", seed=0)


def _row(r: Result):
    return (r.request_id, r.kind, r.text, r.score, r.tokens_in,
            r.tokens_out, r.credits)


def _serve(engine, reqs):
    return [_row(r) for r in engine.submit_batch(copy.deepcopy(reqs))]


# ---------------------------------------------------------------------------
# paged KV allocator
# ---------------------------------------------------------------------------


def test_paged_kv_allocator(cont_engine):
    kv = PagedKVCache(cont_engine.model, block_size=16, num_blocks=8)
    assert kv.max_seq_blocks == 7          # block 0 is scratch
    assert kv.free_count == 7
    assert kv.blocks_for(1) == 1 and kv.blocks_for(16) == 1
    assert kv.blocks_for(17) == 2
    got = kv.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert kv.free_count == 4
    assert kv.can_alloc(4) and not kv.can_alloc(5)
    with pytest.raises(OutOfBlocks):
        kv.alloc(5)
    kv.free_blocks(got)
    assert kv.free_count == 7
    with pytest.raises(ValueError):
        kv.free_blocks(got)                # double free
    with pytest.raises(ValueError):
        kv.free_blocks([0])                # scratch block is not allocable


def test_paged_kv_scatter_gather_roundtrip(cont_engine):
    import jax
    import jax.numpy as jnp
    kv = PagedKVCache(cont_engine.model, block_size=8, num_blocks=6)
    b0, b1 = kv.alloc(2), kv.alloc(1)
    tables = jnp.asarray(np.array([[b0[0], b0[1]], [b1[0], 0]], np.int32))
    zero = jnp.zeros((2,), jnp.int32)
    counts = np.array([5, 3], np.int32)
    dense = kv.gather(kv.pool, tables, zero)
    rng = np.random.default_rng(0)

    def fill(x):
        return jnp.asarray(rng.standard_normal(x.shape)).astype(x.dtype)

    fake = {k: jax.tree.map(fill, dense[k]) for k in kv.pool}
    pool2 = kv.scatter(kv.pool, fake, tables, zero, jnp.asarray(counts), 8)
    got = kv.gather(pool2, tables, jnp.asarray(counts))
    for k in kv.pool:
        for g, f, a in zip(jax.tree.leaves(got[k]), jax.tree.leaves(fake[k]),
                           jax.tree.leaves(kv._axes[k])):
            g = np.moveaxis(np.asarray(g, np.float32), (a, a + 1), (0, 1))
            f = np.moveaxis(np.asarray(f, np.float32), (a, a + 1), (0, 1))
            for row, cnt in enumerate(counts):
                # written prefix persisted exactly; tails and the scratch
                # block stayed zero
                assert (g[row, :cnt] == f[row, :cnt]).all()
                assert (g[row, cnt:] == 0).all()


# ---------------------------------------------------------------------------
# parity: continuous == static, byte-identical
# ---------------------------------------------------------------------------


def _ragged_workload():
    reqs = []
    rid = 0
    for i, mt in enumerate([40, 4, 9, 2, 17, 4, 1, 6]):
        rid += 1
        reqs.append(Request(
            "w" * (3 + 11 * i) + f" complete case {i}", "proxy-8b", COMPLETE,
            max_tokens=mt, request_id=rid))
    for i in range(5):
        rid += 1
        reqs.append(Request(f"score this ragged row {i}" + "?" * (7 * i),
                            "proxy-8b", SCORE, request_id=rid))
    return reqs


def test_parity_ragged_lengths(static_engine, cont_engine):
    reqs = _ragged_workload()
    assert _serve(static_engine, reqs) == _serve(cont_engine, reqs)


def test_parity_midstream_admission(static_engine, cont_engine):
    # 3x more requests than slots: admission happens mid-stream as
    # earlier sequences retire, never at batch boundaries
    reqs = []
    for i in range(3 * cont_engine.max_batch):
        reqs.append(Request(f"queued request number {i} says hello",
                            "proxy-8b", COMPLETE,
                            max_tokens=24 if i % 5 == 0 else 3,
                            request_id=i + 1))
    before = cont_engine._batcher.admitted
    assert _serve(static_engine, reqs) == _serve(cont_engine, reqs)
    assert cont_engine._batcher.admitted - before == len(reqs)


def test_parity_chunked_prefill_long_prompt(static_engine, cont_engine):
    # prompts several chunks long: chunked decode-mode prefill must match
    # the static one-shot prefill.  Text, token counts and credits are
    # exact.  SCOREs get a tolerance: the two prefills reduce attention in
    # a different order, so the float32 logits can differ in their last
    # bits; 1e-6 is about 16 float32 ulps of a score near 0.5
    long = "the quick brown fox jumps over the lazy dog " * 4
    reqs = [Request(long + f"[{i}]", "proxy-8b",
                    SCORE if i % 2 else COMPLETE, max_tokens=6,
                    request_id=i + 1) for i in range(4)]
    static, cont = _serve(static_engine, reqs), _serve(cont_engine, reqs)
    no_score = lambda row: row[:3] + row[4:]
    assert [no_score(r) for r in static] == [no_score(r) for r in cont]
    for s, c in zip(static, cont):
        if s[1] == SCORE:
            assert c[3] == pytest.approx(s[3], rel=0, abs=1e-6)
        else:
            assert s[3] is None and c[3] is None


def test_parity_repeated_waves_reuse_pool(static_engine, cont_engine):
    # the paged pool is reused across serve() waves; stale KV from an
    # earlier wave must never leak into a later one
    reqs = _ragged_workload()[:6]
    first = _serve(cont_engine, reqs)
    kv = cont_engine._batcher.kv
    assert kv.free_count == kv.num_blocks - 1   # all blocks recycled
    assert first == _serve(cont_engine, reqs)
    assert first == _serve(static_engine, reqs)


def test_parity_through_client_eager_and_pipelined():
    outs = {}
    for backend in ("static", "continuous"):
        for pipelined in (False, True):
            client = make_engine_client(("proxy-8b",), seed=0,
                                        pipelined=pipelined, backend=backend)
            scores = client.filter_scores(
                [f"is item {i} in stock?" for i in range(5)],
                model="proxy-8b")
            texts = client.complete(
                [f"describe item {i}" for i in range(3)],
                model="proxy-8b", max_tokens=5)
            outs[(backend, pipelined)] = (scores.tolist(), texts)
    assert outs[("static", False)] == outs[("continuous", False)]
    assert outs[("static", True)] == outs[("continuous", True)]
    assert outs[("static", False)] == outs[("static", True)]


# ---------------------------------------------------------------------------
# retirement / admission mechanics
# ---------------------------------------------------------------------------


def test_eos_retires_before_max_tokens(cont_engine):
    b = ContinuousBatcher(cont_engine, block_size=16)
    blocks = b.kv.alloc(1)
    seq = _Seq(req=Request("x", "proxy-8b", COMPLETE, max_tokens=64,
                           request_id=1),
               index=0, enc=[tok.BOS_ID, 5, 6], slot=0, blocks=blocks,
               state="decode", cur=tok.EOS_ID)
    active = [seq] + [None] * (b.slots - 1)
    results = [None]
    free_before = b.kv.free_count
    b._consume(seq, active, results, t0=0.0)
    assert results[0] is not None and results[0].tokens_out == 1
    assert active[0] is None                       # slot freed
    assert b.retired_eos == 1
    assert b.kv.free_count == free_before + 1      # blocks recycled


def test_oversized_request_raises(cont_engine):
    b = cont_engine._batcher
    need = (b.kv.max_seq_blocks + 1) * b.block_size
    reqs = [Request("p", "proxy-8b", COMPLETE, max_tokens=need,
                    request_id=1)]
    with pytest.raises(EngineFailure):
        cont_engine.submit_batch(reqs)


def test_unsupported_arch_falls_back_to_static():
    cfg = cfgs.get_smoke_config("recurrentgemma-9b")
    assert not supports(cfg)
    eng = JaxInferenceEngine("recurrentgemma-9b", smoke=True, backend="auto")
    assert eng.backend == "static"
    with pytest.raises(ValueError):
        JaxInferenceEngine("recurrentgemma-9b", smoke=True,
                           backend="continuous")


def test_supported_arch_defaults_to_continuous(cont_engine):
    assert supports(cont_engine.cfg)
    eng = JaxInferenceEngine("proxy-8b", smoke=True, backend="auto")
    assert eng.backend == "continuous"


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------


def test_latency_attributed_per_request(cont_engine):
    # one long tail + many short completions: the shorts retire early and
    # must not inherit the batch-drain latency (no smearing)
    reqs = [Request(f"req {i}", "proxy-8b", COMPLETE,
                    max_tokens=64 if i == 0 else 2, request_id=i + 1)
            for i in range(6)]
    res = cont_engine.submit_batch(copy.deepcopy(reqs))
    lats = [r.latency_s for r in res]
    assert len(set(lats)) > 1, "per-request latency is smeared"
    assert res[0].latency_s == max(lats)   # the long tail finishes last
    assert all(l <= res[0].latency_s for l in lats)


def test_static_latency_not_smeared(static_engine):
    reqs = [Request(f"req {i}", "proxy-8b", COMPLETE,
                    max_tokens=48 if i == 0 else 2, request_id=i + 1)
            for i in range(4)]
    res = static_engine.submit_batch(copy.deepcopy(reqs))
    lats = [r.latency_s for r in res]
    assert res[0].latency_s == max(lats)
    assert min(lats) < max(lats)


def test_decode_jit_cache_bucketed(static_engine):
    # decode step functions are keyed on the bucketed batch, so serving
    # B=3 then B=4 compiles exactly one decode entry
    def decode_keys():
        return {k for k in static_engine._jit_cache if k[0] == "decode"}

    counts = []
    for B in (3, 4):
        reqs = [Request("same prompt here", "proxy-8b", COMPLETE,
                        max_tokens=3, request_id=i + 1) for i in range(B)]
        static_engine.submit_batch(reqs)
        counts.append(len(decode_keys()))
    assert counts[0] == counts[1], "B=3 and B=4 must share one decode key"


def test_duplicate_request_ids_stable_order(static_engine, cont_engine):
    for eng in (static_engine, cont_engine):
        reqs = [Request("first of a duplicated id", "proxy-8b", SCORE,
                        request_id=9),
                Request("second of a duplicated id", "proxy-8b", SCORE,
                        request_id=9)]
        res = eng.submit_batch(copy.deepcopy(reqs))
        solo = [eng.submit_batch([copy.deepcopy(r)])[0].score for r in reqs]
        assert [r.score for r in res] == solo  # submission order kept


def test_unknown_request_id_raises(static_engine):
    reqs = [Request("p", "proxy-8b", SCORE, request_id=1)]
    bogus = [Result(99, "proxy-8b", SCORE, score=0.5)]
    with pytest.raises(EngineFailure):
        static_engine._restore_order(reqs, bogus)


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


def test_backend_stats_and_roofline(cont_engine):
    reqs = _ragged_workload()[:4]
    cont_engine.submit_batch(copy.deepcopy(reqs))
    stats = cont_engine.backend_stats()
    assert stats["backend"] == "continuous"
    assert stats["prefill_steps"] > 0 and stats["decode_steps"] > 0
    assert stats["kv_peak_blocks"] > 0
    # the CPU has no published peaks: no bound is divided out for it
    with pytest.raises(ValueError, match="no published peaks"):
        cont_engine.backend_roofline()

    # the static backend reports too, without batcher telemetry
    st = JaxInferenceEngine("proxy-8b", smoke=True, backend="static")
    assert st.backend_stats()["backend"] == "static"
    assert st.backend_roofline() == {}
