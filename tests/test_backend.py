"""Continuous-batching decode backend: paged KV cache, per-step admission,
parity with the static path (results must be byte-identical), and the
satellite regressions (latency attribution, decode jit bucketing, result
ordering for duplicate / unknown request ids)."""
import copy

import numpy as np
import pytest
from _loop_join import join_mid_loop

from repro.inference import tokenizer as tok
from repro.inference.api import make_engine_client
from repro.inference.backend import (COMPLETE, SCORE, EngineFailure, Request,
                                     Result)
from repro.inference.continuous import (ContinuousBatcher, _Caller, _Seq,
                                        supports)
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
    caller = _Caller(1, t0=0.0)
    seq = _Seq(req=Request("x", "proxy-8b", COMPLETE, max_tokens=64,
                           request_id=1),
               index=0, enc=[tok.BOS_ID, 5, 6], slot=0, blocks=blocks,
               state="decode", cur=tok.EOS_ID, caller=caller)
    active = [seq] + [None] * (b.slots - 1)
    results = caller.results
    free_before = b.kv.free_count
    b._consume(seq, active)
    assert results[0] is not None and results[0].tokens_out == 1
    assert caller.left == 0                        # its caller is woken
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
# shared step loop: concurrent callers
# ---------------------------------------------------------------------------


def _same_rows(got, want):
    """Text, token counts and credits exact; SCOREs to float32 rounding."""
    assert [r[:3] + r[4:] for r in got] == [r[:3] + r[4:] for r in want]
    for g, w in zip(got, want):
        assert g[3] == pytest.approx(w[3], rel=0, abs=1e-6)


def _longer_set():
    reqs = [Request("w" * (5 + 9 * i) + f" joining case {i}", "proxy-8b",
                    COMPLETE, max_tokens=mt, request_id=i + 1)
            for i, mt in enumerate([12, 3, 30, 5])]
    reqs += [Request(f"does this joining row {i} pass?" + "!" * (13 * i),
                     "proxy-8b", SCORE, request_id=10 + i) for i in range(4)]
    return reqs


def test_concurrent_callers_match_serving_alone(cont_engine):
    first, second = _ragged_workload()[:7], _longer_set()
    a, b, _ = join_mid_loop(cont_engine, first, second)
    _same_rows([_row(r) for r in a], _serve(cont_engine, first))
    _same_rows([_row(r) for r in b], _serve(cont_engine, second))
    assert [r.request_id for r in b] == [r.request_id for r in second]


def test_driver_hands_off_once_its_own_sequences_retire(cont_engine):
    # the driver's two SCOREs retire after one prefill step; the joiner's
    # 40-token COMPLETE is still decoding when the driver returns, so the
    # joiner takes the loop over and finishes it
    first = [Request(f"short score {i}", "proxy-8b", SCORE,
                     request_id=i + 1) for i in range(2)]
    second = [_ragged_workload()[0]]
    a, b, left_behind = join_mid_loop(cont_engine, first, second)
    assert left_behind == 1                 # the joiner's, still live
    assert b[0].tokens_out == second[0].max_tokens
    _same_rows([_row(r) for r in a], _serve(cont_engine, first))
    _same_rows([_row(r) for r in b], _serve(cont_engine, second))
    kv = cont_engine._batcher.kv
    assert kv.free_count == kv.num_blocks - 1


def test_oversized_request_fails_only_its_caller(cont_engine):
    b = cont_engine._batcher
    need = (b.kv.max_seq_blocks + 1) * b.block_size
    bad = [Request("p", "proxy-8b", COMPLETE, max_tokens=need,
                   request_id=1),
           Request("a fine one", "proxy-8b", SCORE, request_id=2)]
    first = _ragged_workload()[:6]
    a, err, _ = join_mid_loop(cont_engine, first, bad)
    assert isinstance(err, EngineFailure)
    _same_rows([_row(r) for r in a], _serve(cont_engine, first))
    assert b._callers == 0 and not b._pending


def test_step_failure_fails_every_caller(cont_engine):
    # a step that raises under the driver must not strand the joiner:
    # it fails too, and the batcher is left empty for the next caller
    b = cont_engine._batcher

    def broken(active):
        raise RuntimeError("device lost")

    b._decode_step = broken
    try:
        a, err, _ = join_mid_loop(cont_engine, _ragged_workload()[:2],
                                  _ragged_workload()[2:4])
    finally:
        del b._decode_step
    assert isinstance(a, RuntimeError)       # the driver's own error
    assert isinstance(err, EngineFailure)
    assert b._callers == 0 and not b._pending
    assert all(s is None for s in b._active)
    assert b.kv.free_count == b.kv.num_blocks - 1
    reqs = _ragged_workload()[:3]
    assert _serve(cont_engine, reqs) == _serve(cont_engine, reqs)


def test_joined_counts_mixed_admissions(cont_engine):
    b = cont_engine._batcher
    before = b.stats()
    _serve(cont_engine, _ragged_workload())
    lone = b.stats()
    assert lone["joined"] == before["joined"]
    first, second = _ragged_workload()[:7], _longer_set()
    join_mid_loop(cont_engine, first, second)
    after = b.stats()
    # the driver's seven fill the slots alone; the joiner's first is
    # admitted beside them, and none after the driver's last retired
    # counts
    assert after["admitted"] - lone["admitted"] == len(first) + len(second)
    assert 1 <= after["joined"] - lone["joined"] <= len(second)


@pytest.mark.parametrize("workload,steps", [
    ("ragged", (6, 39, 26, 75)),
    ("midstream", (10, 32, 48, 153)),
])
def test_lone_caller_runs_the_same_steps(cont_engine, workload, steps):
    """A caller alone runs the steps the one-caller loop ran (counts
    measured on that loop): prefill steps, decode steps, prefilling rows
    and decoding slots."""
    if workload == "ragged":
        reqs = _ragged_workload()
    else:
        reqs = [Request(f"queued request number {i} says hello", "proxy-8b",
                        COMPLETE, max_tokens=24 if i % 5 == 0 else 3,
                        request_id=i + 1)
                for i in range(3 * cont_engine.max_batch)]
    keys = ("prefill_steps", "decode_steps", "prefill_rows", "decode_tokens")
    before = cont_engine.backend_stats()
    _serve(cont_engine, reqs)
    after = cont_engine.backend_stats()
    assert tuple(after[k] - before[k] for k in keys) == steps
    assert after["joined"] == before["joined"]


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
    """Step counters and the step loop's own clock on a ragged wave.  (The
    roofline bound this test once read is gone; the name stays.)"""
    reqs = _ragged_workload()[:4]
    before = cont_engine.backend_stats()
    cont_engine.submit_batch(copy.deepcopy(reqs))
    stats = cont_engine.backend_stats()
    assert stats["backend"] == "continuous"
    assert stats["prefill_steps"] > 0 and stats["decode_steps"] > 0
    assert stats["kv_peak_blocks"] > 0

    def delta(key):
        return stats[key] - before[key]

    # the four fit the slots at once, so every prefill step carries each
    # sequence still prefilling: one row per chunk of each prompt
    chunk = cont_engine._batcher.prefill_chunk
    lens = [len(tok.encode(r.prompt, max_len=cont_engine.max_seq))
            for r in reqs]
    assert delta("prefill_rows") == sum(-(-n // chunk) for n in lens)
    assert delta("prefill_tokens") == sum(lens)
    assert delta("prefill_rows") <= delta("prefill_steps") * cont_engine.max_batch
    assert stats["loop_s"] >= stats["readback_s"] >= 0
    assert delta("loop_s") >= delta("readback_s") > 0

    # the static backend reports too, without batcher telemetry
    st = JaxInferenceEngine("proxy-8b", smoke=True, backend="static")
    assert st.backend_stats()["backend"] == "static"
    assert "prefill_rows" not in st.backend_stats()


def test_step_programs_carry_pinned_names(cont_engine):
    """The jitted steps lower to modules named for ``_prefill_fn`` and
    ``_decode_fn``, which device-trace readers match."""
    import jax
    cont_engine.submit_batch(copy.deepcopy(_ragged_workload()[:2]))
    b = cont_engine._batcher

    def like(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, np.int32)

    common = (jax.tree.map(like, cont_engine.params),
              jax.tree.map(like, b.kv.pool))
    seen = set()
    for key, fn in cont_engine._jit_cache.items():
        if key[0] == "cb_prefill":
            _, slots, chunk, nb, _ = key
            args = common + (ints(slots, nb), ints(slots), ints(slots),
                             ints(slots, chunk))
            want = "jit__prefill_fn"
        elif key[0] == "cb_decode":
            _, slots, nb, _ = key
            args = common + (ints(slots, nb), ints(slots), ints(slots),
                             ints(slots, 1))
            want = "jit__decode_fn"
        else:
            continue
        assert f"module @{want}" in fn.lower(*args).as_text()
        seen.add(want)
    assert seen == {"jit__prefill_fn", "jit__decode_fn"}
