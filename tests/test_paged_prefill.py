"""Chunked prefill over the paged KV pool in place: each attention layer
writes its chunk into the rows' own blocks and reads them back through the
block tables.  The oracle is the composition the step replaced, kept here:
gather the dense view of every layer, run the model over it, scatter the
new keys and values back."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.inference.backend import SCORE, Request
from repro.inference.continuous import (ContinuousBatcher, _kernel_width,
                                        _kv_lens)
from repro.inference.engine import JaxInferenceEngine
from repro.kernels.paged_attention import ops as paged_ops
from repro.models import attention, model_zoo


def _oracle_prefill(b, params, pool, tables, lens, counts, toks):
    """gather -> model.apply over the dense view -> scatter."""
    cache = b.kv.gather(pool, tables, lens)
    C = toks.shape[1]
    pos = lens[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    out = b.model.apply(params, {"tokens": toks, "positions": pos},
                        mode="decode", cache=cache, paged=True)
    last = jnp.clip(counts - 1, 0, C - 1)
    h = out["hidden"][jnp.arange(toks.shape[0]), last]
    logits = b.model.logits_of(params, h)
    pool = b.kv.scatter(pool, out["cache"], tables, lens, counts, C)
    return pool, logits, lens + counts


def _step_case(b):
    """Eight slots: a first chunk; a chunk crossing a block boundary; a
    ragged chunk; a decode-state row (counts 0, lens > 0); an inactive
    slot; a row far past the sliding window; a one-token chunk; a chunk
    that starts a new block.  Block size 32, chunk 32."""
    bs, C = b.block_size, b.prefill_chunk
    lens = np.array([0, 20, 40, 64, 0, 100, 7, 96], np.int32)
    counts = np.array([32, 32, 5, 0, 0, 17, 1, 32], np.int32)
    nb = 8
    tables = np.zeros((b.slots, nb), np.int32)
    rng = np.random.default_rng(1)
    ids = rng.permutation(np.arange(1, b.kv.num_blocks))
    used = 0
    for s in range(b.slots):
        if s == 4:
            continue                                    # inactive: all 0
        need = -(-(int(lens[s]) + C) // bs)
        tables[s, :need] = ids[used:used + need]
        used += need
    toks = rng.integers(4, 260, (b.slots, C)).astype(np.int32)
    return tables, lens, counts, toks


def _random_pool(pool, seed):
    leaves, tree = jax.tree.flatten(pool)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype)
        for k, x in zip(keys, leaves)])


def _written(b, tables, lens, counts):
    """Per pool block, the offsets this step writes."""
    bs = b.block_size
    out = {}
    for s in range(b.slots):
        for p in range(int(lens[s]), int(lens[s] + counts[s])):
            out.setdefault(int(tables[s, p // bs]), set()).add(p % bs)
    return out


@pytest.mark.parametrize("arch", ["proxy-8b", "trinity-mini"])
def test_in_place_prefill_equals_gather_run_scatter(arch):
    eng = JaxInferenceEngine(arch, smoke=True, max_seq=128,
                             backend="continuous", seed=3)
    b = eng._batcher
    tables, lens, counts, toks = _step_case(b)
    pool = _random_pool(b.kv.pool, 5)
    args = [jnp.asarray(x) for x in (tables, lens, counts, toks)]
    want_pool, want_logits, want_lens = jax.jit(
        lambda *a: _oracle_prefill(b, *a))(eng.params, pool, *args)
    got_pool, got_logits, got_lens = jax.jit(b._prefill_fn)(
        eng.params, pool, *args)
    np.testing.assert_array_equal(got_lens, want_lens)
    adv = counts > 0
    np.testing.assert_allclose(np.asarray(got_logits)[adv],
                               np.asarray(want_logits)[adv],
                               rtol=1e-5, atol=1e-5)
    # the pool: bitwise as it was outside the written rows, and the
    # oracle's inside them
    written = _written(b, tables, lens, counts)
    for group in pool:
        for name in pool[group]:
            before = np.asarray(pool[group][name]["kv"], np.float32)
            got = np.asarray(got_pool[group][name]["kv"], np.float32)
            want = np.asarray(want_pool[group][name]["kv"], np.float32)
            a = b.kv._axis(group)
            before, got, want = (np.moveaxis(x, (a, a + 1), (0, 1))
                                 for x in (before, got, want))
            mask = np.zeros(got.shape[:2], bool)        # [NB, bs]
            for blk, offs in written.items():
                mask[blk, sorted(offs)] = True
            np.testing.assert_array_equal(got[~mask], before[~mask])
            np.testing.assert_array_equal(got[mask], want[mask])
            assert not np.array_equal(got[mask], before[mask])


@pytest.mark.parametrize("arch", ["proxy-8b", "trinity-mini"])
def test_in_place_prefill_serves_the_oracle_scores(arch):
    """Whole SCOREs through several chunks, rows joining mid-wave: the
    served path against the same engine stepping the oracle."""
    prompts = ["x" * n + f" row {n}" for n in (3, 40, 70, 101, 5, 64)]
    reqs = [Request(p, arch, SCORE, request_id=i + 1)
            for i, p in enumerate(prompts)]
    got = {}
    for name in ("in_place", "oracle"):
        eng = JaxInferenceEngine(arch, smoke=True, max_seq=160,
                                 backend="continuous", seed=2, max_batch=4)
        b = eng._batcher
        if name == "oracle":
            b._prefill_fn = lambda *a, b=b: _oracle_prefill(b, *a)
        got[name] = [r.score for r in eng.submit_batch(reqs)]
    np.testing.assert_allclose(got["in_place"], got["oracle"], rtol=0,
                               atol=1e-6)


def test_counters_count_the_in_place_steps_and_their_blocks(monkeypatch):
    """One 100-token SCORE through 32-token chunks on the 6-layer smoke
    Trinity, 8 slots, its batcher set to the kernel, which a stand-in
    (the reference, recording the tables' width) replaces off TPU.  Its
    steps start at 0, 32, 64, 96 and read through their chunk: 32, 64, 96,
    128 positions, 1 + 2 + 3 + 4 blocks; each of the 7 idle slots reads
    one chunk, 1 block a step.  Every step's tables reach the kernel at
    the widest bucket: 8 blocks of 32 for 128 tokens and 4 chunks."""
    widths = []

    def stand_in(q, pages, tables, kv_lens, *, window=None):
        widths.append(tables.shape[1])
        return attention.paged_attention(q, pages, tables, kv_lens,
                                         impl="reference", window=window or 0)

    monkeypatch.setattr(paged_ops, "paged_attention", stand_in)
    eng = JaxInferenceEngine("trinity-mini", smoke=True, max_seq=128,
                             backend="continuous", seed=1)
    eng._batcher.paged_impl = "pallas"
    eng.submit_batch([Request("x" * 99, "trinity-mini", SCORE,
                              request_id=1)])
    st = eng.backend_stats()
    assert st["prefill_steps"] == 4
    assert st["prefill_pool_steps"] == st["prefill_steps"]
    assert st["kv_blocks_read"] == 6 * ((1 + 2 + 3 + 4) + 7 * 4)
    assert widths and set(widths) == {8}


def test_counters_stay_zero_where_the_reference_reads_the_pool():
    eng = JaxInferenceEngine("trinity-mini", smoke=True, max_seq=128,
                             backend="continuous", seed=1)
    assert eng._batcher.paged_impl == "reference"      # off TPU
    eng.submit_batch([Request("x" * 99, "trinity-mini", SCORE,
                              request_id=1)])
    st = eng.backend_stats()
    assert st["prefill_steps"] == 4
    assert st["prefill_pool_steps"] == st["kv_blocks_read"] == 0


def test_the_kernel_is_chosen_on_tpu_and_never_falls_back(monkeypatch):
    pages = jax.ShapeDtypeStruct((545, 32, 16, 128), jnp.bfloat16)
    assert paged_ops.impl_for(32, pages) == "reference"     # off TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_ops.impl_for(32, pages) == "pallas"
    # 3 KV heads interleaved (6) fill no whole bf16 sublane tile
    with pytest.raises(ValueError, match="tiled"):
        paged_ops.impl_for(12, jax.ShapeDtypeStruct((545, 32, 6, 128),
                                                    jnp.bfloat16))


@pytest.mark.parametrize("max_seq,width", [(2048, 128), (8192, 512),
                                           (1024, 64)])
def test_kernel_width_is_the_widest_bucket_a_sequence_reaches(max_seq, width):
    """The benchmark cells' kernel widths (qwen3-8b.filter,
    trinity-mini.longdoc, qwen3-32b.dashboard: chunk 32, blocks of 32, 8
    slots), which tests/test_tpu_compile.py compiles for a v5e: a
    full-length prompt plus four chunks, bucketed, and no step the warm-up
    runs is wider."""
    eng = types.SimpleNamespace(max_seq=max_seq, max_batch=8,
                                device=jax.devices()[0],
                                model=model_zoo.build("proxy-8b", smoke=True))
    b = ContinuousBatcher(eng)
    assert b._kernel_width == width
    assert _kernel_width(max_seq + 128, 32, width // 2) == width // 2
    assert _kernel_width(30, 32, 4) == 1


def test_kv_lens_read_through_the_chunk_where_a_row_advances():
    lens = np.array([0, 40, 64, 0], np.int32)
    counts = np.array([32, 5, 0, 0], np.int32)
    np.testing.assert_array_equal(_kv_lens(lens, counts, 32),
                                  [32, 72, 32, 32])
    np.testing.assert_array_equal(
        _kv_lens(jnp.asarray(lens), jnp.asarray(counts), 32),
        [32, 72, 32, 32])


def _filter_shapes(window):
    """The filter's step at the top bucket: 8 slots x 32 tokens, 32 query
    heads over 8 KV heads of 128, pages of 32, 64 a row, bf16; rows at
    assorted lengths."""
    B, C, H, KV, hd, bs, nb = 8, 32, 32, 8, 128, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    pages = jax.random.normal(ks[0], (B * nb + 1, bs, 2 * KV, hd),
                              jnp.bfloat16)
    q = jax.random.normal(ks[1], (B, C, H, hd), jnp.bfloat16)
    tables = (1 + np.random.default_rng(0).permutation(B * nb)
              ).reshape(B, nb).astype(np.int32)
    kv_lens = jnp.asarray([32, 64, 300, 777, 1024, 1500, 2000, 2048],
                          jnp.int32)
    return q, pages, jnp.asarray(tables), kv_lens


@pytest.mark.parametrize("window", [0, 512])
def test_pallas_paged_attention_matches_its_reference_on_tpu(window):
    if jax.default_backend() != "tpu":
        pytest.skip("the ragged paged-attention kernel runs on TPU only")
    q, pages, tables, kv_lens = _filter_shapes(window)
    got, want = (np.asarray(jax.jit(
        lambda *a, impl=impl: attention.paged_attention(
            *a, window=window, impl=impl))(q, pages, tables, kv_lens),
        np.float32) for impl in ("pallas", "reference"))
    np.testing.assert_allclose(got, want, rtol=0, atol=3e-2)
