"""Chunked-prefill attention read straight from a paged KV pool.

The continuous batcher's prefill step advances every slot by a chunk of
``C`` tokens.  Each attention layer has written the chunk's keys and
values into the rows' own pool blocks; this reads them back through the
block tables, with no dense per-row copy of the cache:

  * :func:`paged_attention` — JAX's ragged paged-attention Pallas kernel
    (``jax.experimental.pallas.ops.tpu.ragged_paged_attention``) over the
    pool as it lies: pages ``[N, bs, 2 KV, hd]`` with key head ``h`` at
    ``2h`` and value head ``h`` at ``2h + 1``, the tables scalar-
    prefetched, each row's pages fetched by DMA in blocks of up to
    ``PAGES_PER_BLOCK`` and reduced with an online softmax in VMEM.  Only a
    row's own pages up to its ``kv_len`` are read; the float32 logits never
    leave the chip's fast memory.
  * :func:`gather_pages` — the same pages as a dense ``[B, nb * bs]`` view
    of keys and values, which the jnp reference (``models.attention.
    paged_attention`` off TPU) attends over.

The kernel cannot run in Pallas's interpret mode on the CPU; off TPU the
reference runs instead, reading the same pool through the same tables.
:func:`impl_for` makes that choice once, on the host, for a model's heads
and pool.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import ragged_paged_attention as rpa

# KV pages one kernel step fetches.  On a TPU v5e, 8 rows of 32 queries
# over pages of 32 tokens: 605 us a call at 16 pages against 615 at 8 and
# 639 at 4 (qwen3-8b's heads, rows to 2,048 tokens), 751 against 836 and
# 940 (Trinity-Mini's, rows to 8,192).  With qwen3-8b's heads, 32 pages,
# or 64 queries a block at 16, overflow VMEM.
PAGES_PER_BLOCK = 16


def impl_for(num_heads: int, pages) -> str:
    """The implementation a prefill step over ``pages`` (a pool leaf, heads
    on its second-to-last axis) runs: "pallas" on TPU, "reference"
    elsewhere.  On TPU, heads the kernel cannot tile (2 KV heads
    interleaved must fill whole sublane tiles of the pool's dtype) raise
    ``ValueError`` rather than falling back to the reference."""
    if jax.default_backend() != "tpu":
        return "reference"
    rpa.kernel.get_min_heads_per_blk(num_heads, pages.shape[-2], pages.dtype,
                                     pages.dtype)
    return "pallas"


def paged_attention(q, pages, tables, kv_lens, *, window=None):
    """q: [B, C, H, hd], row b's queries at positions ``kv_lens[b] - C``
    .. ``kv_lens[b] - 1``; pages: [N, bs, 2 KV, hd] holding those queries'
    keys and values already; tables: int32 [B, nb], each row's pages in
    order; kv_lens: int32 [B], at least C.  ``window``: a query at position
    t reads positions (t - window, t] only.  Returns [B, C, H, hd]."""
    B, C, H, hd = q.shape
    out = rpa.ragged_paged_attention(
        q.reshape(B * C, H, hd), pages, kv_lens, tables,
        jnp.arange(B + 1, dtype=jnp.int32) * C, jnp.full((1,), B, jnp.int32),
        sm_scale=1.0 / math.sqrt(hd), sliding_window=window,
        num_kv_pages_per_block=min(tables.shape[1], PAGES_PER_BLOCK),
        num_queries_per_block=C)
    return out.reshape(B, C, H, hd)


def gather_pages(pages, tables):
    """pages: [N, bs, 2 KV, hd]; tables: int32 [B, nb].  Returns the keys
    and values of each row's pages in table order, [B, nb * bs, KV, hd]
    each."""
    B, nb = tables.shape
    _, bs, kv2, hd = pages.shape
    g = jnp.take(pages, tables.reshape(-1), axis=0)
    g = g.reshape(B, nb * bs, kv2 // 2, 2, hd)
    return g[..., 0, :], g[..., 1, :]
