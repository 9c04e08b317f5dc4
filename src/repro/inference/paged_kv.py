"""Paged KV cache: fixed-size blocks, a free-list allocator, block tables.

The continuous-batching backend (``inference/continuous.py``) cannot give
every slot a dense ``[B, Smax]`` cache — sequences of wildly different
lengths would all pay for the longest one, and a retiring sequence would
strand its whole allocation until the batch drains.  Instead the cache is
a **pool of fixed-size blocks**:

  * the pool mirrors ``model.init_cache`` block-for-block, with each
    attention layer's ``k`` and ``v`` held in one leaf ``kv`` whose
    ``(batch, Smax)`` axis pair is replaced by ``(num_blocks, block_size)``
    and whose heads interleave keys and values (head ``2h`` is key head
    ``h``, head ``2h + 1`` value head ``h``): a scanned-period cache
    ``[P, B, Smax, KV, hd]`` becomes ``[P, NB, bs, 2 KV, hd]`` and a lead
    or tail one ``[B, Smax, KV, hd]`` becomes ``[NB, bs, 2 KV, hd]``.
    That is the page layout the ragged paged-attention kernel reads;
  * each live sequence owns a **block table** — the ordered list of pool
    blocks holding its tokens — allocated from a host-side free list at
    admission and returned at retirement;
  * block 0 is reserved as a sacrificial scratch block: unassigned table
    entries point at it, so reads of empty slots see junk that is never
    trusted, and writes of invalid positions are dropped (out-of-range
    block index + ``mode="drop"``).

A chunked-prefill step works on the pool in place (``pages``): each
attention layer writes its chunk's keys and values into the rows' own
blocks and then attends through the block tables.  A decode step uses
the pure ``gather``/``scatter`` pair instead: materialise the dense
per-step view, run the model, and persist only the newly valid
keys/values back into the pool.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

from repro.models.blocks import interleave_kv


class OutOfBlocks(RuntimeError):
    """Free list exhausted — the caller should defer admission."""


def _leaf_axis(shape, block_size: int) -> int:
    """Index of the (batch, seq) axis pair in an ``init_cache(1, bs)``
    leaf: the first ``i`` with ``shape[i] == 1 and shape[i+1] == bs``."""
    for i in range(len(shape) - 1):
        if shape[i] == 1 and shape[i + 1] == block_size:
            return i
    raise ValueError(
        f"cache leaf {shape} has no (batch, seq={block_size}) axis pair — "
        "architecture is not paged-cache compatible")


class PagedKVCache:
    def __init__(self, model, *, block_size: int = 32, num_blocks: int = 64,
                 device=None):
        if block_size < 2:
            raise ValueError("block_size must be >= 2")
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is scratch)")
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        template = model.init_cache(1, self.block_size, paged=True)

        def pool_leaf(c):
            if set(c) != {"k", "v"}:
                raise ValueError(
                    f"cache {sorted(c)} is not an attention layer's k/v — "
                    "architecture is not paged-cache compatible")
            x = c["k"]
            a = _leaf_axis(x.shape, self.block_size)
            shape = (x.shape[:a] + (self.num_blocks, self.block_size,
                                    2 * x.shape[a + 2]) + x.shape[a + 3:])
            return {"kv": jnp.zeros(shape, x.dtype, device=device)}

        # per group, the model-cache-shaped tree of each k/v leaf's
        # (batch, seq) axis: 1 in the scanned periods, 0 in lead and tail
        self._axes: Dict[str, Any] = {}
        self.pool: Dict[str, Any] = {}
        for key, sub in template.items():
            if key == "len":
                continue
            self._axes[key] = jax.tree.map(
                lambda x: _leaf_axis(x.shape, self.block_size), sub)
            self.pool[key] = {name: pool_leaf(c) for name, c in sub.items()}
        # LIFO free list; block 0 stays out as the sacrificial scratch block
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))

    # ------------------------------------------------------------------
    # host-side allocator
    # ------------------------------------------------------------------

    def blocks_for(self, tokens: int) -> int:
        return max(-(-int(tokens) // self.block_size), 1)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def max_seq_blocks(self) -> int:
        """Largest block table a single sequence can hold."""
        return self.num_blocks - 1

    def can_alloc(self, n: int) -> bool:
        return len(self._free) >= n

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise OutOfBlocks(f"need {n} blocks, {len(self._free)} free")
        return [self._free.pop() for _ in range(n)]

    def free_blocks(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            if not 0 < b < self.num_blocks:
                raise ValueError(f"bad block id {b}")
            if b in self._free:
                raise ValueError(f"double free of block {b}")
            self._free.append(b)

    # ------------------------------------------------------------------
    # pure gather / scatter (jit-safe; pool passed explicitly)
    # ------------------------------------------------------------------

    def _axis(self, group: str) -> int:
        return jax.tree.leaves(self._axes[group])[0]

    def gather(self, pool, tables, lens):
        """Materialise the dense model cache view of ``tables``.

        pool: as ``self.pool``; tables: int32 [B, nb]; lens: int32 [B].
        Returns a ``model.init_cache``-shaped cache with Smax = nb * bs
        and ``"len" = lens``.
        """
        B, nb = tables.shape
        flat = tables.reshape(-1)

        def one(c, a):
            leaf = c["kv"]
            shp = leaf.shape
            # one gather over every layer's blocks at once, flattened to
            # [P * NB, ...] and read at table + p * NB: gathering along the
            # NB axis of [P, NB, ...] lowers to a loop over the blocks.
            # Tables hold valid block ids only, so "clip" needs no select.
            P, NB = math.prod(shp[:a]), shp[a]
            idx = flat[None] + NB * jnp.arange(P, dtype=jnp.int32)[:, None]
            g = jnp.take(leaf.reshape((P * NB,) + shp[a + 1:]), idx, axis=0,
                         mode="clip")                   # [P, B*nb, bs, ..]
            # heads split as (KV, 2): XLA slices that pair axis from the
            # gathered view; a strided slice of the heads it does not
            g = g.reshape(shp[:a] + (B, nb * shp[a + 1], shp[a + 2] // 2, 2)
                          + shp[a + 3:])
            return {"k": g[..., 0, :], "v": g[..., 1, :]}

        cache = {k: {name: one(c, self._axis(k))
                     for name, c in pool[k].items()} for k in pool}
        cache["len"] = lens
        return cache

    def write_index(self, tables, start, count, width: int):
        """Where a step's new tokens go: per row and new position, the pool
        block (-1 where the token is not written: beyond ``count``) and the
        offset in it.  start/count: int32 [B]; ``width`` the static number
        of new positions a row has (the prefill chunk, or 1)."""
        bs = self.block_size
        nbw = tables.shape[1]
        i = jnp.arange(width, dtype=jnp.int32)[None]           # [1, C]
        pos = start[:, None] + i                               # [B, C]
        blk = jnp.take_along_axis(
            tables, jnp.clip(pos // bs, 0, nbw - 1), axis=1)   # [B, C]
        return jnp.where(i < count[:, None], blk, -1), pos % bs

    def scatter(self, pool, cache, tables, start, count, width: int):
        """Persist newly written cache positions back into the pool.

        cache: dense view returned by the model, with new tokens written at
        positions ``start .. start+width-1`` per row; start/count: int32
        [B]; ``width`` is the static per-row write window (the prefill
        chunk size, or 1 for a decode step).  Only the first ``count``
        positions per row are persisted — ragged chunk tails and inactive
        slots never touch the pool.
        """
        B = tables.shape[0]
        blk, off = self.write_index(tables, start, count, width)
        blk = jnp.where(blk < 0, self.num_blocks, blk)         # OOB -> drop
        pos = start[:, None] + jnp.arange(width, dtype=jnp.int32)[None]
        bidx = jnp.arange(B, dtype=jnp.int32)[:, None]

        def core(pl, dk, dv):
            # pl: [NB, bs, 2KV, hd]; dk, dv: [B, S, KV, hd]
            p = jnp.clip(pos, 0, dk.shape[1] - 1)
            vals = interleave_kv(dk[bidx, p], dv[bidx, p])   # [B, C, 2KV, hd]
            return pl.at[blk, off].set(vals, mode="drop")

        def one(c, dn, a):
            fn = core
            for _ in range(a):          # vmap over leading axes (periods)
                fn = jax.vmap(fn, in_axes=(0, 0, 0))
            return {"kv": fn(c["kv"], dn["k"], dn["v"])}

        return {k: {name: one(c, cache[k][name], self._axis(k))
                    for name, c in pool[k].items()} for k in pool}
