"""Paged KV cache: fixed-size blocks, a free-list allocator, block tables.

The continuous-batching backend (``inference/continuous.py``) cannot give
every slot a dense ``[B, Smax]`` cache — sequences of wildly different
lengths would all pay for the longest one, and a retiring sequence would
strand its whole allocation until the batch drains.  Instead the cache is
a **pool of fixed-size blocks**:

  * the pool mirrors ``model.init_cache`` leaf-for-leaf with the
    ``(batch, Smax)`` axis pair replaced by ``(num_blocks, block_size)``:
    a scanned-period leaf ``[P, B, Smax, KV, hd]`` becomes
    ``[P, NB, bs, KV, hd]`` and a tail leaf ``[B, Smax, KV, hd]`` becomes
    ``[NB, bs, KV, hd]``;
  * each live sequence owns a **block table** — the ordered list of pool
    blocks holding its tokens — allocated from a host-side free list at
    admission and returned at retirement;
  * block 0 is reserved as a sacrificial scratch block: unassigned table
    entries point at it, so gathers of empty slots read junk that is never
    trusted, and scatters of invalid positions are dropped (out-of-range
    block index + ``mode="drop"``).

``gather``/``scatter`` are pure functions (the pool is threaded through
jit as an argument), so one jitted step function can materialise the
dense per-step view, run the model, and persist only the newly valid
keys/values back into the pool.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp


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
        template = model.init_cache(1, self.block_size)

        def pool_leaf(x):
            a = _leaf_axis(x.shape, self.block_size)
            shape = (x.shape[:a] + (self.num_blocks, self.block_size)
                     + x.shape[a + 2:])
            return jnp.zeros(shape, x.dtype, device=device)

        self._axes: Dict[str, Any] = {}
        self.pool: Dict[str, Any] = {}
        for key, sub in template.items():
            if key == "len":
                continue
            self._axes[key] = jax.tree.map(
                lambda x: _leaf_axis(x.shape, self.block_size), sub)
            self.pool[key] = jax.tree.map(pool_leaf, sub)
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

    def gather(self, pool, tables, lens):
        """Materialise the dense model cache view of ``tables``.

        pool: as ``self.pool``; tables: int32 [B, nb]; lens: int32 [B].
        Returns a ``model.init_cache``-shaped cache with Smax = nb * bs
        and ``"len" = lens``.
        """
        B, nb = tables.shape
        flat = tables.reshape(-1)

        def one(leaf, a):
            g = jnp.take(leaf, flat, axis=a)        # [..., B*nb, bs, ...]
            shp = leaf.shape
            return g.reshape(shp[:a] + (B, nb * shp[a + 1]) + shp[a + 2:])

        cache = {k: jax.tree.map(one, pool[k], self._axes[k]) for k in pool}
        cache["len"] = lens
        return cache

    def scatter(self, pool, cache, tables, start, count, width: int):
        """Persist newly written cache positions back into the pool.

        cache: dense view returned by the model, with new tokens written at
        positions ``start .. start+width-1`` per row; start/count: int32
        [B]; ``width`` is the static per-row write window (the prefill
        chunk size, or 1 for a decode step).  Only the first ``count``
        positions per row are persisted — ragged chunk tails and inactive
        slots never touch the pool.
        """
        bs = self.block_size
        B, nbw = tables.shape
        i = jnp.arange(width, dtype=jnp.int32)[None]           # [1, C]
        pos = start[:, None] + i                               # [B, C]
        valid = i < count[:, None]
        blk = jnp.take_along_axis(
            tables, jnp.clip(pos // bs, 0, nbw - 1), axis=1)   # [B, C]
        blk = jnp.where(valid, blk, self.num_blocks)           # OOB -> drop
        off = pos % bs
        bidx = jnp.arange(B, dtype=jnp.int32)[:, None]

        def core(pl, dn):
            # pl: [NB, bs, ...]; dn: [B, S, ...]
            vals = dn[bidx, jnp.clip(pos, 0, dn.shape[1] - 1)]  # [B, C, ...]
            return pl.at[blk, off].set(vals, mode="drop")

        def one(pl, dn, a):
            fn = core
            for _ in range(a):          # vmap over leading axes (periods)
                fn = jax.vmap(fn, in_axes=(0, 0))
            return fn(pl, dn)

        return {k: jax.tree.map(one, pool[k], cache[k], self._axes[k])
                for k in pool}
