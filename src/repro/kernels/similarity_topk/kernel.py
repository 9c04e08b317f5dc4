"""Pallas TPU kernel: tiled batched cosine-similarity + top-k retrieval.

The semantic index's scoring path: queries [Q, D] against a corpus
[N, D] (both pre-normalized by the wrapper, so the MXU matmul *is* the
cosine similarity), returning the k best corpus rows per query.

Grid: (num_q_blocks, num_n_blocks) — corpus blocks innermost and
sequential.  Each step computes one [block_q, block_n] similarity tile
on the MXU, then merges it into a running per-query top-k held in VMEM
scratch via k rounds of select-max-and-mask (k is small; the rounds are
VPU work over [block_q, k + block_n] candidates).  The final corpus
block writes the running winners out.  Ties break toward the lower
corpus index — identical to the reference's stable argsort — because
earlier blocks (and earlier selections) sit first in the candidate
concatenation and ``argmax`` returns the first occurrence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _sim_topk_kernel(q_ref, c_ref, vals_ref, idx_ref, sv_ref, si_ref, *,
                     k: int, block_n: int, n_real: int):
    ni = pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(ni == 0)
    def _init():
        sv_ref[...] = jnp.full_like(sv_ref, NEG_INF)
        si_ref[...] = jnp.full_like(si_ref, -1)

    q = q_ref[...].astype(jnp.float32)          # [bq, d]
    c = c_ref[...].astype(jnp.float32)          # [bn, d]
    s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bn]
    bq = s.shape[0]
    gidx = (jax.lax.broadcasted_iota(jnp.int32, (bq, block_n), 1)
            + ni * block_n)
    s = jnp.where(gidx < n_real, s, NEG_INF)    # mask padded corpus rows

    cand_v = jnp.concatenate([sv_ref[...], s], axis=1)        # [bq, k+bn]
    cand_i = jnp.concatenate([si_ref[...], gidx], axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, cand_v.shape, 1)
    vals, idxs = [], []
    for _ in range(k):                          # unrolled: k is small
        am = jnp.argmax(cand_v, axis=1)         # first max -> lowest index
        hit = pos == am[:, None]
        vals.append(jnp.sum(jnp.where(hit, cand_v, 0.0), axis=1))
        idxs.append(jnp.sum(jnp.where(hit, cand_i, 0), axis=1))
        cand_v = jnp.where(hit, NEG_INF, cand_v)
    sv_ref[...] = jnp.stack(vals, axis=1)
    si_ref[...] = jnp.stack(idxs, axis=1).astype(jnp.int32)

    @pl.when(ni == nn - 1)
    def _fin():
        # selections that only ever saw -inf (k > N) report index -1
        out_v = sv_ref[...]
        vals_ref[...] = out_v
        idx_ref[...] = jnp.where(out_v == NEG_INF, -1, si_ref[...])


def similarity_topk_kernel(q, c, k: int, *, block_q: int = 128,
                           block_n: int = 512, interpret: bool):
    """q: [Q, D], c: [N, D] — unit-normalized fp32 rows.
    Returns ``(vals [Q, k] fp32 descending, idx [Q, k] int32)``."""
    Q, D = q.shape
    N = c.shape[0]
    block_q = max(min(block_q, Q), 1)
    block_n = max(min(block_n, N), 1)
    pad_q = (-Q) % block_q
    pad_n = (-N) % block_n
    if pad_q:
        q = jnp.pad(q, ((0, pad_q), (0, 0)))
    if pad_n:
        c = jnp.pad(c, ((0, pad_n), (0, 0)))
    nq = (Q + pad_q) // block_q
    nn = (N + pad_n) // block_n

    kernel = functools.partial(_sim_topk_kernel, k=k, block_n=block_n,
                               n_real=N)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(nq, nn),
        in_specs=[
            pl.BlockSpec((block_q, D), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_n, D), lambda qi, ni: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, ni: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q + pad_q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q + pad_q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        interpret=interpret,
    )(q.astype(jnp.float32), c.astype(jnp.float32))
    return vals[:Q], idx[:Q]
