"""What every architecture's float32 reference shares: the probes it reads,
the lower-precision control streams, and the readout of the logits.

An architecture module (``reference/<model_type>.py``) draws its own
weights and runs its own layers; it feeds each probe's embedded tokens
through them with ``streams`` and hands the final hidden states to
``gather`` and ``read_out``.  So every architecture's controls are this
one code, the code the limits were set against.

``controls`` are streams beside the reference: the same forward with
every matrix product one precision step below the served bfloat16 --
``int8`` (weights per output channel, activations per token, symmetric;
int32 accumulation) or ``fp8`` (float8_e4m3fn, scaled the same way).
The check's limits are set so that a control fails them.

Each sequence is padded on the right to a power-of-two length and run by
itself; causal attention keeps padding out of every real position.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class Probe:
    """Read the logits at ``positions`` of ``tokens``; ``targets`` are the
    ids whose logits are read there (a served or label token)."""
    tokens: List[int]
    positions: List[int]
    targets: List[int]


def bucket(n: int, lo: int = 64) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def quantize(x, axis):
    """Symmetric int8 along ``axis`` (the contracted one); returns (q, scale)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def mm_f32(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HI)


def mm_int8(x, wq):
    """x: [S, k] float32; wq: (int8 [k, n], scale [1, n])."""
    q, ws = wq
    xq, xs = quantize(x, axis=-1)
    acc = jax.lax.dot_general(xq, q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


FP8_MAX = 448.0          # largest finite float8_e4m3fn


def to_fp8(x, axis):
    """Scaled to float8_e4m3fn along ``axis`` and back: (values, scale)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32), scale


def mm_fp8(x, wq):
    """One bfloat16 pass: float8_e4m3fn values (4 significant bits) are
    bfloat16 values, so the products are exact and sum in float32."""
    q, ws = wq
    xq, xs = to_fp8(x, axis=-1)
    return jnp.matmul(xq, q, precision=jax.lax.Precision.DEFAULT) * xs * ws


LOWER = {"int8": (lambda w: quantize(w, axis=0), mm_int8),
         "fp8": (lambda w: to_fp8(w, axis=0), mm_fp8)}


# ---------------------------------------------------------------------------
# the streams
# ---------------------------------------------------------------------------


def streams(layer: Callable, w: Dict[str, jax.Array], xs: dict,
            controls: tuple) -> dict:
    """One layer on every stream of one sequence.  ``layer(x, w, mm)``
    multiplies by each of its weight matrices through ``mm``: the
    reference's float32 product, or a control's on its lowered weights.
    xs: {"ref": [S, d], <control>: [S, d], ...}."""
    with jax.default_matmul_precision("highest"):
        out = {"ref": layer(xs["ref"], w, mm_f32)}
        for name in controls:
            lower, mm = LOWER[name]
            wq = {k: lower(v.astype(jnp.float32)) for k, v in w.items()}
            out[name] = layer(xs[name], wq, mm)
        return out


def _take(e, ids):
    return e[ids].astype(jnp.float32)


def inputs(embed, probes: Sequence[Probe], controls: tuple) -> List[dict]:
    """Each probe's tokens through ``embed`` [vocab, d], padded to its
    length bucket, one copy per stream."""
    take_j = jax.jit(_take)
    hidden = []
    for p in probes:
        ids = np.zeros((bucket(len(p.tokens)),), np.int32)
        ids[:len(p.tokens)] = p.tokens
        x = take_j(embed, ids)
        hidden.append({name: x for name in ("ref",) + controls})
    return hidden


# ---------------------------------------------------------------------------
# the readout
# ---------------------------------------------------------------------------


def _readout(head, hs, tok, eps: float, yes: int, no: int, controls: tuple):
    """Statistics of the logits at gathered positions: hs[stream] [P, d]."""
    with jax.default_matmul_precision("highest"):
        r = mm_f32(rms_norm(hs["ref"], eps), head)
        out = {"ref_t": jnp.take_along_axis(r, tok[:, None], 1)[:, 0],
               "ref_max": jnp.max(r, -1),
               "ref_lse": jax.nn.logsumexp(r, -1),
               "ref_yes": r[:, yes], "ref_no": r[:, no]}
        for name in controls:
            lower, mm = LOWER[name]
            c = mm(rms_norm(hs[name], eps), lower(head.astype(jnp.float32)))
            pick = jnp.argmax(c, -1)
            out.update({
                f"{name}_pick_ref": jnp.take_along_axis(
                    r, pick[:, None], 1)[:, 0],
                f"{name}_t": jnp.take_along_axis(c, tok[:, None], 1)[:, 0],
                f"{name}_lse": jax.nn.logsumexp(c, -1),
                f"{name}_yes": c[:, yes], f"{name}_no": c[:, no]})
        return out


def _rows(x, positions):
    return x[positions]


def gather(hidden: List[dict], probes: Sequence[Probe]
           ) -> Dict[str, np.ndarray]:
    """Each probe's positions of each stream's final hidden states, in
    probe order, on the host: {stream: [positions, d]}."""
    rows_j = jax.jit(_rows)
    picked = {name: [] for name in hidden[0]}
    for p, x in zip(probes, hidden):
        pos = np.zeros((bucket(len(p.positions), lo=1),), np.int32)
        pos[:len(p.positions)] = p.positions
        for name in picked:
            picked[name].append(
                np.asarray(rows_j(x[name], pos))[:len(p.positions)])
    return {k: np.concatenate(v) for k, v in picked.items()}


def read_out(head, picked: Dict[str, np.ndarray], probes: Sequence[Probe], *,
             eps: float, yes: int, no: int, controls: tuple, max_rows: int
             ) -> List[Dict[str, np.ndarray]]:
    """One readout dict per probe (arrays over its positions): the final
    RMSNorm at ``eps`` and ``head`` [d, vocab] over ``gather``'s rows, in
    blocks of ``max_rows``."""
    readout_j = jax.jit(_readout, static_argnums=(3, 4, 5, 6))
    toks = [t for p in probes for t in p.targets]
    owner = np.asarray([i for i, p in enumerate(probes)
                        for _ in p.positions])
    got: Dict[str, List[np.ndarray]] = {}
    for a in range(0, len(toks), max_rows):
        n = min(max_rows, len(toks) - a)
        rows = {k: np.zeros((max_rows, v.shape[1]), np.float32)
                for k, v in picked.items()}
        for k, v in picked.items():
            rows[k][:n] = v[a:a + n]
        tk = np.zeros((max_rows,), np.int32)
        tk[:n] = toks[a:a + n]
        part = readout_j(head, rows, tk, eps, yes, no, controls)
        for k, v in part.items():
            got.setdefault(k, []).append(np.asarray(v, np.float64)[:n])
    flat = {k: np.concatenate(v) for k, v in got.items()}
    return [{k: v[owner == i] for k, v in flat.items()}
            for i in range(len(probes))]
