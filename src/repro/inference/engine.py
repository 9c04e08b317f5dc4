"""JAX inference engine: one hosted model, continuous-batching decode.

This is the Cortex Platform "Inference Engine" (paper §2) adapted to TPU:

  * **continuous batching** (default for pure-attention decoders): fixed
    [max_batch] slots over a paged KV cache; finished sequences retire at
    EOS and queued work is admitted at *every* decode step, with long
    prompts chunk-prefilled between steps (``inference/continuous.py``).
    SCORE and COMPLETE ride this path; CLASSIFY/EMBED (single forward
    passes) and non-attention architectures use the static path below.
    On the CPU either path gives the same text, token counts and
    credits, and SCOREs agree to float32 rounding; on the TPU they agree
    to bfloat16 rounding (``continuous.py``'s determinism contract);
  * static-shape batch fallback: one blocking prefill+decode call per
    batch, finished sequences retiring early from the decode loop;
  * bucketed prefill (power-of-two lengths) and bucketed decode batch
    sizes to bound recompilation;
  * four request kinds: COMPLETE (greedy decode), SCORE (yes/no confidence
    from next-token logits — the cascade's s_i, §5.2), CLASSIFY
    (label-likelihood scoring over a candidate set — AI_CLASSIFY), EMBED
    (masked mean-pooled hidden states projected to the requested
    dimensionality — the semantic index's vectors, priced per input
    token on the embedding tier);
  * per-request credit metering (AI credits, §4) and latency accounting;
  * fault injection (EngineFailure) so the scheduler's retry/straggler
    logic is testable;
  * spans on the profiler's clock (``repro.obs.trace``): one per static
    batch (``engine.score`` / ``engine.classify`` / ``engine.complete`` /
    ``engine.embed``) and ``engine.first_call`` around the first
    execution of each jitted program key, so a compile names its step.

Modality frontends are stubs per the assignment: FILE inputs are mapped to
deterministic pseudo-embeddings derived from the URI hash.
"""
from __future__ import annotations

import hashlib
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs import base as cfgs
from repro.inference import tokenizer as tok
from repro.inference.backend import (CLASSIFY, COMPLETE, EMBED, SCORE,
                                     EngineFailure, Request, Result,
                                     credits_for)
from repro.models import model_zoo
from repro.obs.trace import active_tracer

# Program names of the continuous batcher's jitted steps, pinned: HLO
# modules and the profiler's events read ``jit__prefill_fn`` and
# ``jit__decode_fn`` however the step functions are written, and device
# trace readers match these names.
_PROGRAM_NAMES = {"cb_prefill": "_prefill_fn", "cb_decode": "_decode_fn"}


def _bucket(n: int, lo: int = 32) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _named(fn, name: str):
    """``fn`` under the function name ``name`` (jit's program name)."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program


def _first_call(key, jitted):
    """``jitted`` whose first call, the one that traces and compiles (or
    loads from the compile cache), is an ``engine.first_call`` span that
    carries ``key``."""
    called = False

    def call(*args):
        nonlocal called
        if called:
            return jitted(*args)
        called = True
        with active_tracer().span("engine.first_call",
                                  kind="engine.first_call", key=repr(key)):
            return jitted(*args)
    return call


def _hash_embed(key: str, shape, scale=0.1) -> np.ndarray:
    seed = int.from_bytes(hashlib.sha256(key.encode()).digest()[:4], "little")
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape) * scale).astype(np.float32)


class JaxInferenceEngine:
    """Hosts one model on one device and serves batched requests.

    ``arch`` is a model name (its published config, or the CPU smoke
    preset with ``smoke=True``) or a `ModelConfig` such as a depth cut of
    a published config; the config's ``name`` then routes and prices
    requests.  Params, the KV pool and every step input are committed to
    ``device`` (default: the first device), so the engine's jitted steps
    run there."""

    def __init__(self, arch: Union[str, cfgs.ModelConfig], *,
                 engine_id: str = "", smoke: bool = True,
                 device: Optional[jax.Device] = None,
                 max_batch: int = 8, max_seq: int = 384, seed: int = 0,
                 failure_rate: float = 0.0, straggle_s: float = 0.0,
                 backend: str = "auto", block_size: int = 32,
                 kv_blocks: Optional[int] = None, prefill_chunk: int = 32,
                 decode_impl: str = "auto"):
        from repro.inference import continuous as cb
        self.model = model_zoo.build(arch, smoke=smoke)
        self.cfg = self.model.cfg
        self.arch = arch if isinstance(arch, str) else self.cfg.name
        self.engine_id = engine_id or f"{self.arch}#0"
        assert self.cfg.vocab_size >= tok.VOCAB_SIZE
        self.device = device if device is not None else jax.devices()[0]
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.failure_rate = failure_rate
        self.straggle_s = straggle_s
        self._rng = np.random.default_rng(seed + 17)
        # one jitted init on the engine's device: normal, scale and cast
        # fuse, so no float32 copy of a stacked [layers, ...] leaf exists
        self.params = jax.jit(
            self.model.init_params,
            out_shardings=SingleDeviceSharding(self.device))(
                jax.random.PRNGKey(seed))
        self._jit_cache: Dict[Any, Any] = {}
        self.jit_compiles = 0      # distinct jit entries (compile proxy)
        # batches dispatch concurrently (static kinds beside the step
        # loop): this guards the jit cache's inserts and the meters below
        self._lock = threading.Lock()
        # decode backend: continuous batching wherever the architecture
        # supports a paged cache, unless explicitly pinned
        if backend == "auto":
            backend = "continuous" if cb.supports(self.cfg) else "static"
        elif backend == "continuous" and not cb.supports(self.cfg):
            raise ValueError(f"{self.arch}: architecture does not support "
                             "the continuous paged-KV backend")
        elif backend not in ("continuous", "static"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        self._batcher = None
        if backend == "continuous":
            self._batcher = cb.ContinuousBatcher(
                self, block_size=block_size, num_blocks=kv_blocks,
                prefill_chunk=prefill_chunk, decode_impl=decode_impl)
        # telemetry
        self.total_requests = 0
        self.total_tokens = 0
        self.total_credits = 0.0

    # ------------------------------------------------------------------
    # batching helpers
    # ------------------------------------------------------------------

    def _encode_batch(self, prompts: Sequence[str], cap: int
                      ) -> Tuple[np.ndarray, np.ndarray, int]:
        enc = [tok.encode(p, max_len=cap) for p in prompts]
        lens = np.asarray([len(e) for e in enc], np.int32)
        L = _bucket(int(lens.max()))
        L = min(L, cap)
        toks = np.full((len(enc), L), tok.PAD_ID, np.int32)
        for i, e in enumerate(enc):
            toks[i, :len(e)] = e[:L]
        return toks, np.minimum(lens, L), L

    def _modality_batch(self, requests: Sequence[Request], B: int,
                        S: int) -> Dict[str, np.ndarray]:
        extra: Dict[str, np.ndarray] = {}
        cfg = self.cfg
        if cfg.frontend == "frames":
            frames = np.stack([
                _hash_embed(r.metadata.get("file", r.prompt)[:128],
                            (cfg.encoder_seq, cfg.d_model))
                for r in requests])
            extra["frames"] = frames
        if cfg.frontend == "patches":
            P = min(cfg.num_patches, 16)  # smoke-scale patch count
            patches = np.stack([
                _hash_embed(r.metadata.get("file", r.prompt)[:128],
                            (P, cfg.d_model)) for r in requests])
            extra["patches"] = patches
            side = max(int(np.sqrt(P)), 1)
            pos = np.zeros((B, P + S, 3), np.int32)
            ar = np.arange(P)
            pos[:, :P, 0] = 0
            pos[:, :P, 1] = ar // side
            pos[:, :P, 2] = ar % side
            pos[:, P:, :] = (np.arange(S)[None, :, None] + 1)
            extra["positions"] = pos
        return extra

    def put(self, x):
        """Commit a host array (or pytree of them) to this engine's device."""
        return jax.device_put(x, self.device)

    def _jit(self, key, fn, donate=()):
        jitted = self._jit_cache.get(key)
        if jitted is not None:
            return jitted
        with self._lock:
            jitted = self._jit_cache.get(key)
            if jitted is not None:
                return jitted
            self.jit_compiles += 1
            name = _PROGRAM_NAMES.get(key[0])
            if name is not None:
                fn = _named(fn, name)
            jitted = self._jit_cache[key] = jax.jit(fn,
                                                    donate_argnums=donate)
        return _first_call(key, jitted)

    def _prefill(self, requests: Sequence[Request], cap: Optional[int] = None,
                 extra_capacity: int = 0):
        cap = cap or self.max_seq
        toks, lens, L = self._encode_batch([r.prompt for r in requests], cap)
        B = len(requests)
        extra = self._modality_batch(requests, B, L)
        smax = L + extra_capacity

        def prefill_fn(params, tokens, lengths, extra):
            cache = self.model.init_cache(tokens.shape[0], smax)
            batch = {"tokens": tokens, "lengths": lengths, **extra}
            out = self.model.apply(params, batch, mode="prefill", cache=cache)
            logits = self.model.logits_of(params, out["last_hidden"])
            return logits, out["cache"]

        fn = self._jit(("prefill", B, L, smax, tuple(sorted(extra))),
                       prefill_fn)
        logits, cache = fn(self.params, self.put(toks), self.put(lens),
                           self.put(extra))
        return logits, cache, lens, L

    # ------------------------------------------------------------------
    # request kinds
    # ------------------------------------------------------------------

    def _score_batch(self, requests: Sequence[Request],
                     t0: Optional[float] = None) -> List[Result]:
        t0 = time.perf_counter() if t0 is None else t0
        logits, _, lens, _ = self._prefill(requests)
        lf = np.asarray(logits, np.float32)
        py = lf[:, tok.YES_ID]
        pn = lf[:, tok.NO_ID]
        score = 1.0 / (1.0 + np.exp(-(py - pn)))   # P(yes | {yes,no})
        lat = time.perf_counter() - t0
        return [
            Result(r.request_id, self.arch, SCORE, score=float(score[i]),
                   tokens_in=int(lens[i]),
                   credits=credits_for(self.arch, int(lens[i])),
                   latency_s=lat, engine_id=self.engine_id)
            for i, r in enumerate(requests)]

    def _classify_batch(self, requests: Sequence[Request],
                        t0: Optional[float] = None) -> List[Result]:
        """Label-likelihood classification: logprob of each candidate label
        as a continuation of the prompt, softmax over candidates."""
        t0 = time.perf_counter() if t0 is None else t0
        results = []
        flat_prompts, flat_labels, owners = [], [], []
        for i, r in enumerate(requests):
            for lb in (r.labels or ()):
                flat_prompts.append(r.prompt + "\nanswer: ")
                flat_labels.append(lb)
                owners.append(i)
        if not flat_prompts:
            # no candidate labels: still a served (and metered) request —
            # prompt tokens were shipped even though no label was scored
            out = []
            for r in requests:
                ti = len(tok.encode(r.prompt, max_len=self.max_seq))
                out.append(Result(
                    r.request_id, self.arch, CLASSIFY, label=None, labels=(),
                    tokens_in=ti, credits=credits_for(self.arch, ti),
                    engine_id=self.engine_id))
            return _stamp_latency(out, t0)
        lps, tokens_used = self._sequence_logprob(flat_prompts, flat_labels)
        per_req: Dict[int, List[Tuple[str, float]]] = {}
        for o, lb, lp in zip(owners, flat_labels, lps):
            per_req.setdefault(o, []).append((lb, lp))
        tokens_per_req: Dict[int, int] = {}
        for o, t in zip(owners, tokens_used):
            tokens_per_req[o] = tokens_per_req.get(o, 0) + t
        for i, r in enumerate(requests):
            pairs = per_req.get(i, [])
            if not pairs:
                # label-less request coalesced into a labeled batch: serve
                # (and meter) it like the all-empty early-return path
                ti = len(tok.encode(r.prompt, max_len=self.max_seq))
                results.append(Result(
                    r.request_id, self.arch, CLASSIFY, label=None, labels=(),
                    tokens_in=ti, credits=credits_for(self.arch, ti),
                    engine_id=self.engine_id))
                continue
            lbls = [p[0] for p in pairs]
            lp = np.asarray([p[1] for p in pairs])
            probs = np.exp(lp - lp.max())
            probs = probs / probs.sum()
            order = np.argsort(-probs)
            top = lbls[int(order[0])]
            chosen: Tuple[str, ...]
            if r.multi_label:
                k = len(lbls)
                thr = 1.5 / max(k, 2)
                chosen = tuple(lbls[j] for j in order if probs[j] >= thr) or (top,)
            else:
                chosen = (top,)
            ti = tokens_per_req.get(i, 0)
            results.append(Result(
                r.request_id, self.arch, CLASSIFY, label=top, labels=chosen,
                tokens_in=ti, credits=credits_for(self.arch, ti),
                engine_id=self.engine_id))
        return _stamp_latency(results, t0)

    def _sequence_logprob(self, prompts: Sequence[str],
                          continuations: Sequence[str]):
        """Mean per-token logprob of each continuation given its prompt."""
        seqs, masks = [], []
        for p, c in zip(prompts, continuations):
            pe = tok.encode(p, max_len=self.max_seq // 2)
            ce = tok.encode(c, bos=False)
            seqs.append(pe + ce)
            masks.append([0] * len(pe) + [1] * len(ce))
        L = _bucket(max(len(s) for s in seqs))
        L = min(L, self.max_seq)
        B = len(seqs)
        toks = np.full((B, L), tok.PAD_ID, np.int32)
        msk = np.zeros((B, L), np.float32)
        for i, (s, m) in enumerate(zip(seqs, masks)):
            s, m = s[:L], m[:L]
            toks[i, :len(s)] = s
            msk[i, :len(m)] = m

        fn = self._jit(("seqlp", B, L), self._seqlp_fn)
        lps = np.asarray(fn(self.params, self.put(toks), self.put(msk)))
        return lps.tolist(), [int(m.sum() + (1 - m).sum()) for m in msk]

    def _seqlp_fn(self, params, tokens, mask):
        batch = {"tokens": tokens}
        if self.cfg.frontend == "frames":
            batch["frames"] = jnp.zeros(
                (tokens.shape[0], self.cfg.encoder_seq, self.cfg.d_model),
                jnp.bfloat16)
        out = self.model.apply(params, batch, mode="train", remat=False)

        def row_logprob(args):
            # one row's [L-1, vocab] fp32 logits at a time: the whole
            # batch's would not fit beside full-width weights
            h, tgt = args
            logits = self.model.logits_of(params, h)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return jnp.take_along_axis(logp, tgt[:, None], -1)[:, 0]

        # hidden[t] predicts token[t+1]
        lp = jax.lax.map(row_logprob, (out["hidden"][:, :-1], tokens[:, 1:]))
        m = mask[:, 1:]
        return jnp.sum(lp * m, 1) / jnp.maximum(jnp.sum(m, 1), 1.0)

    def _embed_batch(self, requests: Sequence[Request],
                     t0: Optional[float] = None) -> List[Result]:
        """Masked mean-pool of the final hidden states, projected to the
        requested dimensionality by a fixed seeded matrix and unit-
        normalized.  One encoder pass, no decode loop — which is why the
        EMBED tier prices input tokens only."""
        t0 = time.perf_counter() if t0 is None else t0
        toks, lens, L = self._encode_batch([r.prompt for r in requests],
                                           self.max_seq)
        B = len(requests)
        extra = self._modality_batch(requests, B, L)

        def embed_fn(params, tokens, lengths, extra):
            batch = {"tokens": tokens, **extra}
            out = self.model.apply(params, batch, mode="train", remat=False)
            h = out["hidden"].astype(jnp.float32)          # [B, L, D]
            mask = (jnp.arange(h.shape[1])[None, :]
                    < lengths[:, None]).astype(jnp.float32)
            pooled = jnp.sum(h * mask[..., None], axis=1) \
                / jnp.maximum(jnp.sum(mask, axis=1, keepdims=True), 1.0)
            return pooled

        fn = self._jit(("embed", B, L, tuple(sorted(extra))), embed_fn)
        pooled = np.asarray(fn(self.params, self.put(toks), self.put(lens),
                               self.put(extra)))
        results = []
        for i, r in enumerate(requests):
            dim = int(r.metadata.get("embed_dim", 64))
            proj = _hash_embed(f"{self.arch}|embed-proj|{dim}",
                               (pooled.shape[1], dim), scale=1.0)
            v = pooled[i] @ proj
            v = v / max(float(np.linalg.norm(v)), 1e-12)
            results.append(Result(
                r.request_id, self.arch, EMBED,
                embedding=tuple(float(x) for x in v),
                tokens_in=int(lens[i]),
                credits=credits_for(self.arch, int(lens[i]), EMBED),
                engine_id=self.engine_id))
        return _stamp_latency(results, t0)

    def _complete_batch(self, requests: Sequence[Request],
                        t0: Optional[float] = None) -> List[Result]:
        """Greedy decode over batch slots; finished sequences retire early
        (the static fallback path — the continuous backend admits new work
        at every step instead of batch boundaries)."""
        t0 = time.perf_counter() if t0 is None else t0
        B0 = len(requests)
        max_new = max(r.max_tokens for r in requests)
        # bucket the decode batch to powers of two: per-row results are
        # batch-independent, so padding with sentinel rows costs nothing
        # and keeps the decode jit key count logarithmic in batch size
        Bp = _bucket(B0, lo=1)
        padded: List[Request] = list(requests) + [
            Request("", self.arch, COMPLETE, max_tokens=1)
            for _ in range(Bp - B0)]
        logits, cache, lens, L = self._prefill(
            padded, extra_capacity=_bucket(max(max_new, 1), lo=16))
        B = Bp

        def decode_fn(params, cache, tokens):
            out = self.model.apply(params, {"tokens": tokens}, mode="decode",
                                   cache=cache)
            lg = self.model.logits_of(params, out["hidden"][:, 0])
            return lg, out["cache"]

        fn = self._jit(("decode", B, cache_sig(cache)), decode_fn)
        cur = np.asarray(jnp.argmax(logits, -1), np.int32)[:, None]
        done = np.zeros(B, bool)
        outs: List[List[int]] = [[] for _ in range(B)]
        finish = [t0] * B
        for step in range(max_new):
            for i in range(B):
                if not done[i]:
                    outs[i].append(int(cur[i, 0]))
                    if cur[i, 0] == tok.EOS_ID or len(outs[i]) >= padded[i].max_tokens:
                        done[i] = True
                        finish[i] = time.perf_counter()
            if done.all():
                break
            lg, cache = fn(self.params, cache, self.put(cur))
            cur = np.asarray(jnp.argmax(lg, -1), np.int32)[:, None]
        end = time.perf_counter()
        results = []
        for i, r in enumerate(requests):
            text = tok.decode(outs[i])
            ntok = int(lens[i]) + len(outs[i])
            results.append(Result(
                r.request_id, self.arch, COMPLETE, text=text,
                tokens_in=int(lens[i]), tokens_out=len(outs[i]),
                credits=credits_for(self.arch, ntok),
                latency_s=(finish[i] if done[i] else end) - t0,
                engine_id=self.engine_id))
        return results

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def submit_batch(self, requests: Sequence[Request]) -> List[Result]:
        """Serve one batch; safe to call from several threads at once.
        SCORE/COMPLETE join the continuous batcher's shared step loop;
        static-path batches run on the calling thread beside it, reading
        only ``params`` (the KV pool is the batcher's)."""
        if self.failure_rate and self._rng.random() < self.failure_rate:
            raise EngineFailure(f"{self.engine_id}: injected fault")
        if self.straggle_s:
            time.sleep(self.straggle_s)
        t0 = time.perf_counter()
        out: List[Result] = []
        cont: List[Request] = []
        by_kind: Dict[str, List[Request]] = {}
        for r in requests:
            if self._batcher is not None and r.kind in (SCORE, COMPLETE):
                cont.append(r)
            else:
                by_kind.setdefault(r.kind, []).append(r)
        if cont:
            out.extend(self._batcher.serve(cont, t0))
        tr = active_tracer()
        for kind, reqs in by_kind.items():
            for i in range(0, len(reqs), self.max_batch):
                chunk = reqs[i:i + self.max_batch]
                with tr.span(f"engine.{kind}", kind=f"engine.{kind}",
                             requests=len(chunk)):
                    if kind == SCORE:
                        out.extend(self._score_batch(chunk, t0))
                    elif kind == CLASSIFY:
                        out.extend(self._classify_batch(chunk, t0))
                    elif kind == EMBED:
                        out.extend(self._embed_batch(chunk, t0))
                    else:
                        out.extend(self._complete_batch(chunk, t0))
        with self._lock:
            for r in out:
                self.total_credits += r.credits
                self.total_tokens += r.tokens_in + r.tokens_out
            self.total_requests += len(requests)
        return self._restore_order(requests, out)

    def _restore_order(self, requests: Sequence[Request],
                       out: List[Result]) -> List[Result]:
        """Return results in submission order.  Duplicated request ids map
        to submission positions in production order (stable); a result
        whose id was never submitted is an engine invariant violation."""
        slots: Dict[int, List[int]] = {}
        for i, r in enumerate(requests):
            slots.setdefault(r.request_id, []).append(i)
        taken: Dict[int, int] = {}
        keyed: List[Tuple[int, Result]] = []
        for res in out:
            positions = slots.get(res.request_id)
            k = taken.get(res.request_id, 0)
            if positions is None or k >= len(positions):
                raise EngineFailure(
                    f"{self.engine_id}: result for unknown request_id "
                    f"{res.request_id!r}")
            taken[res.request_id] = k + 1
            keyed.append((positions[k], res))
        keyed.sort(key=lambda t: t[0])
        return [res for _, res in keyed]

    def hosted_models(self) -> List[str]:
        return [self.arch]

    def capacity_hint(self) -> int:
        """Preferred per-dispatch batch size (scheduler right-sizing).
        The continuous backend absorbs oversized batches through per-step
        admission, so it advertises a deeper queue."""
        if self._batcher is not None:
            return self.max_batch * 4
        return self.max_batch

    def backend_stats(self) -> Dict[str, Any]:
        """Decode-backend telemetry (continuous batching + jit entries)."""
        d: Dict[str, Any] = {"backend": self.backend,
                             "jit_entries": self.jit_compiles}
        if self._batcher is not None:
            d.update(self._batcher.stats())
        return d


def _stamp_latency(results: List[Result], t0: float) -> List[Result]:
    """Chunk-level latency for single-forward-pass kinds: every request in
    the chunk finished when the chunk did (no per-request step loop to
    attribute from)."""
    lat = time.perf_counter() - t0
    for r in results:
        r.latency_s = lat
    return results


def cache_sig(cache):
    leaves = jax.tree.leaves(cache)
    return tuple((l.shape, str(l.dtype)) for l in leaves[:3])
