"""Continuous-batching decode backend (per-step admission, paged KV).

The static path in ``engine.py`` runs one blocking prefill+decode call per
batch: finished slots retire early from the *loop*, but freed capacity is
only refilled at batch boundaries, so a 4-token AI_FILTER score queues
behind a 128-token AI_COMPLETE generation that happens to share the batch.
This module is the backend the paper's serving layer actually wants:

  * **slots** — a fixed-width in-flight batch (XLA static shapes).  Every
    step, finished sequences retire (EOS or max_tokens), their KV blocks
    return to the pool, and queued requests are admitted into the freed
    slots — admission happens at *every* step, not at batch boundaries;
  * **paged KV** — each sequence owns a block table over a shared pool
    (``paged_kv.PagedKVCache``); a step gathers the dense view, runs the
    model, and scatters only the newly valid keys/values back;
  * **chunked prefill** — prompts enter the cache ``prefill_chunk`` tokens
    at a time, batched across every prefilling slot and interleaved with
    decode steps, so a long prompt never stalls in-flight decodes for its
    full length;
  * **flash decode** — single-token steps route ``decode_attention``
    through the ``kernels/decode_attention`` flash path
    (``attention.use_decode_impl``): Pallas on TPU, the jnp reference
    off-TPU.

Determinism contract on the CPU: generated text, token counts and credits
are identical to the static path; SCOREs agree to float32 rounding.
Chunked decode-mode prefill contracts the same valid positions as one-shot
prefill (masked tails contribute exact zeros) but reduces them in another
order, so logits may differ in their last bits.  Per-row outputs are
independent of batch composition, and the flash-decode reference is
bitwise equal to the dense cache attention.  The parity tests in
``tests/test_backend.py`` pin these.  On the TPU, bfloat16 activations
and shape-dependent MXU sums move SCOREs by about 1e-2 even within the
static path; ``chip_smoke.py`` holds the chip to stated tolerances.

Callers share one step loop.  ``serve`` enqueues its sequences on one
batcher-wide FIFO; the caller that finds no loop running becomes the
*driver* and steps every caller's sequences, while the others wait until
their own have retired.  A driver drives only until its own sequences
have retired, then hands the loop to a waiting caller, so no caller
serves other callers' work for ever.  Admission stays strict FIFO in
enqueue order across callers; mixing callers in one step is safe because
of the determinism contract above: a row's result does not depend on
what shares its step.  A lone caller runs exactly the steps it would run
alone.

Tracing: the loop's phases are spans (``engine.wave``, ``engine.join``,
``engine.tokenize``, ``engine.admit``, ``engine.prefill_step``,
``engine.decode_step``, ``engine.readback``, ``engine.retire``; see
``repro.obs.trace``), so a ``jax.profiler`` trace names what the host did
in each device idle gap.  ``engine.wave`` is a driver's stretch at the
loop, ``engine.join`` a caller waiting for its sequences on another
caller's loop.  The step spans time the host side only, building inputs
and dispatching; the device's time is the profiler's.  ``stats()`` adds
the wall seconds the loop ran, counted once whoever drove it
(``loop_s``), those blocked in readbacks (``readback_s``), and the
sequences admitted while another caller's were live or pending
(``joined``).  Nothing here synchronizes with the device beyond the
readbacks the loop needs anyway.

Sliding-window layers page like global ones: each keeps a full per-layer
paged cache, and its attention masks the positions before the window
(``attention.decode_attention``, ``flash_decode``'s ``window``).  So a
step gathers, for a windowed layer, blocks it then masks away:
``window_blocks`` counts the KV blocks each step gathered for its
windowed layers, over the rows it advanced and those layers, and
``window_blocks_outside`` those of them wholly before the row's window
(before the first position the row's earliest new token sees).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.configs import base as cfgs
from repro.inference import tokenizer as tok
from repro.inference.backend import (COMPLETE, SCORE, EngineFailure, Request,
                                     Result, credits_for)
from repro.inference.paged_kv import PagedKVCache
from repro.models import attention
from repro.obs.trace import active_tracer


def supports(cfg) -> bool:
    """Continuous batching serves attention decoders, global and
    sliding-window layers alike: every block's KV cache must be a flat
    per-layer [B, Smax] tensor for the paged pool to tile (a windowed
    layer gets one too and masks its window; recurrent states and encoder
    caches fall back to the static path)."""
    if cfg.is_encoder_decoder or cfg.frontend != "none":
        return False
    return all(b in (cfgs.ATTN, cfgs.LOCAL_ATTN) for b in cfg.block_pattern)


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class _Caller:
    """One ``serve`` call: its results, its clock, its sequences left."""
    __slots__ = ("t0", "results", "left", "error")

    def __init__(self, n: int, t0: float):
        self.t0 = t0
        self.results: List[Optional[Result]] = [None] * n
        self.left = n              # sequences not yet retired
        self.error: Optional[BaseException] = None


@dataclasses.dataclass
class _Seq:
    """One in-flight sequence (a slot's occupant)."""
    req: Request
    index: int                 # position in the submitted request list
    enc: List[int]             # encoded prompt
    slot: int
    blocks: List[int]
    state: str = "prefill"     # "prefill" -> "decode" (COMPLETE only)
    filled: int = 0            # prompt tokens already in the paged cache
    cur: int = -1              # last sampled token (next decode input)
    out: List[int] = dataclasses.field(default_factory=list)
    caller: Optional[_Caller] = None


class ContinuousBatcher:
    """Step loop + paged-KV state for one :class:`JaxInferenceEngine`.

    Owns no model/params — it drives the engine's model through two jitted
    step functions (shared via ``engine._jit`` so compile counting and
    caching live in one place).  Safe for concurrent ``serve`` calls,
    which share the loop (module docstring): ``_cv`` guards the pending
    queue and the callers' hand-off; the slots, the pool and the step
    state belong to whichever caller drives.
    """

    def __init__(self, engine, *, block_size: int = 32,
                 num_blocks: Optional[int] = None, prefill_chunk: int = 32,
                 decode_impl: str = "auto"):
        self.engine = engine
        self.model = engine.model
        self.slots = engine.max_batch
        self.block_size = int(block_size)
        self.prefill_chunk = int(prefill_chunk)
        self.decode_impl = decode_impl
        if num_blocks is None:
            # every slot can hold a full-length prompt plus a generous
            # generation budget; +1 for the sacrificial scratch block
            per_seq = -(-(engine.max_seq + 4 * self.prefill_chunk)
                        // self.block_size)
            num_blocks = self.slots * per_seq + 1
        self.kv = PagedKVCache(self.model, block_size=self.block_size,
                               num_blocks=num_blocks, device=engine.device)
        width = self.kv.max_seq_blocks
        self.tables_np = np.zeros((self.slots, width), np.int32)
        self.lens_np = np.zeros((self.slots,), np.int32)
        # device mirror of (block tables, lengths, decode-active mask),
        # valid between slot mutations — see _device_state
        self._dev: Optional[Dict[str, Any]] = None
        self._cv = threading.Condition()
        self._pending: Deque[_Seq] = deque()   # every caller's, FIFO
        self._active: List[Optional[_Seq]] = [None] * self.slots
        self._callers = 0          # callers with sequences live or pending
        self._driving = False      # a caller is running the step loop
        # telemetry
        self.waves = 0             # serve() calls
        self.admitted = 0          # sequences admitted into slots
        self.joined = 0            # of which beside another caller's
        self.retired = 0
        self.retired_eos = 0       # retired on EOS before max_tokens
        self.prefill_steps = 0
        self.decode_steps = 0
        self.prefill_rows = 0      # prefilling slots, summed over prefill steps
        self.prefill_tokens = 0    # prompt tokens written via chunked prefill
        self.decode_tokens = 0     # decode-step slot participations
        self.peak_blocks = 0
        self.loop_s = 0.0          # wall seconds the step loop ran
        self.readback_s = 0.0      # of which blocked reading step outputs
        self._windowed = sum(b == cfgs.LOCAL_ATTN
                             for b in self.model.cfg.block_pattern)
        self.window_blocks = 0     # gathered for windowed layers (docstring)
        self.window_blocks_outside = 0   # of which wholly before the window

    # ------------------------------------------------------------------
    # serve loop
    # ------------------------------------------------------------------

    def serve(self, requests: Sequence[Request],
              t0: Optional[float] = None) -> List[Result]:
        """Serve SCORE/COMPLETE requests to completion; returns results in
        submission order with per-request completion-time latency (from
        ``t0``).  Tokenizes outside any lock, enqueues, then drives the
        step loop or joins the one another caller drives.  A request that
        can never fit the pool raises `EngineFailure` here, before any of
        the call's requests is enqueued."""
        t0 = time.perf_counter() if t0 is None else t0
        tr = active_tracer()
        caller = _Caller(len(requests), t0)
        with tr.span("engine.tokenize", kind="engine.tokenize"):
            seqs = [_Seq(req=r, index=i, slot=-1, blocks=[], caller=caller,
                         enc=tok.encode(r.prompt, max_len=self.engine.max_seq))
                    for i, r in enumerate(requests)]
        for seq in seqs:
            need = self._blocks_needed(seq)
            if need > self.kv.max_seq_blocks:
                raise EngineFailure(
                    f"{self.engine.engine_id}: request {seq.req.request_id} "
                    f"needs {need} KV blocks, pool holds "
                    f"{self.kv.max_seq_blocks} (raise kv_blocks)")
        with self._cv:
            self.waves += 1
            if not seqs:
                return []
            self._pending.extend(seqs)
            self._callers += 1
            drive = not self._driving
            self._driving = True
        if not drive:
            with tr.span("engine.join", kind="engine.join",
                         requests=len(requests)):
                drive = self._wait(caller)
        if drive:
            self._drive(caller, tr)
        if caller.error is not None:
            raise caller.error
        return caller.results  # type: ignore[return-value]

    def _wait(self, caller: _Caller) -> bool:
        """Block until ``caller``'s sequences have retired (False) or the
        loop is handed to it with some still to serve (True)."""
        with self._cv:
            while caller.left and caller.error is None and self._driving:
                self._cv.wait()
            if not caller.left or caller.error is not None:
                return False
            self._driving = True
            return True

    def _drive(self, caller: _Caller, tr) -> None:
        """Run the step loop over every caller's sequences until
        ``caller``'s own have retired, then hand the loop on."""
        start = time.perf_counter()
        active = self._active
        try:
            with tr.span("engine.wave", kind="engine.wave",
                         requests=len(caller.results)):
                while True:
                    with self._cv:
                        if not caller.left:
                            break
                        with tr.span("engine.admit", kind="engine.admit"):
                            self._admit(self._pending, active)
                    if any(s is not None and s.state == "prefill"
                           for s in active):
                        self._prefill_step(active)
                    if any(s is not None and s.state == "decode"
                           for s in active):
                        self._decode_step(active)
        except BaseException as e:
            self._abort(caller, e)
            raise
        finally:
            self.loop_s += time.perf_counter() - start
            with self._cv:
                self._driving = False
                self._cv.notify_all()

    def _abort(self, driver: _Caller, error: BaseException) -> None:
        """The loop failed under ``driver``: fail every other caller with
        sequences in the batcher, and empty the slots and the queue."""
        with self._cv:
            seqs = list(self._pending) + [s for s in self._active
                                          if s is not None]
            for s in seqs:
                if s.caller is not driver and s.caller.error is None:
                    s.caller.error = EngineFailure(
                        f"{self.engine.engine_id}: the step loop failed "
                        f"under another caller: {error!r}")
                    s.caller.error.__cause__ = error
            for s in self._active:
                if s is not None:
                    self.kv.free_blocks(s.blocks)
            self._pending.clear()
            self._active[:] = [None] * self.slots
            self.tables_np[:] = 0
            self.lens_np[:] = 0
            self._dev = None
            self._callers = 0
            self._cv.notify_all()

    def _readback(self, x, dtype) -> np.ndarray:
        """A step output on the host: blocks until the device computed it."""
        start = time.perf_counter()
        with active_tracer().span("engine.readback", kind="engine.readback"):
            out = np.asarray(x, dtype)
        self.readback_s += time.perf_counter() - start
        return out

    # ------------------------------------------------------------------

    def _blocks_needed(self, seq: _Seq) -> int:
        horizon = len(seq.enc)
        if seq.req.kind == COMPLETE:
            horizon += max(int(seq.req.max_tokens), 1)
        return self.kv.blocks_for(horizon)

    def _admit(self, pending: Deque[_Seq], active: List[Optional[_Seq]]
               ) -> int:
        """FIFO admission into free slots while KV blocks last, in enqueue
        order across callers (held under ``_cv``).  Head-of-line order is
        kept deliberately: skipping ahead would make results depend on
        pool pressure, and the determinism contract forbids it (per-row
        results are batch-independent, so order alone is enough).
        """
        n = 0
        free_slots = [i for i, s in enumerate(active) if s is None]
        while pending and free_slots:
            seq = pending[0]
            need = self._blocks_needed(seq)
            if not self.kv.can_alloc(need):
                break
            pending.popleft()
            seq.slot = free_slots.pop(0)
            seq.blocks = self.kv.alloc(need)
            self.tables_np[seq.slot, :] = 0
            self.tables_np[seq.slot, :need] = seq.blocks
            self.lens_np[seq.slot] = 0
            active[seq.slot] = seq
            n += 1
        if n:
            self._dev = None
        self.admitted += n
        if self._callers > 1:
            self.joined += n
        used = self.kv.num_blocks - 1 - self.kv.free_count
        self.peak_blocks = max(self.peak_blocks, used)
        return n

    def _device_state(self, active: List[Optional[_Seq]], nb: int
                      ) -> Dict[str, Any]:
        """Device mirror of the per-slot step state.

        Rebuilt from the host arrays only when a slot mutated (admission,
        retirement, prefill->decode flip) or the bucketed table width
        changed; across steady-state decode runs — the dominant phase —
        every step reuses it, so the only per-step host->device transfer
        is the sampled-token vector.  The ``.copy()`` calls matter:
        ``device_put`` of an aligned numpy array can be zero-copy on CPU
        and execution is asynchronous, so jit must never alias a host
        buffer the step loop later mutates.  ``lens`` is threaded through
        the step functions (each returns the advanced lengths), keeping
        it device-resident between rebuilds."""
        if self._dev is None or self._dev["nb"] != nb:
            act = np.asarray(
                [1 if (s is not None and s.state == "decode") else 0
                 for s in active], np.int32)
            put = self.engine.put
            self._dev = {
                "nb": nb,
                "tables": put(self.tables_np[:, :nb].copy()),
                "lens": put(self.lens_np.copy()),
                "act": put(act),
            }
        return self._dev

    def _gather_width(self, active: List[Optional[_Seq]], horizon: int
                      ) -> int:
        """Block-table width for this step: max blocks any live row needs
        to cover ``len + horizon`` tokens, bucketed to a power of two to
        bound jit keys."""
        nb = 1
        for s in active:
            if s is not None:
                h = horizon if s.state == "prefill" else 1
                nb = max(nb, self.kv.blocks_for(int(self.lens_np[s.slot]) + h))
        return min(_pow2(nb), self.kv.max_seq_blocks)

    def _count_window(self, seqs: Sequence[_Seq], nb: int) -> None:
        """The window counters of one step gathering ``nb`` blocks for the
        rows of ``seqs``, before their lengths advance."""
        if not self._windowed:
            return
        W, bs = self.model.cfg.attention_window, self.block_size
        for s in seqs:
            first = int(self.lens_np[s.slot]) - W + 1   # first position seen
            self.window_blocks += nb * self._windowed
            self.window_blocks_outside += (min(max(first, 0) // bs, nb)
                                           * self._windowed)

    # ------------------------------------------------------------------
    # batched chunked prefill
    # ------------------------------------------------------------------

    def _prefill_step(self, active) -> None:
        C = self.prefill_chunk
        B = self.slots
        pre = [s for s in active if s is not None and s.state == "prefill"]
        with active_tracer().span("engine.prefill_step",
                                  kind="engine.prefill_step", rows=len(pre)):
            nb = self._gather_width(active, C)
            toks = np.zeros((B, C), np.int32)
            counts = np.zeros((B,), np.int32)
            for s in pre:
                v = min(C, len(s.enc) - s.filled)
                toks[s.slot, :v] = s.enc[s.filled:s.filled + v]
                counts[s.slot] = v
            key = ("cb_prefill", B, C, nb, self.decode_impl)
            fn = self.engine._jit(key, self._prefill_fn, donate=(1,))
            dev = self._device_state(active, nb)
            self._count_window(pre, nb)
            self.kv.pool, logits, new_lens = fn(
                self.engine.params, self.kv.pool, dev["tables"], dev["lens"],
                self.engine.put(counts), self.engine.put(toks))
            self.prefill_steps += 1
            self.prefill_rows += len(pre)
            self.prefill_tokens += int(counts.sum())
            for s in pre:
                v = int(counts[s.slot])
                s.filled += v
                self.lens_np[s.slot] += v
            dev["lens"] = new_lens
        lf = None
        for s in pre:
            if s.filled >= len(s.enc):
                if lf is None:
                    lf = self._readback(logits, np.float32)
                self._finish_prefill(s, lf[s.slot], active)

    def _prefill_fn(self, params, pool, tables, lens, counts, toks):
        cache = self.kv.gather(pool, tables, lens)
        C = toks.shape[1]
        pos = lens[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        out = self.model.apply(params, {"tokens": toks, "positions": pos},
                               mode="decode", cache=cache, paged=True)
        last = jnp.clip(counts - 1, 0, C - 1)
        h = out["hidden"][jnp.arange(toks.shape[0]), last]
        logits = self.model.logits_of(params, h)
        pool = self.kv.scatter(pool, out["cache"], tables, lens, counts, C)
        return pool, logits, lens + counts

    def _finish_prefill(self, s: _Seq, logits_row: np.ndarray, active
                        ) -> None:
        r = s.req
        if r.kind == SCORE:
            # the static _score_batch's arithmetic on these logits
            py = logits_row[tok.YES_ID]
            pn = logits_row[tok.NO_ID]
            score = 1.0 / (1.0 + np.exp(-(py - pn)))
            self._retire(s, active, score=float(score))
            return
        s.cur = int(np.argmax(logits_row))
        s.state = "decode"
        self._dev = None        # slot joins the decode-active mask
        self._consume(s, active)

    # ------------------------------------------------------------------
    # decode step
    # ------------------------------------------------------------------

    def _decode_step(self, active) -> None:
        B = self.slots
        dec = [s for s in active if s is not None and s.state == "decode"]
        with active_tracer().span("engine.decode_step",
                                  kind="engine.decode_step", rows=len(dec)):
            nb = self._gather_width(active, 1)
            cur = np.zeros((B, 1), np.int32)
            for s in dec:
                cur[s.slot, 0] = s.cur
            key = ("cb_decode", B, nb, self.decode_impl)
            fn = self.engine._jit(key, self._decode_fn, donate=(1,))
            dev = self._device_state(active, nb)
            self._count_window(dec, nb)
            self.kv.pool, nxt_dev, new_lens = fn(
                self.engine.params, self.kv.pool, dev["tables"], dev["lens"],
                dev["act"], self.engine.put(cur))
            self.decode_steps += 1
            self.decode_tokens += len(dec)
            for s in dec:
                self.lens_np[s.slot] += 1
            dev["lens"] = new_lens
        nxt = self._readback(nxt_dev, np.int32)
        for s in dec:
            s.cur = int(nxt[s.slot])
            self._consume(s, active)

    def _decode_fn(self, params, pool, tables, lens, act, cur):
        cache = self.kv.gather(pool, tables, lens)
        with attention.use_decode_impl(self.decode_impl):
            out = self.model.apply(params, {"tokens": cur}, mode="decode",
                                   cache=cache, paged=True)
        logits = self.model.logits_of(params, out["hidden"][:, 0])
        pool = self.kv.scatter(pool, out["cache"], tables, lens, act, 1)
        return pool, jnp.argmax(logits, -1), lens + act

    def _consume(self, s: _Seq, active) -> None:
        """Append the sampled token and retire on EOS / max_tokens —
        exactly the static loop's append-then-check chain."""
        s.out.append(s.cur)
        if s.cur == tok.EOS_ID or len(s.out) >= s.req.max_tokens:
            if s.cur == tok.EOS_ID and len(s.out) < s.req.max_tokens:
                self.retired_eos += 1
            self._retire(s, active)

    # ------------------------------------------------------------------

    def _retire(self, s: _Seq, active, score: Optional[float] = None
                ) -> None:
        """Free ``s``'s slot and blocks, and hand its result to its
        caller; the caller's last one wakes it."""
        with active_tracer().span("engine.retire", kind="engine.retire"):
            r = s.req
            eng = self.engine
            ti = len(s.enc)
            if r.kind == SCORE:
                res = Result(r.request_id, eng.arch, SCORE, score=score,
                             tokens_in=ti, credits=credits_for(eng.arch, ti),
                             engine_id=eng.engine_id)
            else:
                res = Result(r.request_id, eng.arch, COMPLETE,
                             text=tok.decode(s.out), tokens_in=ti,
                             tokens_out=len(s.out),
                             credits=credits_for(eng.arch, ti + len(s.out)),
                             engine_id=eng.engine_id)
            caller = s.caller
            res.latency_s = time.perf_counter() - caller.t0
            caller.results[s.index] = res
            self.kv.free_blocks(s.blocks)
            active[s.slot] = None
            self.lens_np[s.slot] = 0
            self.tables_np[s.slot, :] = 0
            self._dev = None
            self.retired += 1
            with self._cv:
                caller.left -= 1
                if not caller.left:
                    self._callers -= 1
                    self._cv.notify_all()

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        occ = (self.decode_tokens / (self.decode_steps * self.slots)
               if self.decode_steps else 0.0)
        return {
            "waves": self.waves, "admitted": self.admitted,
            "joined": self.joined,
            "retired": self.retired, "retired_eos": self.retired_eos,
            "prefill_steps": self.prefill_steps,
            "decode_steps": self.decode_steps,
            "prefill_rows": self.prefill_rows,
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "decode_slot_occupancy": occ,
            "kv_blocks": self.kv.num_blocks,
            "kv_block_size": self.block_size,
            "kv_peak_blocks": self.peak_blocks,
            "loop_s": self.loop_s,
            "readback_s": self.readback_s,
            "window_blocks": self.window_blocks,
            "window_blocks_outside": self.window_blocks_outside,
        }
