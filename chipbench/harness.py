"""One run of one cell: build, warm up, measure, check, report.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration (``configs/<config>.json``) and the architecture module
its ``model_type`` names (``reference/<model_type>.py``: the program's
model config, the counts and the float32 reference), its traffic mix
(``traffic/<mix>.json``, read by ``generator.py``), its check's sample
and limits (``limits/<workload>.json``) and one reader per per-layer
metric (``metrics/<metric>.py``).  Adding a cell, a configuration, an
architecture or a metric adds files and entries; nothing here changes.

The system under test is the program's own serving path: a
``JaxInferenceEngine`` built as ``make_engine_client`` builds one (the
engine's default slots, block size, prefill chunk and KV blocks; only the
deployment's ``max_seq`` comes from the configuration), registered in a
``Scheduler``, queried through ``ServingEngine.submit`` over a catalog of
the tables the mix generates from ``--seed``.

The benchmark watches the engine from outside, by wrapping four of its
methods on the one instance: ``submit_batch`` (every request and result
as the scheduler dispatched it), ``_sequence_logprob`` (the label
log-probabilities behind an AI_CLASSIFY answer, which its result drops),
the continuous batcher's ``_retire`` (the token ids a COMPLETE served,
which its text drops) and, in a traced run, its ``_decode_step`` (the
valid cache lengths of each decode step).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import re
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import flops, generator, tokenizer, tracereduce
from chipbench.peaks import peaks

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
LATE_S = 60.0          # how long past the window's close an answer may come
TRACE_S = 12.0         # seconds of the steady window a traced run records


# ---------------------------------------------------------------------------
# what a cell is made of
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    conf: dict                      # the configuration file, as it is run
    mix: dict                       # the traffic file
    check: dict                     # limits/<workload>.json ({} if none)
    end_to_end: List[dict]
    per_layer: List[dict]
    chips: int
    arch: Any                       # reference/<model_type>.py, loaded

    @property
    def max_seq(self) -> int:
        return int(self.conf["serving"]["max_seq"])


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    conf = json.loads((root / conf_entry["file"]).read_text())
    mix = json.loads((root / "chipbench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    check_path = root / "chipbench" / "limits" / f"{workload}.json"
    check = json.loads(check_path.read_text()) if check_path.exists() else {}
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    # a per-layer metric is read in the cells its ``workloads`` lists
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", ())]
    return Cell(workload, cell["config"], conf, mix, check, e2e, per_layer,
                int(cell["chips"]), load_arch(conf["model_type"], root))


def load_arch(model_type: str, root: Path = ROOT):
    """The architecture module of a configuration's ``model_type``,
    ``chipbench/reference/<model_type>.py`` under ``root``, loaded by path.
    It gives the program's ``model_config(conf, name)``, the sizes and
    counts ``Shape.of(conf)``, and the float32 reference ``run``."""
    if not re.fullmatch(r"\w[\w-]*", model_type):
        raise ValueError(f"model_type {model_type!r} names no module")
    path = root / "chipbench" / "reference" / f"{model_type}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"model_type {model_type!r}: no architecture module {path}")
    return load_module("chipbench.reference." + model_type, path)


def load_module(name: str, path: Path):
    """The Python file at ``path``, loaded as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod             # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# watching the engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Dispatch:
    t0: float
    t1: float
    requests: list
    results: list


class Recorder:
    """Wraps one engine instance's methods; keeps what they saw."""

    def __init__(self, engine):
        self.dispatches: List[Dispatch] = []
        self.tokens: Dict[int, List[int]] = {}      # id(request) -> ids
        self.label_lp: Dict[Tuple[str, str], float] = {}
        self.steps: List[Tuple[float, List[int]]] = []
        self._lock = threading.Lock()
        submit = engine.submit_batch

        def submit_batch(requests):
            t0 = time.perf_counter()
            out = submit(requests)
            with self._lock:
                self.dispatches.append(
                    Dispatch(t0, time.perf_counter(), list(requests), out))
            return out

        engine.submit_batch = submit_batch
        seqlp = engine._sequence_logprob

        def _sequence_logprob(prompts, continuations):
            lps, used = seqlp(prompts, continuations)
            with self._lock:
                self.label_lp.update(zip(zip(prompts, continuations), lps))
            return lps, used

        engine._sequence_logprob = _sequence_logprob
        batcher = engine._batcher
        retire = batcher._retire

        def _retire(s, *args, **kw):
            self.tokens[id(s.req)] = list(s.out)
            return retire(s, *args, **kw)

        batcher._retire = _retire
        self._batcher = batcher

    def watch_decode_steps(self):
        b = self._batcher
        step = b._decode_step

        def _decode_step(active, *args, **kw):
            lens = [int(b.lens_np[s.slot]) + 1 for s in active
                    if s is not None and s.state == "decode"]
            self.steps.append((time.perf_counter(), lens))
            return step(active, *args, **kw)

        b._decode_step = _decode_step

    def clear(self):
        self.dispatches, self.tokens, self.steps = [], {}, []
        self.label_lp = {}

    def served(self):
        """(request, result, what it served beyond its result) of every
        dispatched request: a COMPLETE's token ids, an AI_CLASSIFY's
        {label: mean log-probability}."""
        for d in self.dispatches:
            for req, res in zip(d.requests, d.results):
                if req.kind == "classify":
                    p = req.prompt + tokenizer.CLASSIFY_SUFFIX
                    yield req, res, {lb: self.label_lp.get((p, lb))
                                     for lb in req.labels or ()}
                else:
                    yield req, res, self.tokens.get(id(req))


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        self.names: List[str] = []

        def on_duration(event, duration_secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration_secs
                self.compiles += 1
                self.names.append(str(kw.get("fun_name", "?")))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# building and warming
# ---------------------------------------------------------------------------


def build_engine(arch, conf: dict, name: str, seed: int, device):
    """The engine as ``make_engine_client`` builds one, for the architecture
    module ``arch``'s model at the configuration's ``max_seq``, weights
    drawn from ``seed`` on device."""
    import jax
    from repro.inference.engine import JaxInferenceEngine
    engine = JaxInferenceEngine(arch.model_config(conf, name),
                                engine_id=f"{name}#0", seed=seed,
                                device=device,
                                max_seq=int(conf["serving"]["max_seq"]))
    jax.block_until_ready(engine.params)
    if engine._batcher is None:
        raise RuntimeError(f"{name}: the engine did not take the "
                           f"continuous backend")
    return engine


def build(cell: Cell, work: generator.Workload, seed: int, device):
    """Engine and serving engine of one run."""
    from repro.core import Catalog
    from repro.core.serving import ServingConfig, ServingEngine
    from repro.inference.scheduler import Scheduler
    from repro.obs import Observability
    from repro.tables.table import Table
    engine = build_engine(cell.arch, cell.conf, cell.config_name, seed,
                          device)
    sched = Scheduler()
    sched.register(engine)
    catalog = Catalog({n: Table(cols) for n, cols in work.tables.items()})
    serving = ServingEngine(catalog, sched, cfg=ServingConfig(
        default_model=engine.arch, proxy_model=engine.arch,
        obs=Observability(enabled=False)))
    return engine, serving


def _pow2(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def warm_up(engine, serving, work: generator.Workload, max_seq: int) -> None:
    """Every program shape the cell's traffic can reach, then its host path.

    The continuous batcher keys its prefill and decode steps by the
    power-of-two number of KV blocks its longest live slot needs, and the
    static CLASSIFY forward by (label sequences, power-of-two length).
    Each step is run once per reachable width through the engine's own
    jit cache, on empty slots (nothing is written); each CLASSIFY bucket
    gets one request batch.
    """
    import numpy as np
    from repro.inference.backend import CLASSIFY, Request
    b = engine._batcher
    qs = work.queries + work.warm
    gen = max((q.max_tokens for q in qs if q.shape == "complete"), default=0)
    longest = max((len(tokenizer.encode(q.prompt(t), max_len=max_seq))
                   for q in qs if q.shape != "classify" for t in q.texts),
                  default=0)
    if longest:
        top = min(_pow2(-(-(longest + gen + b.prefill_chunk)
                          // b.block_size)), b.kv.max_seq_blocks)
        put = engine.put
        zeros = np.zeros((b.slots,), np.int32)
        nb = 1
        while nb <= top:
            tables = put(np.zeros((b.slots, nb), np.int32))
            fn = engine._jit(("cb_prefill", b.slots, b.prefill_chunk, nb,
                              b.decode_impl), b._prefill_fn, donate=(1,))
            b.kv.pool, _, _ = fn(engine.params, b.kv.pool, tables, put(zeros),
                                 put(zeros), put(np.zeros(
                                     (b.slots, b.prefill_chunk), np.int32)))
            if gen:
                fn = engine._jit(("cb_decode", b.slots, nb, b.decode_impl),
                                 b._decode_fn, donate=(1,))
                b.kv.pool, _, _ = fn(engine.params, b.kv.pool, tables,
                                     put(zeros), put(zeros),
                                     put(np.zeros((b.slots, 1), np.int32)))
            nb *= 2
    cls = [q for q in qs if q.shape == "classify"]
    if cls:
        rows = max(q.rows for q in cls)
        labels = cls[0].labels
        half = max_seq // 2
        need = {min(_pow2(max(
            len(tokenizer.encode(t + tokenizer.CLASSIFY_SUFFIX, max_len=half))
            + len(tokenizer.encode(lb, bos=False))
            for t in q.texts for lb in q.labels), lo=32), max_seq)
            for q in cls}
        for L in sorted(need):
            p = max(min(L - 12, half) - len(tokenizer.CLASSIFY_SUFFIX) - 1, 1)
            for k in (rows, 2 * rows):
                batch = [Request(("w" * p) + f" {i}", engine.arch, CLASSIFY,
                                 labels=labels, request_id=i + 1)
                         for i in range(k)]
                engine.submit_batch(batch)
    # the host path: each warm query once, the closed loop's together
    tickets = [serving.submit(q.tenant, q.sql) for q in work.warm]
    for t in tickets:
        t.result()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Record:
    query: generator.Query
    start: float                    # due (open loop) or sent (closed loop)
    sent: float
    end: float = math.nan
    ok: bool = False
    error: str = ""
    table: Any = None
    ticket: Any = None


def _finish(rec: Record, timeout: float) -> Record:
    try:
        rec.table = rec.ticket.result(timeout=timeout)
        rec.ok = True
    except Exception as e:                       # failed, refused or late
        rec.error = f"{type(e).__name__}: {e}"
    rec.end = time.perf_counter()
    return rec


def run_closed(serving, work: generator.Workload, t0: float,
               seconds: float) -> List[Record]:
    t_end = t0 + seconds
    order = iter(work.queries)
    lock = threading.Lock()
    records: List[Record] = []

    def session():
        while time.perf_counter() < t_end:
            with lock:
                q = next(order)
            now = time.perf_counter()
            rec = Record(q, now, now, ticket=serving.submit(q.tenant, q.sql))
            with lock:
                records.append(rec)
            _finish(rec, timeout=t_end + LATE_S - time.perf_counter())

    threads = [threading.Thread(target=session, daemon=True)
               for _ in range(work.sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records


def run_open(serving, work: generator.Workload, t0: float, seconds: float
             ) -> Tuple[List[Record], List[float]]:
    """Send each query at its due time, whatever is still running."""
    records, late = [], []
    due = [q for q in work.queries if q.due_s < seconds]
    with ThreadPoolExecutor(max_workers=64) as pool:
        futures = []
        for q in due:
            at = t0 + q.due_s
            wait = at - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            now = time.perf_counter()
            late.append(now - at)
            rec = Record(q, at, now, ticket=serving.submit(q.tenant, q.sql))
            records.append(rec)
            futures.append(pool.submit(
                _finish, rec, t0 + seconds + LATE_S - now))
        for f in futures:
            f.result()
    return records, late


class Tracer:
    """Records ``TRACE_S`` seconds of the middle of the window."""

    def __init__(self, t0: float, seconds: float):
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        span = min(TRACE_S, seconds / 2)
        self.at = t0 + (seconds - span) / 2
        self.span = span
        self.host0 = self.stop_at = math.nan
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self._opts = opts
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        import jax
        time.sleep(max(self.at - time.perf_counter(), 0))
        self.host0 = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=self._opts)
        time.sleep(self.span)
        self.stop_at = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self) -> tracereduce.Reduced:
        self._thread.join()
        try:
            events = tracereduce.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return tracereduce.reduce(events)

    def covers(self, t: float) -> bool:
        return self.host0 <= t < self.stop_at


# ---------------------------------------------------------------------------
# what the readers see
# ---------------------------------------------------------------------------


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    """Share of [a0, a1] that lies inside [b0, b1]."""
    if a1 <= a0:
        return 1.0 if b0 <= a0 <= b1 else 0.0
    return max(min(a1, b1) - max(a0, b0), 0.0) / (a1 - a0)


def request_flops(shape, req, res, max_seq: int) -> float:
    if req.kind == "score":
        return flops.score(shape, len(tokenizer.encode(req.prompt,
                                                       max_len=max_seq)))
    if req.kind == "complete":
        return flops.complete(shape, len(tokenizer.encode(
            req.prompt, max_len=max_seq)), res.tokens_out)
    if req.kind == "classify":
        p = len(tokenizer.encode(req.prompt + tokenizer.CLASSIFY_SUFFIX,
                                 max_len=max_seq // 2))
        return flops.classify(shape, p, [len(tokenizer.encode(
            lb, bos=False)) for lb in (req.labels or ())])
    raise ValueError(req.kind)


@dataclasses.dataclass
class RunData:
    """Everything a per-layer metric reader may read."""
    cell: Cell
    shape: Any                               # the architecture's Shape
    peaks: dict
    t0: float
    seconds: float
    records: List[Record]
    dispatches: List[Dispatch]
    steps: List[Tuple[float, List[int]]]     # decode steps, traced run only
    backend: Tuple[dict, dict]               # engine.backend_stats() at t0, end
    pipeline: Tuple[dict, dict]              # pipeline stats at t0, end
    slots: int
    prefill_chunk: int
    trace: Optional[tracereduce.Reduced]
    tracer: Optional[Tracer]

    def delta(self, which: str, key: str) -> float:
        a, b = getattr(self, which)
        return float(b[key]) - float(a[key])

    def flops_in_window(self) -> float:
        t1 = self.t0 + self.seconds
        total = 0.0
        for d in self.dispatches:
            share = overlap(d.t0, d.t1, self.t0, t1)
            if share:
                total += share * sum(
                    request_flops(self.shape, q, r, self.cell.max_seq)
                    for q, r in zip(d.requests, d.results))
        return total

    def mfu_percent(self) -> float:
        return 100.0 * self.flops_in_window() / (self.seconds
                                                 * self.peaks["flops"])

    def step_ms(self, program: str) -> Optional[float]:
        if self.trace is None:
            return None
        n, s = self.trace.module(program)
        return 1e3 * s / n if n else None

    def due(self) -> List[Record]:
        return [r for r in self.records if r.start < self.t0 + self.seconds]


def load_reader(metric: str) -> Callable[[RunData], Optional[float]]:
    """``metrics/<metric>.py``'s ``read``; the file name is the metric's
    name, dots and all, so it is loaded by path."""
    return load_module("chipbench.metrics." + metric.replace(".", "_"),
                       BENCH / "metrics" / f"{metric}.py").read


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------


def rows_per_s(records: List[Record], t0: float, seconds: float) -> float:
    """Rows of semantic-operator input of the queries that completed, each
    counted for the share of its time that fell in the window."""
    t1 = t0 + seconds
    return sum(r.query.rows * overlap(r.sent, r.end, t0, t1)
               for r in records if r.ok) / seconds


def latencies(records: List[Record], t0: float, seconds: float) -> np.ndarray:
    """Due time to answer (or to failure) of every query due in the window."""
    return np.asarray([r.end - r.start for r in records
                       if r.start < t0 + seconds])


def end_to_end(name: str, records, t0, seconds) -> float:
    if name == "rows_per_s":
        return rows_per_s(records, t0, seconds)
    if name == "query_p50_s":
        return float(np.percentile(latencies(records, t0, seconds), 50))
    raise KeyError(f"no end-to-end metric {name!r}")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def log(*parts) -> None:
    print("[chipbench]", *parts, file=sys.stderr, flush=True)


def free(engine, serving) -> None:
    """Drop the program's device state before the reference runs."""
    import jax
    serving.close()
    batcher = engine._batcher
    for leaf in jax.tree.leaves((engine.params, batcher.kv.pool,
                                 batcher._dev or {})):
        leaf.delete()
    engine.params = batcher.kv.pool = batcher._dev = None
    engine._jit_cache.clear()
    gc.collect()


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, started: Optional[float] = None,
        devices: Optional[list] = None, controls: Sequence[str] = (),
        chip_peaks: Optional[dict] = None) -> dict:
    """One run of one cell; returns the result line's object.  ``controls``
    also reads those lower-precision streams' numbers (calibration only);
    ``chip_peaks`` stands in for the peaks table where a test drives a run
    on a device the table does not know."""
    import jax
    from chipbench import correct
    started = time.perf_counter() if started is None else started
    cell = load_cell(workload, root)
    devices = devices if devices is not None else jax.devices()
    if len(devices) < cell.chips:
        raise RuntimeError(f"{workload} needs {cell.chips} chips, JAX found "
                           f"{len(devices)}")
    device = devices[0]
    meter = CompileMeter()
    work = generator.generate(cell.mix, seed, seconds, cell.max_seq)
    engine, serving = build(cell, work, seed, device)
    warm_up(engine, serving, work, cell.max_seq)
    recorder = Recorder(engine)
    if trace:
        recorder.watch_decode_steps()
    compiles_before, hits_before = meter.compiles, meter.cache_hits
    backend0 = engine.backend_stats()
    pipe0 = serving.pipeline.stats_snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - started
    tracer = Tracer(t0, seconds) if trace else None
    if work.loop == "closed":
        records, late = run_closed(serving, work, t0, seconds), []
    else:
        records, late = run_open(serving, work, t0, seconds)
    backend1 = engine.backend_stats()
    pipe1 = serving.pipeline.stats_snapshot()
    compiles = meter.compiles - compiles_before
    loaded = meter.cache_hits - hits_before
    if compiles:
        log("compiled in the window:", meter.names[compiles_before:])
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices[:cell.chips])
    log(f"window {seconds} s: {len(records)} queries, "
        f"{sum(not r.ok for r in records)} failed, {compiles} compiles and "
        f"{loaded} cache loads in the window, setup {setup_s:.3f} s "
        f"({meter.compiles} compiles, {meter.cache_hits} cache hits)")
    if late:
        log(f"generator lateness: max {max(late) * 1e3:.3f} ms, p95 "
            f"{float(np.percentile(late, 95)) * 1e3:.3f} ms over {len(late)}")
    data = RunData(cell, cell.arch.Shape.of(cell.conf),
                   chip_peaks or peaks(device.device_kind), t0,
                   seconds, records, list(recorder.dispatches),
                   list(recorder.steps), (backend0, backend1), (pipe0, pipe1),
                   engine.max_batch, engine._batcher.prefill_chunk,
                   tracer.reduce() if tracer else None, tracer)
    served = list(recorder.served())
    recorder.clear()
    free(engine, serving)
    del engine, serving
    gc.collect()
    due = data.due()
    failed = sum(not r.ok for r in due)
    metrics: Dict[str, dict] = {}
    if trace:
        for m in cell.per_layer:
            value = load_reader(m["name"])(data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = end_to_end(m["name"], records, t0, seconds)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    verdict = correct.check(cell, seed, records, served,
                            window_compiles=compiles + loaded,
                            controls=controls)
    out = {"correct": verdict.correct and failed == 0,
           "attempted": len(due), "failed": failed, "metrics": metrics,
           "device": {"platform": device.platform, "kind": device.device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": mem_peak}}
    if trace and data.trace is not None:
        out["device"]["busy_s"] = data.trace.busy_s
        out["device"]["window_s"] = data.trace.window_s
        out["breakdown"] = tracereduce.breakdown(data.trace)
    if controls:
        out["control"] = verdict.control
        out["control_correct"] = verdict.control_correct
        out["gaps"] = verdict.gaps
    out["checks"] = verdict.checks
    return out
