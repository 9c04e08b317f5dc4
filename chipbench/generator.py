"""The one traffic generator: a mix file of parameters in, queries out.

A mix (``traffic/<mix>.json``) names its loop and its shapes:

* ``"loop": "closed"`` -- ``sessions`` analysts, each sending its next
  query when the last one returns.  Every query reads a fresh table of
  its own (``slices`` of them), so no two queries share a row.
* ``"loop": "open"`` -- queries from ``tenants`` drawn
  Zipf(``tenant_zipf``), arriving Poisson at ``rate_qps``; each query's
  shape is drawn by weight and its template Zipf(``template_zipf``) over
  ``templates`` ranks, the ranks in ``shared_ranks`` being the same SQL
  for every tenant.

Only the bytes of the texts depend on ``--seed``.  Row lengths, the
queries and their order come from a fixed stream, and the arrival gaps
are the rate's exponential quantiles in an order drawn from that stream.
So every seed asks the same work at the same moments: a tail latency
then varies from run to run with the system, not with a new arrival
pattern.

Nothing here imports the program: tables come out as plain columns.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

FIXED = 20240601            # the stream that fixes sizes and the query mix

WORDS = {
    "review": (
        "battery charger screen cracked arrived broken works great stopped "
        "after week return refund quality cheap sturdy loud quiet fast slow "
        "cable button leaks smell heavy light box missing parts manual easy "
        "hard install app update fails warranty replaced seller shipping "
        "late early color size fits tight loose love hate okay again never "
        "recommend would buy twice daughter kitchen office garden camera "
        "lens zoom blurry sharp speaker bass pairs drops signal").split(),
    "ticket": (
        "invoice charged twice card declined login password reset outage "
        "down error timeout page dashboard export report slow api key token "
        "expired plan upgrade downgrade cancel refund please urgent asap "
        "customer account admin user team seat license mobile app crash ios "
        "android browser chrome safari sync data missing deleted restore "
        "backup webhook integration email notification billing address tax "
        "receipt region latency spike since yesterday today morning").split(),
}


@dataclasses.dataclass
class Query:
    """One query of a run, with what the check needs to judge its answer."""
    sql: str
    tenant: str
    shape: str                  # filter | classify | complete
    table: str
    ids: List[int]              # rows the semantic operator reads
    texts: List[str]
    question: str = ""          # PROMPT template text before ' {0}'
    labels: Tuple[str, ...] = ()
    limit: Optional[int] = None
    max_tokens: int = 0
    due_s: float = 0.0          # open loop: due time from the window start
    key: Tuple = ()             # (shape, rank, table): equal keys, equal SQL

    @property
    def rows(self) -> int:
        return len(self.ids)

    def prompt(self, text: str) -> str:
        return f"{self.question} {text}" if self.shape != "classify" else text


@dataclasses.dataclass
class Workload:
    loop: str
    tables: Dict[str, Dict[str, list]]   # name -> {"id": [...], "text": [...]}
    queries: List[Query]                 # closed: in order; open: by due time
    warm: List[Query]                    # one query per shape, own tables
    sessions: int = 0
    rate_qps: float = 0.0


def lognormal_params(median: float, p95: float) -> Tuple[float, float]:
    return math.log(median), math.log(p95 / median) / 1.6448536269514722


def stratified_lengths(n: int, median: float, p95: float, cap: int,
                       rng: np.random.Generator) -> np.ndarray:
    """n lognormal lengths at the quantiles (i + 0.5) / n, in rng's order."""
    from statistics import NormalDist
    mu, sigma = lognormal_params(median, p95)
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.minimum(np.exp(mu + sigma * z).round().astype(int), cap)
    return rng.permutation(np.maximum(lengths, 8))


def iid_lengths(n: int, median: float, p95: float, cap: int,
                rng: np.random.Generator) -> np.ndarray:
    mu, sigma = lognormal_params(median, p95)
    lengths = np.exp(rng.normal(mu, sigma, n)).round().astype(int)
    return np.clip(lengths, 8, cap)


def make_texts(prefix: str, lengths: Sequence[int], kind: str,
               rng: np.random.Generator) -> List[str]:
    """Texts of exactly ``lengths`` ASCII bytes, each unique by its prefix."""
    words = WORDS[kind]
    out = []
    for i, n in enumerate(lengths):
        head = f"[{prefix}-{i}] "
        picks = rng.integers(0, len(words), size=n // 3 + 4)
        body = " ".join(words[j] for j in picks)
        while len(head) + len(body) < n:
            body += " " + body
        out.append((head + body)[:n])
    return out


def zipf_probs(n: int, a: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** a
    return w / w.sum()


def _cap(max_seq: int, shapes: Sequence[dict]) -> int:
    """Longest text whose prompt (BOS + question + space) fits max_seq."""
    longest = max((len(q) for s in shapes for q in s.get("questions", [""])),
                  default=0)
    return max_seq - longest - 2


def _fill(sql: str, **kw) -> str:
    for k, v in kw.items():
        sql = sql.replace("{" + k + "}", str(v))
    return sql


def _closed(mix: dict, seed: int, max_seq: int) -> Workload:
    shape = mix["shapes"][0]
    n, cap = shape["rows"], _cap(max_seq, mix["shapes"])
    text = mix["text"]
    sizes = np.random.default_rng(FIXED)
    content = np.random.default_rng(seed)
    tables, queries = {}, []
    for i in range(mix["slices"] + mix["sessions"]):
        name = f"s{i:04d}"
        lengths = stratified_lengths(n, text["median_bytes"],
                                     text["p95_bytes"], cap, sizes)
        texts = make_texts(name, lengths, text["kind"], content)
        tables[name] = {"id": list(range(n)), "text": texts}
        question = shape["questions"][0]
        queries.append(Query(
            sql=_fill(shape["sql"], table=name, question=question),
            tenant=f"analyst{i % mix['sessions']}", shape=shape["name"],
            table=name, ids=list(range(n)), texts=texts, question=question,
            key=(shape["name"], i, name)))
    warm = queries[mix["slices"]:]
    return Workload("closed", tables, queries[:mix["slices"]], warm,
                    sessions=mix["sessions"])


def _template(shape: dict, rank: int, table: str, rows: Dict[str, list],
              tenant: str) -> Query:
    n = shape["rows"]
    lo = shape["first_row"] + rank * n
    ids = list(range(lo, lo + n))
    texts = [rows[table][shape.get("column", "text")][i] for i in ids]
    q = Query(sql="", tenant=tenant, shape=shape["name"], table=table,
              ids=ids, texts=texts, limit=shape.get("limit"),
              max_tokens=shape.get("max_tokens", 0),
              key=(shape["name"], rank, table))
    if shape["name"] == "classify":
        sets = shape["label_sets"]
        q.labels = tuple(sets[rank % len(sets)])
        labels = ", ".join(f"'{x}'" for x in q.labels)
        q.sql = _fill(shape["sql"], table=table, lo=lo, hi=lo + n,
                      labels=labels)
    else:
        qs = shape["questions"]
        q.question = qs[rank % len(qs)]
        q.sql = _fill(shape["sql"], table=table, lo=lo, hi=lo + n,
                      question=q.question)
    return q


def _open(mix: dict, seed: int, seconds: float, max_seq: int) -> Workload:
    shapes = mix["shapes"]
    cap, text = _cap(max_seq, shapes), mix["text"]
    sizes = np.random.default_rng(FIXED)
    content = np.random.default_rng(seed)
    names = [f"t{k}" for k in range(mix["tenants"])] + ["shared", "warm"]
    tables = {}
    for name in names:
        lengths = iid_lengths(mix["table_rows"], text["median_bytes"],
                              text["p95_bytes"], cap, sizes)
        tables[name] = {"id": list(range(mix["table_rows"])),
                        "text": make_texts(name, lengths, text["kind"],
                                           content)}
        if "subject" in mix:
            sub = mix["subject"]
            lengths = iid_lengths(mix["table_rows"], sub["median_bytes"],
                                  sub["p95_bytes"], sub["max_bytes"], sizes)
            tables[name]["subject"] = make_texts(f"{name}s", lengths,
                                                 sub["kind"], content)
    # the queries: fixed for a given count
    count = max(int(mix["rate_qps"] * seconds), 1)
    weights = np.asarray([s["weight"] for s in shapes], float)
    p_tenant = zipf_probs(mix["tenants"], mix["tenant_zipf"])
    p_rank = zipf_probs(mix["templates"], mix["template_zipf"])
    shared = set(mix.get("shared_ranks", ()))
    picks = []
    for _ in range(count):
        s = int(sizes.choice(len(shapes), p=weights / weights.sum()))
        r = int(sizes.choice(mix["templates"], p=p_rank))
        t = int(sizes.choice(mix["tenants"], p=p_tenant))
        picks.append((s, r, t))
    order = sizes.permutation(count)
    u = (np.arange(count) + 0.5) / count
    gaps = sizes.permutation(-np.log1p(-u) / mix["rate_qps"])
    due = np.cumsum(gaps) - gaps[0] * 0.5
    queries = []
    for j, i in enumerate(order):
        s, r, t = picks[i]
        table = "shared" if r in shared else f"t{t}"
        q = _template(shapes[s], r, table, tables, f"tenant{t}")
        q.due_s = float(due[j])
        queries.append(q)
    warm = [_template(s, 0, "warm", tables, "warmup") for s in shapes]
    return Workload("open", tables, queries, warm,
                    rate_qps=float(mix["rate_qps"]))


def generate(mix: dict, seed: int, seconds: float, max_seq: int) -> Workload:
    if mix["loop"] == "open":
        return _open(mix, seed, seconds, max_seq)
    if mix["loop"] == "closed":
        return _closed(mix, seed, max_seq)
    raise ValueError(f"unknown loop {mix['loop']!r}")
