"""Whether what the timed path produced is right.

Numbers, each held to its limit from ``limits/<workload>.json`` (only
those the file names are compared, and one it names that was not read
fails):

* ``rows_wrong`` -- queries whose rows disagree with the answers the
  engine served for them: an AI_FILTER must return exactly the rows whose
  served SCORE is at least 0.5 (with ``LIMIT k``, k of them, or all there
  are), an AI_CLASSIFY the served label of each row, an AI_COMPLETE one
  row per input row.  Exact: its limit is 0.
* ``window_compiles`` -- programs compiled or loaded from the compile
  cache inside the measured window.  Always compared, limit 0.
* against the float32 reference of the configuration's architecture
  (``reference/<model_type>.py``), over a sample of the served requests
  drawn from ``--seed`` with the longest of each kind in it, the widest
  gap (``<gap>``) and, for a kind the limits file gives a ``tolerance``,
  the share of the sample whose gap exceeds it (``<gap>_share``):
  - ``score_gap``: between a served SCORE's logit, log(s / (1 - s)),
    and the reference's logit(yes) - logit(no) after the same prompt;
  - ``token_gap``: by which the reference's logit of a served (greedy)
    COMPLETE token lies below the reference's best at that position, the
    prompt and earlier served tokens fed in (a request's widest);
  - ``label_lp_gap``: between the mean log-probability of a candidate
    label after an AI_CLASSIFY prompt as the engine scored it and as the
    reference scores it.

A control is a lower-precision stream of the reference (int8 or fp8,
``reference/common.py``, the same code for every architecture),
put in the program's place on the same prompts and tokens: its numbers
read the same gaps for what it would have served, and the same limits
judge it.  A limit is sound only where every control fails it.
"""
from __future__ import annotations

import dataclasses
import math
import sys
import time
from typing import Dict

import numpy as np

from chipbench import tokenizer
from chipbench.reference.common import Probe

KINDS = ("score", "complete", "classify")


# kind of gap -> the name of its widest
GAP_NAMES = {"score": "score_gap", "token": "token_gap",
             "label": "label_lp_gap"}
GAP_KINDS = tuple(GAP_NAMES)


@dataclasses.dataclass
class Verdict:
    correct: bool
    checks: Dict[str, dict]          # name -> {"value": v, "limit": l}
    control: Dict[str, Dict[str, float]]     # control -> {number: value}
    control_correct: Dict[str, bool]         # control -> passes the limits
    gaps: Dict[str, Dict[str, list]]         # stream -> kind -> gaps


def served_maps(served):
    scores, labels, tokens = {}, {}, {}
    for req, res, toks in served:
        if req.kind == "score":
            scores[req.prompt] = res.score
        elif req.kind == "classify":
            labels[(req.prompt, tuple(req.labels or ()))] = res.label
        elif req.kind == "complete":
            tokens[req.prompt] = toks
    return scores, labels, tokens


def rows_wrong(records, served) -> int:
    scores, labels, _ = served_maps(served)
    wrong = 0
    for rec in records:
        if not rec.ok:
            continue
        q, table = rec.query, rec.table
        ids = [int(x) for x in table.column("id")]
        if q.shape == "filter":
            passing = [i for i, t in zip(q.ids, q.texts)
                       if scores.get(q.prompt(t), -1.0) >= 0.5]
            if any(q.prompt(t) not in scores for t in q.texts):
                ok = False
            elif q.limit is None:
                ok = sorted(ids) == passing
            else:
                ok = (set(ids) <= set(passing)
                      and len(ids) == min(q.limit, len(passing)))
        elif q.shape == "classify":
            want = {i: labels.get((t, q.labels)) for i, t in zip(q.ids, q.texts)}
            got = {int(r["id"]): r["label"] for r in table.rows()}
            ok = got == want and None not in want.values()
        else:
            ok = sorted(ids) == sorted(q.ids)
        wrong += not ok
    return wrong


def draw(served, sizes: Dict[str, int], seed: int):
    """Per kind, the longest served request and others drawn from seed."""
    rng = np.random.default_rng(seed)
    out = []
    for kind in KINDS:
        pool = [s for s in served if s[0].kind == kind]
        n = min(int(sizes.get(kind, 0)), len(pool))
        if not n:
            continue
        size = [len(s[0].prompt) + len(s[2] or ()) for s in pool]
        first = int(np.argmax(size))
        rest = [i for i in range(len(pool)) if i != first]
        pick = [first] + list(rng.choice(rest, n - 1, replace=False))
        out += [pool[i] for i in pick]
    return out


def probes(sample, max_seq: int):
    """Reference probes of each sampled request, and who owns each."""
    out, owner = [], []
    for k, (req, res, toks) in enumerate(sample):
        if req.kind == "score":
            ids = tokenizer.encode(req.prompt, max_len=max_seq)
            out.append(Probe(ids, [len(ids) - 1], [tokenizer.YES_ID]))
            owner.append((k, None))
        elif req.kind == "complete":
            ids = tokenizer.encode(req.prompt, max_len=max_seq)
            toks = list(toks)
            out.append(Probe(ids + toks[:-1],
                             [len(ids) - 1 + j for j in range(len(toks))],
                             toks))
            owner.append((k, None))
        else:
            pe = tokenizer.encode(req.prompt + tokenizer.CLASSIFY_SUFFIX,
                                  max_len=max_seq // 2)
            for lb in req.labels:
                ce = tokenizer.encode(lb, bos=False)
                out.append(Probe(pe + ce, [len(pe) - 1 + j
                                           for j in range(len(ce))], ce))
                owner.append((k, lb))
    return out, owner


def _logit(p: float) -> float:
    return math.log(p) - math.log1p(-p)


def _no_gaps(controls):
    return {s: {k: [] for k in GAP_KINDS}
            for s in ("served",) + tuple(controls)}


def gaps(sample, owner, reads, controls=()):
    """The gap of each sampled answer, per kind: the served answer's, and
    each control's put in its place: {stream: {kind: [gap, ...]}} with
    the streams ``served`` and the controls."""
    out = _no_gaps(controls)
    for (k, lb), r in zip(owner, reads):
        req, res, _ = sample[k]
        if req.kind == "score":
            d_ref = r["ref_yes"][0] - r["ref_no"][0]
            out["served"]["score"].append(abs(_logit(res.score) - d_ref))
            for c in controls:
                out[c]["score"].append(
                    abs(r[f"{c}_yes"][0] - r[f"{c}_no"][0] - d_ref))
        elif req.kind == "complete":
            out["served"]["token"].append(
                float(np.max(r["ref_max"] - r["ref_t"])))
            for c in controls:
                out[c]["token"].append(
                    float(np.max(r["ref_max"] - r[f"{c}_pick_ref"])))
        else:
            lp_ref = float(np.mean(r["ref_t"] - r["ref_lse"]))
            lp = sample[k][2].get(lb)
            out["served"]["label"].append(
                math.inf if lp is None else abs(lp - lp_ref))
            for c in controls:
                out[c]["label"].append(abs(float(np.mean(
                    r[f"{c}_t"] - r[f"{c}_lse"])) - lp_ref))
    return out


def numbers(per_kind: Dict[str, list], tolerance: Dict[str, float]
            ) -> Dict[str, float]:
    """Per sampled kind its widest gap (``<gap>``) and, where the limits
    file gives the kind a ``tolerance``, the share of the sample whose gap
    exceeds it (``<gap>_share``)."""
    out = {}
    for kind, name in GAP_NAMES.items():
        g = np.asarray(per_kind[kind], float)
        if not g.size:
            continue
        out[name] = float(g.max())
        if kind in tolerance:
            out[name + "_share"] = float(np.mean(g > tolerance[kind]))
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, dict]:
    """{number: {"value", "limit"}} of every number that has a limit; a
    number that was not read stands at infinity."""
    return {n: {"value": values.get(n, math.inf), "limit": lim}
            for n, lim in limits.items()}


def passes(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def check(cell, seed: int, records, served, *, window_compiles: int = 0,
          controls=()) -> Verdict:
    """The run's verdict.  Each control is judged by the same limits, on
    the numbers it has (it serves no rows): ``control_correct``."""
    limits = dict(cell.check.get("limits", {}), window_compiles=0)
    values: Dict[str, float] = {
        "rows_wrong": float(rows_wrong(records, served)),
        "window_compiles": float(window_compiles)}
    sample = draw(served, cell.check.get("sample", {"score": 8}), seed)
    per = _no_gaps(controls)
    if sample:
        t0 = time.perf_counter()
        probe_list, owner = probes(sample, cell.max_seq)
        reads = cell.arch.run(cell.conf, seed, probe_list,
                              yes=tokenizer.YES_ID, no=tokenizer.NO_ID,
                              controls=controls)
        per = gaps(sample, owner, reads, controls)
        print(f"[chipbench] reference over {len(probe_list)} sequences "
              f"({sum(len(p.tokens) for p in probe_list)} tokens): "
              f"{time.perf_counter() - t0:.3f} s", file=sys.stderr,
              flush=True)
    tolerance = cell.check.get("tolerance", {})
    values.update(numbers(per["served"], tolerance))
    checks = judge(values, limits)
    ctl = {c: numbers(per[c], tolerance) for c in controls}
    ctl_ok = {c: passes(judge(v, {n: lim for n, lim in limits.items()
                                  if n in v}))
              for c, v in ctl.items()}
    return Verdict(passes(checks), checks, ctl, ctl_ok, per)


def print_checks(checks: Dict[str, dict], file=sys.stderr) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=file, flush=True)
