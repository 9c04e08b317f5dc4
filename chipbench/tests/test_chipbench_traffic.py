"""The traffic generator: seeded, fixed in size, honest in rate."""
import json

import numpy as np
import pytest

from chipbench import generator, tokenizer
from chipbench.tests.tiny import REPO


def mix(name):
    return json.loads((REPO / f"chipbench/traffic/{name}.json").read_text())


def sqls(work):
    return [(q.tenant, q.sql, q.due_s) for q in work.queries]


@pytest.mark.parametrize("name,max_seq", [("filter", 2048),
                                          ("dashboard", 1024)])
def test_same_seed_same_queries_other_seed_others(name, max_seq):
    a = generator.generate(mix(name), 2**31 + 11, 30, max_seq)
    b = generator.generate(mix(name), 2**31 + 11, 30, max_seq)
    c = generator.generate(mix(name), 12, 30, max_seq)
    assert sqls(a) == sqls(b) and a.tables == b.tables
    assert a.tables != c.tables
    # the seed draws the bytes; the schedule of queries is the same
    assert sqls(a) == sqls(c)


@pytest.mark.parametrize("name,max_seq", [("filter", 2048),
                                          ("dashboard", 1024)])
def test_every_seed_asks_the_same_work(name, max_seq):
    """Sizes and the queries never depend on the seed."""
    def work(seed):
        w = generator.generate(mix(name), seed, 30, max_seq)
        return sorted((q.key, tuple(len(t) for t in q.texts))
                      for q in w.queries)
    assert work(5) == work(2**31 + 99)


def test_no_filter_row_repeats_across_queries():
    w = generator.generate(mix("filter"), 7, 30, 2048)
    texts = [t for q in w.queries + w.warm for t in q.texts]
    assert len(texts) == len(set(texts))
    assert all(q.rows == 16 for q in w.queries)


def test_prompts_fit_the_context():
    for name, max_seq in (("filter", 2048), ("dashboard", 1024)):
        w = generator.generate(mix(name), 3, 30, max_seq)
        for q in w.queries:
            for t in q.texts:
                assert len(tokenizer.encode(q.prompt(t))) <= max_seq


def test_review_lengths_are_the_stated_lognormal_in_strata():
    """Each slice holds the lognormal's 16 quantiles at (i + 0.5) / 16 of
    median 400 and 95th percentile 1600 bytes."""
    from statistics import NormalDist
    mu, sigma = generator.lognormal_params(400, 1600)
    assert abs(np.exp(mu + 1.6448536 * sigma) - 1600) < 1e-2
    want = sorted(round(float(np.exp(mu + sigma * NormalDist().inv_cdf(
        (i + 0.5) / 16)))) for i in range(16))
    w = generator.generate(mix("filter"), 1, 30, 1 << 20)
    for q in w.queries[:50]:
        assert sorted(len(t) for t in q.texts) == want


def test_ticket_lengths_follow_the_stated_lognormal():
    w = generator.generate(mix("dashboard"), 1, 30, 1 << 20)
    n = np.asarray([len(t) for cols in w.tables.values()
                    for t in cols["text"]])
    assert abs(np.median(n) - 200) < 20
    assert abs(np.percentile(n, 95) - 800) < 100


def test_open_loop_arrivals_have_the_stated_rate():
    m = mix("dashboard")
    seconds = 51
    w = generator.generate(m, 2**31 + 3, seconds, 1024)
    due = np.asarray([q.due_s for q in w.queries])
    assert np.all(np.diff(due) >= 0)
    in_window = due < seconds
    rate = in_window.sum() / seconds
    assert abs(rate - m["rate_qps"]) / m["rate_qps"] < 0.05
    gaps = np.diff(due)
    # exponential gaps: the standard deviation equals the mean
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.15


def test_dashboard_shapes_and_sharing():
    m = mix("dashboard")
    w = generator.generate(m, 9, 51, 1024)
    shapes = [q.shape for q in w.queries]
    share = {s: shapes.count(s) / len(shapes)
             for s in ("filter", "classify", "complete")}
    assert abs(share["filter"] - 0.4) < 0.12
    shared = [q for q in w.queries if q.table == "shared"]
    assert shared and len({q.tenant for q in shared}) > 1
    assert all(len(q.labels) == 4 for q in w.queries
               if q.shape == "classify")
