"""Serving driver: run AISQL queries against real JAX inference engines.

    PYTHONPATH=src python -m repro.launch.serve \
        --archs proxy-8b oracle-70b --replicas 2 \
        --sql "SELECT * FROM reviews AS r WHERE AI_FILTER(...)"

Stands up the Cortex-platform analogue (engines + scheduler + API service),
loads the synthetic datasets into a catalog, and executes queries
end-to-end with AI-aware optimization.  The models are the CPU smoke
presets unless ``--layers N`` asks for the published widths cut to N
layers (``--archs proxy-8b --layers 16`` fits one v5e chip per replica;
replica i runs on device i).
"""
from __future__ import annotations

import argparse

from repro.configs import base as cfgs
from repro.core import AisqlEngine, Catalog, CascadeConfig, ExecConfig
from repro.data import datasets as D
from repro.inference.api import make_engine_client
from repro.launch import compile_cache
from repro.tables.table import Table


DEFAULT_SQL = ("SELECT * FROM reviews AS r WHERE "
               "AI_FILTER(PROMPT('positive review? {0}', r.text)) LIMIT 5")


def build_catalog(rows: int = 64) -> Catalog:
    tables = {
        "reviews": D.cascade_table("IMDB", rows=rows),
        "articles": D.nyt_articles(rows),
    }
    left, right, _ = D.join_tables("AGNEWS_100")
    tables["news"] = left
    tables["topics"] = right
    return Catalog(tables)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="+", default=["proxy-8b", "oracle-70b"])
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--layers", type=int, default=0,
                    help="published widths cut to this many layers "
                         "(0: CPU smoke presets)")
    ap.add_argument("--rows", type=int, default=48)
    ap.add_argument("--cascade", action="store_true")
    ap.add_argument("--sql", default=DEFAULT_SQL)
    ap.add_argument("--explain", action="store_true")
    args = ap.parse_args(argv)

    compile_cache.enable()
    archs = [cfgs.depth_cut(a, args.layers) if args.layers else a
             for a in args.archs]
    client = make_engine_client(archs, replicas=args.replicas)
    engine = AisqlEngine(
        build_catalog(args.rows), client,
        executor=ExecConfig(use_cascade=args.cascade,
                            cascade=CascadeConfig(batch_size=32,
                                                  min_samples=8)))
    if args.explain:
        print(engine.explain(args.sql))
        return 0
    out = engine.sql(args.sql)
    print(out)
    for i in range(min(out.num_rows, 10)):
        print(" ", {k: str(v)[:60] for k, v in out.row(i).items()})
    rep = engine.last_report
    print(f"-- {rep.ai_calls} LLM calls, {rep.ai_credits:.6f} credits, "
          f"{rep.wall_seconds:.2f}s wall")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
