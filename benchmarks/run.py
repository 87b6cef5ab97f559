"""Benchmark driver — one module per paper table.

Default output is the legacy ``name,us_per_call,derived`` CSV
(``benchmarks/common.emit``).  ``--json DIR`` additionally writes one
schema-versioned ``BENCH_<table>.json`` per table via the regression
tracker (``repro.obs.bench``), each carrying the context a later diff
needs: git revision, backend name, ``PrecisionPolicy``, machine, JAX
version.  ``--quick`` runs the same code paths at CI-sized problems —
the nightly regression job's mode.

    PYTHONPATH=src python benchmarks/run.py                 # CSV, full
    PYTHONPATH=src python benchmarks/run.py --quick --json bench_out
"""
from __future__ import annotations

import argparse
import importlib
import sys
import traceback

from repro import compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized problems (same code paths)")
    ap.add_argument("--json", metavar="DIR", default=None,
                    help="also write BENCH_<table>.json results into DIR")
    ap.add_argument("--tables", nargs="*", default=None, metavar="TABLE",
                    help="subset of table module names")
    args = ap.parse_args(argv)

    from repro.obs.bench import TABLES, run_tables
    names = list(TABLES) if args.tables is None else args.tables
    unknown = [n for n in names if n not in TABLES]
    if unknown:
        ap.error(f"unknown tables {unknown}: expected from {sorted(TABLES)}")

    print("name,us_per_call,derived")
    if args.json is not None:
        # the tracker runs the tables itself (capturing emit rows); the
        # CSV above still streams to stdout through benchmarks.common.emit
        paths = run_tables(args.json, quick=args.quick, tables=names)
        import json
        failed = []
        for p in paths:
            with open(p) as f:
                if json.load(f).get("error"):
                    failed.append(p)
        if failed:
            print(f"benchmark failures recorded in: {failed}",
                  file=sys.stderr)
            sys.exit(1)
        return

    failures = 0
    for name in names:
        mod = importlib.import_module(f"benchmarks.{name}")
        kwargs = TABLES[name] if args.quick else {}
        try:
            mod.run(**kwargs)
        except Exception:
            failures += 1
            print(f"benchmarks.{name},FAILED,", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    compile_cache.enable()
    main()
