"""Benchmark harness — one module per paper table/claim (DESIGN.md §5).

    PYTHONPATH=src python -m benchmarks.run [--scale] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows.  Every suite runs in a
child interpreter of its own, one after another, and this parent never
imports JAX: a process that has touched JAX holds the chip, and each
suite (and the children some suites start) needs it in turn.
"""
from __future__ import annotations

import argparse
import importlib
import subprocess
import sys

#: suite name -> (module under benchmarks/, keyword arguments of bench())
SUITES = {
    "gen_throughput": ("gen_throughput", {"scale": False}),
    "load_balance": ("load_balance", {}),
    "pipeline_overlap": ("pipeline_overlap", {}),
    "tree_reduce": ("tree_reduce_bench", {}),
    "kernels": ("kernel_bench", {}),
    "padding_and_dropping": ("padding_and_dropping", {}),
    "feature_cache": ("feature_cache", {}),
    "host_fetch": ("host_fetch", {}),
    "serve_latency": ("serve_latency", {}),
    "autotune": ("autotune", {}),
}
#: suites added by --scale
SCALE_SUITES = {
    "gen_throughput_1M": ("gen_throughput", {"scale": True}),
}


def run_suite(name: str) -> None:
    """Child side: run one suite in this process and print its rows."""
    module, kwargs = {**SUITES, **SCALE_SUITES}[name]
    from .common import emit
    emit(importlib.import_module(f"benchmarks.{module}").bench(**kwargs))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", action="store_true",
                    help="include the 1M-nodes-per-iteration configuration")
    ap.add_argument("--only", default=None)
    ap.add_argument("--suite", default=None,
                    choices=sorted({**SUITES, **SCALE_SUITES}),
                    help="run one suite in this process (what the parent "
                         "starts each child with)")
    args = ap.parse_args()
    if args.suite:
        run_suite(args.suite)
        return

    names = list(SUITES) + (list(SCALE_SUITES) if args.scale else [])
    print("name,us_per_call,derived", flush=True)
    failed = False
    for name in names:
        if args.only and args.only != name:
            continue
        # the child inherits this environment unchanged
        proc = subprocess.run([sys.executable, "-m", "benchmarks.run",
                               "--suite", name])
        if proc.returncode != 0:
            failed = True
            print(f"{name},0.0,ERROR", flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
