"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. us_per_call is measured wall time
of the real implementation on this host; derived fields include the
RDMA/ICI-model projections (see benchmarks/common.py).
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (fig12_phases, fig13_memory, fig14_throughput,
                        fig15_prefetch, fig16_cow, fig18_ablation,
                        fig19_state_transfer, fig20_spikes, roofline_table,
                        table1_startup)
from benchmarks.common import fmt_csv
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    ("table1", table1_startup),
    ("fig12", fig12_phases),
    ("fig13", fig13_memory),
    ("fig14", fig14_throughput),
    ("fig15", fig15_prefetch),
    ("fig16_17", fig16_cow),
    ("fig18", fig18_ablation),
    ("fig19", fig19_state_transfer),
    ("fig20", fig20_spikes),
    ("roofline", roofline_table),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module names")
    args = ap.parse_args()
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failed = []
    for name, mod in MODULES:
        if only and name not in only:
            continue
        try:
            rows = mod.run()
            print(fmt_csv(rows), flush=True)
        except Exception:
            failed.append(name)
            traceback.print_exc(file=sys.stderr)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
