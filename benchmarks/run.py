"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--full`` uses paper-scale
populations (slower); default is the 1/10 weak-scaled configuration whose
ratios match (benchmarks/common.py).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma list of module tags (fig3,fig4,...)")
    args, _ = ap.parse_known_args()

    from benchmarks import (fig3_cache_forms, fig4_pagecache,
                            fig8_validation, fig10_makespan, fig13_hitrate,
                            fig14_concurrency, fig15_ect, fig_concurrency,
                            fig_device_pipeline, fig_dynamic_jobs,
                            fig_fault_recovery, fig_live_makespan,
                            fig_open_loop, fig_sharded, fig_tiered_cache,
                            roofline_report, table6_mdp)
    modules = [
        ("fig3", fig3_cache_forms), ("fig4", fig4_pagecache),
        ("table6", table6_mdp), ("fig8", fig8_validation),
        ("fig10", fig10_makespan), ("fig13", fig13_hitrate),
        ("fig14", fig14_concurrency), ("fig15", fig15_ect),
        ("dynamic", fig_dynamic_jobs),
        ("device", fig_device_pipeline),
        ("live", fig_live_makespan),
        ("tiered", fig_tiered_cache),
        ("sharded", fig_sharded),
        ("faults", fig_fault_recovery),
        ("concurrency", fig_concurrency),
        ("openloop", fig_open_loop),
        ("roofline", roofline_report),
    ]
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    for tag, mod in modules:
        if only and tag not in only:
            continue
        t0 = time.monotonic()
        try:
            rows = mod.run(full=args.full)
        except Exception as e:          # keep the harness running
            print(f"{tag}/ERROR,0,{e!r}")
            continue
        us = (time.monotonic() - t0) * 1e6 / max(len(rows), 1)
        for name, derived in rows:
            print(f'{name},{us:.0f},"{derived}"')
        sys.stdout.flush()


if __name__ == "__main__":
    main()
