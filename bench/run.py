"""Benchmark of Seneca's device route feeding real training steps.

    python3 bench/run.py --workload vitb-cold --seed 7 --seconds 30 --trace 0

Runs one cell of ``BENCHMARK.json`` on the chip this process finds:
builds the configuration's model and the traffic's pipeline from
``--seed``, takes the set-up steps, measures a window of ``--seconds``
and checks what the window produced against the plain reference under
``bench/reference``.  ``--trace 1`` records a profiler trace of the
window and reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` with a
trace, and ``checks``: each compared number with its limit).  With no
TPU, or fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchlib.catalog import Catalog
    cell = Catalog().cell(args.workload)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program under test is not in this checkout "
              f"({e})", file=sys.stderr)
        return 2
    from benchlib import harness
    harness.enable_compile_cache()
    try:
        device = harness.device_record(cell.chips)
    except (harness.NoChip, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, device)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
