"""Readings that the correctness limits are set from.

    python3 bench/control.py --workload vith-cold --seeds 1 2 3 ...

For each seed, in one process: the program's set-up steps exactly as a
benchmark run takes them (the sound readings), then, in the program's
place, the reference computed in float8 (the control) and the reference
with half of each batch left out and the mean taken over the rest (a
fault).  Each is compared with the float32 reference by the numbers of
``benchlib.check``.  The served rows are read against the reference
too, and so is the reference's own row rounded to bfloat16 (the
control of the data layer).  A step that returns its state unchanged
reads 1 on ``change_leaf_gap`` by construction and needs no run.

Prints one JSON line per seed and writes them all to ``--out``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def bf16_row_gap(ref_rows) -> float:
    import jax.numpy as jnp
    import numpy as np
    worst = 0.0
    for rows in ref_rows:
        low = np.asarray(jnp.asarray(rows, jnp.float32).astype(jnp.bfloat16)
                         .astype(jnp.float32), np.float64)
        worst = max(worst, float(np.max(np.abs(low - rows))))
    return worst


def readings(cell, seed: int) -> dict:
    """Every reading of one seed."""
    from benchlib import check, harness, system
    s = system.seeds(seed)
    sys_, prog, batches = harness.setup(cell, seed)
    sys_.close()
    sys_.params = sys_.opt_state = sys_.step = None
    del sys_
    gc.collect()
    row_gap, label_errors, ref_rows = harness.check_rows(
        batches, cell.traffic, s["data"])
    ids = [b["ids"] for b in batches]
    out = {"seed": seed, "row_gap": row_gap, "label_errors": label_errors,
           "row_gap_bf16": bf16_row_gap(ref_rows)}
    runs = {"f32": {}, "fp8": {"precision": "fp8"},
            "half": {"use_rows": int(cell.config["batch"]) // 2}}
    ref = {}
    for name, kw in runs.items():
        t0 = time.perf_counter()
        ref[name] = cell.reference.check_steps(cell.config, cell.traffic, s,
                                               ref_rows, ids, **kw)
        out[f"seconds_{name}"] = time.perf_counter() - t0
    out["program"] = check.step_numbers(prog, ref["f32"])
    out["control_fp8"] = check.step_numbers(ref["fp8"], ref["f32"])
    out["fault_half_batch"] = check.step_numbers(ref["half"], ref["f32"])
    out["losses"] = {"program": prog["losses"],
                     **{k: v["losses"] for k, v in ref.items()}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchlib import harness
    from benchlib.catalog import Catalog
    cell = Catalog().cell(args.workload)
    harness.enable_compile_cache()
    try:
        harness.device_record(cell.chips)
    except (harness.NoChip, KeyError) as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    rows = []
    for seed in args.seeds:
        r = readings(cell, seed)
        rows.append(r)
        print(json.dumps(r), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
