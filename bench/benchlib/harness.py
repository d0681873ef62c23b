"""One run of one cell: set-up, the timed window, the trace, the check.

Set-up builds the system from the seed, fills the HBM tier where the
traffic asks for it, compiles the step on the first batch and takes the
first three steps through the window's own call and feed; the check
later replays those three steps in the reference.  The window then runs
steps until ``seconds`` have passed and ends at the completion of the
step that crossed that mark, so it holds whole steps only.  After the
window the program's state is freed and the reference runs.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from benchlib import check, system, xtrace, yardstick
from benchlib.catalog import Cell

SETUP_STEPS = 3
KEPT_BATCHES = 8


class NoChip(RuntimeError):
    pass


class ProgramCount:
    """Programs JAX compiles or loads from its persistent cache while
    this object listens, so a run can show that its window compiled
    nothing."""

    def __init__(self):
        import jax
        self.compiled_or_loaded = 0
        self.loaded = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled_or_loaded += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def snapshot(self):
        return self.compiled_or_loaded, self.loaded


@dataclass
class RunRecord:
    """What one run measured; the metric readers take their numbers here."""
    cell: Cell
    batch: int
    flops_per_sample: float
    peaks: Dict[str, float]
    setup_s: float
    window_s: float = 0.0
    step_intervals: List[float] = field(default_factory=list)
    spans: List = field(default_factory=list)
    times_before: Dict = field(default_factory=dict)
    times_after: Dict = field(default_factory=dict)
    stats_before: Dict = field(default_factory=dict)
    stats_after: Dict = field(default_factory=dict)
    trace: Optional[xtrace.TraceSummary] = None

    @property
    def steps(self) -> int:
        return len(self.step_intervals)

    @property
    def samples(self) -> int:
        return self.steps * self.batch


def enable_compile_cache() -> None:
    """The program's persistent compile cache (``.jax_cache/`` in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` names another), keeping
    every program, kernels and small ones too, so that only a checkout's
    first run of a cell compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable
    cache_dir = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"compile cache: {cache_dir}", file=sys.stderr)


def device_record(chips: int) -> Dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    yardstick.peaks(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def sizes_of(config: Dict) -> Dict:
    return dict(config["sizes"])


def _by_name(tree) -> Dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path): x
            for path, x in flat}


def _check_sizes(model, sizes: Dict) -> None:
    """The program's model has the configuration's sizes; a dotted key
    (``moe.n_experts``) names a field of a nested configuration."""
    cfg = model.cfg
    got = {k: functools.reduce(getattr, k.split("."), cfg) for k in sizes}
    if got != sizes:
        raise ValueError(f"the program's model {got} is not the "
                         f"configuration's {sizes}")


def _first_grads(opt_state, b1: float) -> Dict[str, float]:
    """Each leaf's norm of the first gradient as the optimizer took it
    (after clipping): AdamW's first moment after one step is
    ``(1 - b1) * g``."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda m: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        m))(opt_state.m)
    return {k: float(v) / (1.0 - b1) for k, v in _by_name(norms).items()}


def _changes(params, initial: Dict) -> Dict[str, float]:
    """Each leaf's norm of its change from ``initial``, the host copy of
    the parameters taken before the first step; one leaf is uploaded at
    a time."""
    import jax
    import jax.numpy as jnp
    diff = jax.jit(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))))
    return {name: float(diff(leaf, initial[name]))
            for name, leaf in _by_name(params).items()}


def _host_batch(raw: Dict, epoch: int, rows=None) -> Dict:
    images = np.asarray(raw["images"])
    sel = slice(None) if rows is None else rows
    return {"images": images[sel], "ids": np.asarray(raw["ids"])[sel],
            "labels": np.asarray(raw["labels"])[sel], "epoch": int(epoch)}


def setup(cell: Cell, seed: int, step_builder: Optional[Callable] = None):
    """Build, fill, compile, and take the first steps; returns the
    system, the program's readings of those steps and their batches."""
    import jax
    s = system.seeds(seed)
    sys_ = system.build(cell.config, cell.traffic, seed, step_builder)
    _check_sizes(sys_.model, sizes_of(cell.config))
    sys_.params, sys_.opt_state = jax.block_until_ready(
        sys_.init_state(jax.random.key(s["params"])))
    initial = _by_name(jax.device_get(sys_.params))
    if cell.traffic["fill_before_window"]:
        filled = system.fill_tier(sys_)
        print(f"HBM tier filled: {filled['resident']} samples resident",
              file=sys.stderr)
    batches: List[Dict] = []
    sys_.keep = lambda raw, epoch: batches.append(_host_batch(raw, epoch))
    first = sys_.next_batch()
    t0 = time.perf_counter()
    system.compile_step(sys_, first)
    print(f"step compiled (or loaded) in {time.perf_counter() - t0:.3f} s",
          file=sys.stderr)
    rec = system.run_step(sys_, first)
    prog = {"losses": [rec["loss"]],
            "grad": _first_grads(sys_.opt_state,
                                 cell.config["optimizer"]["b1"])}
    for _ in range(SETUP_STEPS - 1):
        prog["losses"].append(system.run_step(sys_, sys_.next_batch())["loss"])
    prog["change"] = _changes(sys_.params, initial)
    sys_.keep = None
    return sys_, prog, batches


def window(sys_, seconds: float, traced: bool, rng: np.random.Generator,
           record: RunRecord, programs: ProgramCount) -> Dict:
    """Steps until ``seconds`` have passed; keeps a seeded uniform
    sample of the window's batches for the check."""
    import jax
    kept: List = []
    seen = [0]

    def keep(raw, epoch):
        i = seen[0]
        seen[0] += 1
        if len(kept) < KEPT_BATCHES:
            kept.append((raw, epoch))
        else:
            j = int(rng.integers(0, i + 1))
            if j < KEPT_BATCHES:
                kept[j] = (raw, epoch)

    def span(name):
        if traced:
            return jax.profiler.TraceAnnotation("bench." + name)
        return contextlib.nullcontext()

    losses, spans, done = [], [], []
    before = programs.snapshot()
    record.times_before = sys_.pipe.times.as_dict()
    record.stats_before = sys_.server.stats()
    sys_.keep = keep
    t0 = time.perf_counter()
    while True:
        a = time.perf_counter()
        with span("next_batch"):
            batch = sys_.next_batch()
        b = time.perf_counter()
        with span("step"):
            rec = system.run_step(sys_, batch)
        c = time.perf_counter()
        spans += [("next_batch", a, b), ("step", b, c)]
        done.append(c)
        losses.append(rec["loss"])
        del batch
        if c - t0 >= seconds:
            break
    sys_.keep = None
    both, loaded = (a - b for a, b in zip(programs.snapshot(), before))
    print(f"programs in the window: {both} compiled or loaded, {loaded} "
          f"of them loaded", file=sys.stderr)
    record.times_after = sys_.pipe.times.as_dict()
    record.stats_after = sys_.server.stats()
    record.window_s = done[-1] - t0
    record.step_intervals = list(np.diff([t0] + done))
    record.spans = spans
    return {"losses": losses, "kept": kept}


def check_rows(entries: List[Dict], traffic: Dict, data_seed: int):
    """(row_gap, label_errors, reference rows per entry)."""
    from reference import synthetic_images as ref
    d = traffic["dataset"]
    worst, label_errors, refs = 0.0, 0, []
    for e in entries:
        rows = []
        for row, sid, lab in zip(e["images"], e["ids"].tolist(),
                                 e["labels"].tolist()):
            gap, r = ref.closest_row(
                row, data_seed, int(d["mean_encoded_bytes"]),
                tuple(d["image_hw"]), tuple(d["crop_hw"]), sid,
                range(e["epoch"] + 1))
            if not gap <= worst:
                worst = gap
            label_errors += int(lab != ref.label(sid, int(d["n_classes"])))
            rows.append(r)
        refs.append(np.stack(rows))
    return worst, label_errors, refs


def _served_counts(before: Dict, after: Dict) -> Dict[str, int]:
    b = before["telemetry"]["serve_counts"]
    a = after["telemetry"]["serve_counts"]
    return {k: int(a[k] - b.get(k, 0)) for k in a}


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_start: float, device: Optional[Dict] = None,
        step_builder: Optional[Callable] = None) -> Dict:
    """One whole run; returns the result line's object."""
    import jax

    programs = ProgramCount()
    if device is None:
        device = device_record(cell.chips)
    s = system.seeds(seed)
    record = RunRecord(cell, int(cell.config["batch"]),
                       cell.reference.train_flops_per_sample(
                           sizes_of(cell.config)),
                       yardstick.PEAKS.get(device["kind"], {}), 0.0)

    sys_, prog, setup_batches = setup(cell, seed, step_builder)
    both, loaded = programs.snapshot()
    print(f"programs in set-up: {both - loaded} compiled, {loaded} loaded "
          f"from the cache", file=sys.stderr)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    record.setup_s = time.perf_counter() - t_start
    rng = np.random.default_rng(s["sample"])
    win = window(sys_, seconds, traced, rng, record, programs)
    if traced:
        jax.profiler.stop_trace()
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    served = _served_counts(record.stats_before, record.stats_after)
    q = np.percentile(record.step_intervals, [0, 50, 90, 100]) * 1e3
    print(f"window: {record.steps} steps, {record.samples} samples in "
          f"{record.window_s:.6f} s; step ms min {q[0]:.1f} median "
          f"{q[1]:.1f} p90 {q[2]:.1f} max {q[3]:.1f}; served forms {served}",
          file=sys.stderr)

    B = record.batch
    rows_per = int(cell.traffic["check_rows_per_batch"])
    entries = list(setup_batches)
    for raw, epoch in win["kept"]:
        pick = np.sort(rng.choice(B, size=min(rows_per, B), replace=False))
        entries.append(_host_batch(raw, epoch, pick))
    del win["kept"]
    sys_.close()
    sys_.params = sys_.opt_state = sys_.step = None
    del sys_
    gc.collect()

    t_ref = time.perf_counter()
    row_gap, label_errors, ref_rows = check_rows(
        entries, cell.traffic, s["data"])
    ref_steps = cell.reference.check_steps(
        cell.config, cell.traffic, s, ref_rows[:SETUP_STEPS],
        [e["ids"] for e in setup_batches])
    numbers = check.step_numbers(prog, ref_steps)
    for k in ref_steps["change"]:
        print(f"leaf {k}: first gradient {prog['grad'][k]!r} reference "
              f"{ref_steps['grad'][k]!r}; change {prog['change'][k]!r} "
              f"reference {ref_steps['change'][k]!r}", file=sys.stderr)
    numbers.update(row_gap=row_gap, label_errors=float(label_errors),
                   window_nonfinite=float(sum(
                       not math.isfinite(x) for x in win["losses"])))
    print(f"reference: {time.perf_counter() - t_ref:.3f} s; losses "
          f"program {prog['losses']} reference {ref_steps['losses']}",
          file=sys.stderr)
    limits = dict(cell.config["limits"]) | dict(cell.traffic["limits"])
    correct, table = check.verdict(numbers, limits)

    metrics: Dict[str, Dict] = {}
    dev = dict(device, memory_peak_bytes=peak)
    out = {"correct": correct, "attempted": record.steps,
           "failed": int(numbers["window_nonfinite"])}
    if traced:
        record.trace = xtrace.reduce(xtrace.read_planes(
            xtrace.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev.update(busy_s=record.trace.busy_s,
                   window_s=record.trace.window_s)
        out["breakdown"] = xtrace.breakdown(record.trace)
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = m.read(record)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}
    out.update(metrics=metrics, device=dev, checks=table)
    for name in sorted(set(numbers) - set(table)):
        print(f"reading {name} {numbers[name]!r} (not compared)",
              file=sys.stderr)
    for name, v in table.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    return out


def _finite(x):
    """The result as strict JSON: a number that is not finite is null."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def emit(result: Dict) -> None:
    sys.stderr.flush()
    print(json.dumps(_finite(result), allow_nan=False), flush=True)
