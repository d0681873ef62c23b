"""Quickstart: the `repro.api` facade end-to-end on CPU.

    PYTHONPATH=src python examples/quickstart.py

Opens a :class:`repro.api.SenecaServer` over a synthetic image dataset,
pulls a session, feeds a threaded DSI pipeline (storage -> MDP-partitioned
cache -> ODS -> augment) into a reduced ViT training loop, and prints the
server's stats — the smallest real run of the paper's whole stack.  Pass
``--backend jax`` to route batch substitution through the fused
``ods_jax.substitute_jit`` kernel behind the same API.

``--lm`` instead runs the original LM driver (reduced qwen3-8b under the
ResilientTrainer with atomic checkpoints); ``python -m repro.launch.train``
exposes the same paths with all knobs.

``--open-loop RATE`` replaces the closed training loop with trace-timed
request arrivals at RATE req/s (VirtualClock-deterministic, SLO
admission control) and prints exact p50/p99/p999 latency with a
per-phase breakdown — docs/API.md "Open-loop serving & SLOs".
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.api import FaultSpec, JobSpec, SenecaServer, WorkloadRunner
from repro.configs import registry
from repro.configs.base import ParallelismConfig
from repro.data.pipeline import DSIPipeline
from repro.data.storage import RemoteStorage
from repro.data.synthetic import tiny
from repro.launch.train import patchify_stub
from repro.models.model import build
from repro.train.optimizer import AdamW, warmup_cosine
from repro.train.step import build_train_step


def _make_dataset(args):
    """The synthetic dataset, optionally materialized as sharded files
    (--dataset-dir: real file IO through the same token bucket)."""
    ds = tiny(n=1024)
    if args.dataset_dir:
        from repro.data.synthetic import FileDataset
        ds = FileDataset(ds, args.dataset_dir)
        print(f"[quickstart] dataset: {ds.name} "
              f"({ds.n_shards} shard file(s) in {args.dataset_dir})")
    return ds


def _spill_kwargs(args, ds) -> dict:
    """--cache-spill-dir: turn every cache partition into a DRAM→disk
    tier chain (docs/API.md \"Storage engine & cache tiers\")."""
    if not args.cache_spill_dir:
        return {}
    spill = int(0.5 * ds.n_samples * ds.augmented_bytes())
    return {"spill_dir": args.cache_spill_dir, "spill_bytes": spill}


def _device_kwargs(args) -> dict:
    """--device-cache-bytes: add a device-resident HBM cache tier in
    front of DRAM (docs/API.md "Device-resident preprocessing & the
    HBM tier"); pair with ``--executor device`` for the fused
    decode+augment route."""
    if not args.device_cache_bytes:
        return {}
    return {"device_cache_bytes": args.device_cache_bytes}


def _print_tier_labels(server, args) -> None:
    svc = server.service
    parts = [server.partition.label]
    if svc.hbm_partition is not None:
        parts.insert(0, svc.hbm_partition.label)
    if svc.disk_partition is not None:
        parts.append(svc.disk_partition.label)
    if len(parts) > 1:
        levels = ["hbm"] if svc.hbm_partition is not None else []
        levels.append("dram")
        if svc.disk_partition is not None:
            levels.append("disk")
        print(f"[quickstart] {'|'.join(levels)} partition: "
              f"{'|'.join(parts)}")
    if svc.hbm_partition is not None:
        print(f"[quickstart] device cache tier: "
              f"{args.device_cache_bytes} bytes, hbm split "
              f"{svc.hbm_partition.label}")


def _shard_kwargs(args) -> dict:
    """--shards N: route the cache through the sharded data plane
    (docs/API.md \"Sharded data plane\")."""
    if args.shards <= 1 and args.shard_transport == "sim":
        return {}
    return {"shards": args.shards, "shard_transport": args.shard_transport}


def _print_shard_stats(stats) -> None:
    for s in stats.get("shards", ()):
        print(f"[quickstart]   shard {s['shard']}: "
              f"hit_rate={s['hit_rate']:.3f} entries={s['entries']} "
              f"bytes={s['bytes_used']}")


def run_seneca(args) -> None:
    # -- the docs/API.md quickstart, verbatim ---------------------------
    ds = _make_dataset(args)
    server = SenecaServer.for_dataset(ds, cache_frac=0.35, seed=0,
                                      backend=args.backend,
                                      augment_backend=args.augment_backend,
                                      repartition=args.repartition,
                                      **_spill_kwargs(args, ds),
                                      **_device_kwargs(args),
                                      **_shard_kwargs(args))
    print(f"[quickstart] MDP partition: {server.partition.label} "
          f"(backend={args.backend}, executor={args.executor}, "
          f"augment={args.augment_backend}, "
          f"repartition={args.repartition}, shards={args.shards})")
    if server.service.disk_partition is not None:
        print(f"[quickstart] spill tier: disk split "
              f"{server.service.disk_partition.label} in "
              f"{args.cache_spill_dir}")
    _print_tier_labels(server, args)

    cfg = registry.get_reduced("vit-huge")
    model = build(cfg)
    print(f"[quickstart] {cfg.name} (reduced): {model.n_params():,} params")
    params = model.init(jax.random.key(0))
    opt = AdamW(lr=1e-3, schedule=warmup_cosine(1e-3, 10, args.steps))
    state = opt.init(params)
    step = jax.jit(build_train_step(model, ParallelismConfig(), opt))

    losses = []
    t0 = time.monotonic()
    with server.open_session(batch_size=args.batch) as sess:
        pipe = DSIPipeline(sess, RemoteStorage(ds), n_workers=3,
                           executor=args.executor)
        for _ in range(args.steps):
            raw = pipe.next_batch()
            batch = {"patch_embeds": patchify_stub(
                         raw["images"], cfg.frontend_tokens, cfg.d_model),
                     "labels": jax.numpy.asarray(
                         raw["labels"] % cfg.n_classes)}
            params, state, m = step(params, state, batch)
            losses.append(float(m["loss"]))
        stats = sess.stats()
        pipe.stop()
    dt = time.monotonic() - t0

    print(f"[quickstart] {len(losses)} steps in {dt:.1f}s "
          f"({len(losses) * args.batch / dt:.1f} samples/s)")
    print(f"[quickstart] loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    print(f"[quickstart] ods_hit_rate={stats['ods_hit_rate']:.3f} "
          f"substitutions={stats['substitutions']} "
          f"tier_counts={stats['tier_counts']}")
    if "residency_counts" in stats:
        extra = []
        if "disk_bytes_used" in stats:
            extra.append(f"disk_bytes_used={stats['disk_bytes_used']}")
        if "hbm_bytes_used" in stats:
            extra.append(f"hbm_bytes_used={stats['hbm_bytes_used']}")
        print(f"[quickstart] residency={stats['residency_counts']} "
              + " ".join(extra))
    _print_shard_stats(stats)
    rp = stats["repartitions"]
    if rp["applied"]:
        last = rp["last_applied"]
        print(f"[quickstart] repartitioned {rp['applied']}x "
              f"({last['from']} -> {last['to']}, "
              f"predicted gain {last['predicted_gain']:+.1%}); "
              f"live partition: {rp['partition']}")
    else:
        print(f"[quickstart] live partition: {rp['partition']} "
              f"(mode={rp['mode']}, no repartition applied)")
    server.close()      # drops spill-tier files when --cache-spill-dir
    assert np.isfinite(losses).all()
    assert stats["hits"] + stats["misses"] > 0
    print("[quickstart] OK — trained through the repro.api facade")


def _fault_trace(args) -> list:
    """--inject-faults: a small mixed-domain fault trace scaled to the
    run's configuration (docs/API.md "Fault tolerance & elasticity") —
    the preempted job is restored from its sampler checkpoint, so the
    epoch accounting below still holds exactly."""
    faults = [
        FaultSpec("worker-crash", at_s=0.5, job="job0"),
        FaultSpec("preempt", at_s=1.0, job="job0", duration_s=0.5),
        FaultSpec("bandwidth-collapse", at_s=0.8, factor=0.5,
                  duration_s=0.6),
    ]
    if args.shards > 1:
        faults.append(FaultSpec("shard-kill", at_s=0.7,
                                shard=args.shards - 1, duration_s=0.5))
    if args.cache_spill_dir:
        faults.append(FaultSpec("spill-corrupt", at_s=0.9, n_files=2))
    return faults


def run_multi(args) -> None:
    """``--jobs N``: N concurrent sessions sharing one Seneca cache,
    driven by the multi-job WorkloadRunner (docs/API.md "Multi-job
    workloads") — each job is a DSIPipeline with a rate-limited consumer
    emulating its GPU's ingest rate."""
    ds = _make_dataset(args)
    server = SenecaServer.for_dataset(ds, cache_frac=0.35, seed=0,
                                      backend=args.backend,
                                      augment_backend=args.augment_backend,
                                      repartition=args.repartition,
                                      **_spill_kwargs(args, ds),
                                      **_device_kwargs(args),
                                      **_shard_kwargs(args))
    print(f"[quickstart] MDP partition: {server.partition.label} "
          f"({args.jobs} concurrent jobs, one shared cache, "
          f"{args.shards} shard(s))")
    _print_tier_labels(server, args)
    rates = [900, 500, 700, 1100, 600, 800][:args.jobs] or [900]
    trace = [JobSpec(f"job{i}", arrival_s=0.4 * i, epochs=1,
                     batch_size=args.batch, gpu_rate=rates[i % len(rates)],
                     executor=args.executor, n_workers=2)
             for i in range(args.jobs)]
    storage = RemoteStorage(ds, bandwidth=60e6)
    faults = _fault_trace(args) if args.inject_faults else []
    if faults:
        print(f"[quickstart] injecting {len(faults)} fault(s): "
              + ", ".join(f.kind for f in faults))
    runner = WorkloadRunner(server, storage, record_ids=False,
                            faults=faults, fault_policy="checkpoint")
    res = runner.run(trace, timeout=600)
    for job in res.jobs:
        extra = ""
        if job.preemptions or job.worker_restarts:
            extra = (f", {job.preemptions} preemption(s), "
                     f"{job.worker_restarts} worker restart(s)")
        print(f"[quickstart]   {job.spec.name}: arrived "
              f"{job.spec.arrival_s:.1f}s, {job.samples} samples in "
              f"{job.duration_s:.1f}s ({job.epochs_completed} epoch(s)"
              f"{extra})")
    stats = res.stats
    print(f"[quickstart] makespan {res.makespan:.1f}s  "
          f"ods_hit_rate={stats['ods_hit_rate']:.3f} "
          f"substitutions={stats['substitutions']}")
    _print_shard_stats(stats)
    fstats = (stats or {}).get("faults")
    if fstats:
        print(f"[quickstart] faults injected={fstats['injected']} "
              f"recovered={fstats['recovered']} "
              f"shard_failovers={fstats['shard_failovers']}")
    server.close()
    # each job consumes one whole-batch epoch pass (the runner's epoch
    # accounting — exact even when --batch does not divide the dataset;
    # with --inject-faults the checkpoint/restore policy keeps it exact
    # through the preemption too)
    epoch_size = (ds.n_samples // args.batch) * args.batch
    assert res.ok and res.total_samples == args.jobs * epoch_size
    assert all(j.epochs_completed == 1 for j in res.jobs)
    if args.inject_faults:
        assert sum(j.preemptions for j in res.jobs) == 1
    print(f"[quickstart] OK — {args.jobs} jobs shared one Seneca cache")


def run_open_loop(args) -> None:
    """``--open-loop RATE``: drive the server with trace-timed request
    arrivals instead of a closed training loop (docs/API.md "Open-loop
    serving & SLOs") — a VirtualClock replays the schedule
    deterministically, the SLO admission controller degrades/sheds under
    overload, and the exact latency percentiles are printed per phase."""
    from repro.api import SLO
    from repro.workload import (OpenLoopGenerator, VirtualClock,
                                poisson_arrivals)

    ds = _make_dataset(args)
    server = SenecaServer.for_dataset(ds, cache_frac=0.35, seed=0,
                                      backend=args.backend,
                                      **_spill_kwargs(args, ds))
    clock = VirtualClock()
    storage = RemoteStorage(ds, bandwidth=8e6, clock=clock)
    slo = SLO(p99_target_s=args.slo_p99, max_queue=64)
    gen = OpenLoopGenerator(server, storage, clock=clock, slo=slo,
                            n_workers=2, seed=0,
                            phase_costs={"decode": 0.004,
                                         "augment": 0.003})
    n = args.steps * args.batch
    res = gen.run(poisson_arrivals(args.open_loop, n=n, seed=0))
    print(f"[quickstart] open-loop @ {args.open_loop:.0f} req/s, "
          f"{n} requests, SLO p99 target {args.slo_p99 * 1e3:.0f}ms: "
          f"{res.counts}")
    lat = res.percentiles()
    if lat:
        print(f"[quickstart] latency p50={lat['p50'] * 1e3:.2f}ms "
              f"p99={lat['p99'] * 1e3:.2f}ms "
              f"p999={lat['p999'] * 1e3:.2f}ms "
              f"(virtual makespan {res.makespan_s:.2f}s)")
        for phase, pcts in sorted(res.phase_percentiles().items()):
            print(f"[quickstart]   {phase:>8}: "
                  f"p50={pcts['p50'] * 1e3:.2f}ms "
                  f"p99={pcts['p99'] * 1e3:.2f}ms")
    stats = server.stats()
    req = stats["telemetry"]["requests"]
    print(f"[quickstart] stats()['telemetry']['requests']: "
          f"outcomes={req['outcomes']} "
          f"completed={req['completed']}")
    server.close()
    assert res.counts["served"] > 0
    print("[quickstart] OK — open-loop serving through the repro.api "
          "facade")


def run_lm(args) -> None:
    from repro.distributed.ft import FTConfig, ResilientTrainer
    from repro.launch.train import lm_batch_source

    cfg = registry.get_reduced(args.arch)
    model = build(cfg)
    print(f"[quickstart] {cfg.name} (reduced): {model.n_params():,} params")

    params = model.init(jax.random.key(0))
    opt = AdamW(lr=1e-3, schedule=warmup_cosine(1e-3, 20, args.steps))
    trainer = ResilientTrainer(
        step_fn=jax.jit(build_train_step(model, ParallelismConfig(), opt)),
        params=params, opt_state=opt.init(params),
        cfg=FTConfig(ckpt_dir="/tmp/quickstart_ckpt", ckpt_every=50),
        batch_source=lm_batch_source(model, args.batch, args.seq))

    t0 = time.monotonic()
    hist = trainer.run(args.steps)
    dt = time.monotonic() - t0
    print(f"[quickstart] {len(hist)} steps in {dt:.1f}s "
          f"({len(hist) * args.batch * args.seq / dt:,.0f} tok/s)")
    print(f"  loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
    assert hist[-1]["loss"] < hist[0]["loss"]
    print("[quickstart] OK — loss decreased; checkpoints in "
          "/tmp/quickstart_ckpt")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lm", action="store_true",
                    help="run the LM ResilientTrainer driver instead")
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--backend", default="numpy",
                    choices=("numpy", "jax"))
    ap.add_argument("--executor", default="per-sample",
                    choices=("per-sample", "stage-parallel", "device"),
                    help="DSI pipeline executor (stage-parallel = async "
                         "queue-fed stages; device = fused Pallas "
                         "decode+augment with device collate, "
                         "docs/API.md)")
    ap.add_argument("--augment-backend", default="numpy",
                    choices=("numpy", "pallas"),
                    help="batched augment engine for the stage-parallel "
                         "executor (pallas = fused kernel)")
    ap.add_argument("--repartition", default="static",
                    choices=("static", "on-change", "adaptive"),
                    help="live cache repartitioning mode (docs/API.md)")
    ap.add_argument("--jobs", type=int, default=1,
                    help="run N concurrent sessions over one shared "
                         "cache via the WorkloadRunner (docs/API.md "
                         "\"Multi-job workloads\") instead of the "
                         "single-job training loop")
    ap.add_argument("--inject-faults", action="store_true",
                    help="with --jobs N: inject a worker crash, a job "
                         "preemption, a storage-bandwidth collapse — "
                         "plus a shard kill with --shards > 1 and a "
                         "spill corruption with --cache-spill-dir — and "
                         "recover through the checkpoint/restore policy "
                         "(docs/API.md \"Fault tolerance & "
                         "elasticity\")")
    ap.add_argument("--shards", type=int, default=1,
                    help="split the cache across N consistent-hash "
                         "shards (docs/API.md \"Sharded data plane\"); "
                         "prints per-shard hit rates at the end")
    ap.add_argument("--shard-transport", default="sim",
                    choices=("sim", "process"),
                    help="sharded data-plane transport: in-process "
                         "deterministic shards, or one OS process per "
                         "shard")
    ap.add_argument("--device-cache-bytes", type=int, default=0,
                    help="device-resident HBM cache tier budget in "
                         "bytes (0 = off): augmented rows are served "
                         "zero-copy on device and the form×tier MDP "
                         "solves a third simplex (docs/API.md "
                         "\"Device-resident preprocessing & the HBM "
                         "tier\")")
    ap.add_argument("--cache-spill-dir", default=None,
                    help="SSD spill directory: every cache partition "
                         "becomes a DRAM→disk tier chain sized by the "
                         "form×tier MDP (docs/API.md \"Storage engine "
                         "& cache tiers\")")
    ap.add_argument("--open-loop", type=float, default=None,
                    metavar="RATE",
                    help="drive the server open-loop at RATE req/s "
                         "(Poisson arrivals on a VirtualClock, SLO "
                         "admission control) and print latency "
                         "percentiles instead of training (docs/API.md "
                         "\"Open-loop serving & SLOs\")")
    ap.add_argument("--slo-p99", type=float, default=0.05,
                    help="open-loop p99 latency target in seconds")
    ap.add_argument("--dataset-dir", default=None,
                    help="materialize the synthetic dataset as "
                         "write-once sharded files here and serve "
                         "fetches from them (real file IO through the "
                         "storage token bucket)")
    ap.add_argument("--steps", type=int, default=None,
                    help="training steps (default: 30, or 200 with --lm)")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()
    if args.inject_faults and (args.lm or args.jobs < 2):
        ap.error("--inject-faults needs the multi-job runner: "
                 "pass --jobs N (N >= 2) without --lm")
    if args.steps is None:
        args.steps = 200 if args.lm else 30
    if args.open_loop is not None and (args.lm or args.jobs > 1):
        ap.error("--open-loop replaces the training loop: drop --lm / "
                 "--jobs")
    if args.lm:
        run_lm(args)
    elif args.open_loop is not None:
        run_open_loop(args)
    elif args.jobs > 1:
        run_multi(args)
    else:
        run_seneca(args)


if __name__ == "__main__":
    main()
