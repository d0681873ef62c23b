"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set the fake-device flag before ANY other import (jax locks the device
count on first init).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse                                              # noqa: E402
import json                                                  # noqa: E402
import time                                                  # noqa: E402
import traceback                                             # noqa: E402
from typing import Any, Dict, Optional, Tuple                # noqa: E402

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402

from repro.configs import registry                           # noqa: E402
from repro.configs.base import (SHAPES_BY_NAME, ALL_SHAPES,  # noqa: E402
                                ParallelismConfig, ShapeConfig,
                                shape_applicable)
from repro.distributed.sharding import make_rules, use_rules  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402
from repro.models.model import Model, build                  # noqa: E402
from repro.models.params import (abstract_params,            # noqa: E402
                                 param_bytes, partition_specs)
from repro.roofline import analysis as roofline              # noqa: E402
from repro.roofline import hlo_collectives                   # noqa: E402
from repro.train.optimizer import AdamW, Quantized           # noqa: E402
from repro.train.step import build_train_step                # noqa: E402

SDS = jax.ShapeDtypeStruct


def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _opt_specs(params_specs, m_abs, fsdp: bool, dp: int):
    def f(st, spec):
        if isinstance(st, Quantized):
            parts = list(spec) + [None] * (st.q.ndim - 1 - len(spec))
            if st.q.ndim == len(parts) + 1:
                # structured blocks (..., D/Q, Q): inherit the param spec;
                # a sharded trailing param axis moves to the blocks axis
                # when the block count still divides the mesh axis
                last = parts[-1] if parts else None
                keep_last = last if (last is not None and
                                     st.q.shape[-2] % 16 == 0) else None
                qspec = P(*parts[:-1], keep_last, None)
                sspec = qspec
            else:                      # flat fallback (small params)
                nb = st.q.shape[0]
                qspec = P("data", None) if (fsdp and nb % dp == 0) else P()
                sspec = qspec
            return Quantized(qspec, sspec)
        return spec

    return jax.tree.map(f, m_abs, params_specs,
                        is_leaf=lambda x: isinstance(x, Quantized))


def _shard_factor(spec: P, mesh) -> int:
    f = 1
    for ax in spec:
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        for a in axes:
            f *= mesh.shape[a]
    return f


def _bytes_per_device(abs_tree, spec_tree, mesh) -> float:
    total = 0.0
    leaves_a = jax.tree.leaves(abs_tree)
    leaves_s = jax.tree.leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    for a, s in zip(leaves_a, leaves_s):
        nb = np.prod(a.shape) * jnp.dtype(a.dtype).itemsize
        total += nb / _shard_factor(s, mesh)
    return float(total)


def lower_cell(arch: str, shape: ShapeConfig, *, multi_pod: bool,
               parallel: Optional[ParallelismConfig] = None) -> Dict:
    """Lower+compile one cell; returns the record dict (or raises)."""
    cfg = registry.get(arch)
    model = build(cfg)
    parallel = parallel or registry.default_parallelism(cfg, shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    rules = make_rules(cfg, shape, parallel, multi_pod=multi_pod,
                       tp_size=mesh.shape["model"],
                       dp_size=mesh.shape["data"], mesh=mesh)

    defs = model.param_defs()
    p_abs = abstract_params(defs, jnp.dtype(parallel.param_dtype))
    p_specs = partition_specs(defs, rules.mapping)
    in_specs_batch = {
        k: rules.spec(*axes)
        for k, axes in model.batch_logical_axes(shape).items()}
    batch_abs = model.input_specs(shape)

    t0 = time.monotonic()
    with use_rules(rules), jax.set_mesh(mesh):
        if shape.is_train:
            opt = AdamW(state_dtype=parallel.opt_state_dtype)
            o_abs = jax.eval_shape(opt.init, p_abs)
            m_specs = _opt_specs(p_specs, o_abs.m, parallel.fsdp,
                                 mesh.shape["data"])
            o_specs = type(o_abs)(step=P(), m=m_specs, v=m_specs)
            step = build_train_step(model, parallel, opt)
            jitted = jax.jit(
                step,
                in_shardings=(_ns(mesh, p_specs), _ns(mesh, o_specs),
                              _ns(mesh, in_specs_batch)),
                out_shardings=(_ns(mesh, p_specs), _ns(mesh, o_specs),
                               None))
            lowered = jitted.lower(p_abs, o_abs, batch_abs)
            extra_bytes = _bytes_per_device(o_abs, o_specs, mesh)
            kind_note = "train_step"
        elif shape.kind == "prefill":
            c_defs = model.cache_defs(shape.global_batch, shape.seq_len)
            c_abs = abstract_params(c_defs) if cfg.has_decoder and \
                cfg.family not in ("ssm", "hybrid") else \
                abstract_params(c_defs)
            c_specs = partition_specs(c_defs, rules.mapping)

            def prefill_fn(params, batch, cache):
                return model.prefill(params, batch, cache,
                                     remat=parallel.remat)

            jitted = jax.jit(
                prefill_fn,
                in_shardings=(_ns(mesh, p_specs), _ns(mesh, in_specs_batch),
                              _ns(mesh, c_specs)),
                out_shardings=(None, _ns(mesh, c_specs)))
            lowered = jitted.lower(p_abs, batch_abs, c_abs)
            extra_bytes = _bytes_per_device(c_abs, c_specs, mesh)
            kind_note = "prefill_step"
        else:  # decode
            c_defs = model.cache_defs(shape.global_batch, shape.seq_len)
            c_abs = abstract_params(c_defs)
            c_specs = partition_specs(c_defs, rules.mapping)
            tok_abs = SDS((shape.global_batch, 1), jnp.int32)

            def decode_fn(params, cache, tokens, index):
                return model.decode_step(params, cache, tokens, index)

            jitted = jax.jit(
                decode_fn,
                in_shardings=(_ns(mesh, p_specs), _ns(mesh, c_specs),
                              _ns(mesh, rules.spec("batch", None)),
                              NamedSharding(mesh, P())),
                out_shardings=(None, _ns(mesh, c_specs)))
            lowered = jitted.lower(p_abs, c_abs, tok_abs,
                                   SDS((), jnp.int32))
            extra_bytes = _bytes_per_device(c_abs, c_specs, mesh)
            kind_note = "serve_step"

        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower

        cost = compiled.cost_analysis() or {}
        try:
            mem = compiled.memory_analysis()
            mem_stats = {
                k: getattr(mem, k) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                    "peak_memory_in_bytes")
                if hasattr(mem, k)}
        except Exception as e:           # CPU backend may not support it
            mem_stats = {"error": str(e)}
        hlo = compiled.as_text()
        coll = hlo_collectives.analyze(hlo)

    rec = roofline.build_record(
        arch=arch, shape=shape, cfg=cfg,
        mesh_name="2x16x16" if multi_pod else "16x16", chips=chips,
        cost=cost, wire_bytes=coll.total_wire_bytes,
        collectives=dict(coll.per_kind_bytes), note=kind_note)

    params_bpd = _bytes_per_device(p_abs, p_specs, mesh)
    return {
        **{k: v for k, v in rec.__dict__.items()},
        "memory_analysis": {k: float(v) if not isinstance(v, str) else v
                            for k, v in mem_stats.items()},
        "analytic_bytes_per_device": {
            "params": params_bpd, "state_or_cache": extra_bytes,
            "total": params_bpd + extra_bytes},
        "collective_counts": dict(coll.per_kind_count),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "parallelism": parallel.__dict__,
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="comma list or 'all' (assigned archs)")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--set", action="append", default=[], dest="overrides",
                    help="ParallelismConfig override key=value (perf "
                         "hillclimbing), e.g. --set microbatches=8")
    args = ap.parse_args()

    overrides = {}
    for kv in args.overrides:
        k, v = kv.split("=", 1)
        cur = getattr(ParallelismConfig(), k)
        overrides[k] = type(cur)(int(v) if isinstance(cur, (bool, int))
                                 and v.isdigit() else v) \
            if not isinstance(cur, bool) else v in ("1", "true", "True")

    archs = list(registry.ASSIGNED_ARCHS) if args.arch == "all" \
        else args.arch.split(",")
    shapes = [s.name for s in ALL_SHAPES] if args.shape == "all" \
        else args.shape.split(",")
    meshes = args.mesh.split(",")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results: Dict[str, Any] = {}
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            results = json.load(f)

    for arch in archs:
        cfg = registry.get(arch)
        for sname in shapes:
            shape = SHAPES_BY_NAME[sname]
            ok, why = shape_applicable(cfg, shape)
            for mesh_kind in meshes:
                key = f"{arch}|{sname}|{mesh_kind}"
                if key in results and "error" not in results[key] \
                        and not args.force:
                    print(f"[skip cached] {key}")
                    continue
                if not ok:
                    results[key] = {"skipped": why}
                    print(f"[skip n/a] {key}: {why}")
                    continue
                print(f"[lower+compile] {key} ...", flush=True)
                t0 = time.monotonic()
                try:
                    par = None
                    if overrides:
                        par = registry.default_parallelism(
                            cfg, shape).replace(**overrides)
                    rec = lower_cell(arch, shape,
                                     multi_pod=(mesh_kind == "multi"),
                                     parallel=par)
                    results[key] = rec
                    print(f"  ok in {time.monotonic()-t0:.0f}s "
                          f"bottleneck={rec['bottleneck']} "
                          f"frac={rec['roofline_fraction']:.2f}",
                          flush=True)
                except Exception as e:
                    results[key] = {"error": str(e),
                                    "traceback": traceback.format_exc()}
                    print(f"  FAILED: {e}", flush=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1, default=str)
    n_ok = sum(1 for v in results.values()
               if "error" not in v and "skipped" not in v)
    n_err = sum(1 for v in results.values() if "error" in v)
    print(f"done: {n_ok} ok, {n_err} failed, "
          f"{len(results) - n_ok - n_err} skipped -> {args.out}")


if __name__ == "__main__":
    main()
