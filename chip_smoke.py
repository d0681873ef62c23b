"""Chip smoke: the Seneca device route feeding a ViT-Huge train step.

    python chip_smoke.py

Runs ``repro.launch.train`` once, on one TPU, the way a user would call
it: ``vit-huge`` at full width (d_model 1280, 16 heads, d_ff 5120, 197
tokens, 1000 classes, bf16 parameters, all 32 layers, random weights
from seed 0), batch 32, fed by ``SenecaServer`` over the ImageNet-shaped
synthetic dataset (256x256 decode, 224x224 crop, generated from its
seed) through ``DSIPipeline(executor="device")`` with an HBM cache tier
sized for the whole augmented set.  Two epochs over 512 samples: epoch
1 fetches from storage and runs the fused decode+augment kernel, epoch
2 is served from the HBM tier.

It fails (non-zero exit, no result line) when the platform is not
``tpu``, the fused kernel does not compile to a Mosaic kernel, a loss
is not finite, a refill or telemetry error was counted, a served row
differs from the host reference by more than 2e-6, or the warm epoch
moved host->device payload bytes.  The last stdout line is
``{"ok": true, "device": {...}}``; everything else comes before it.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_SAMPLES = 512
BATCH = 32
EPOCHS = 2
PARITY_ROWS = 4
PARITY_ATOL = 2e-6        # the host-parity bound of the pipeline tests


def fail(msg: str) -> None:
    print(f"chip_smoke FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def main() -> None:
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repro package is not next to this script ({e})")
    print("compile cache:", enable_compile_cache())

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's default device is {dev.platform!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print("device:", device)

    import jax.numpy as jnp

    from repro.data.augment import augment_np
    from repro.data.pipeline import _aug_seed
    from repro.data.synthetic import imagenet_like
    from repro.kernels.decode.kernel import decode_augment
    from repro.launch import train

    ds = imagenet_like(n=N_SAMPLES)
    (ih, iw), (ch, cw) = ds.image_hw, ds.crop_hw

    # -- the fused kernel compiles to Mosaic --------------------------
    t0 = time.monotonic()
    scalars = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    kernel_text = decode_augment.lower(
        scalars, scalars, scalars, scalars, scalars, img_h=ih, img_w=iw,
        crop_h=ch, crop_w=cw).compile().as_text()
    kernel_s = time.monotonic() - t0
    if "tpu_custom_call" not in kernel_text:
        fail("the fused decode+augment kernel compiled without a "
             "tpu_custom_call")
    print(f"fused kernel {ih}->{ch} B={BATCH}: tpu_custom_call, compiled "
          f"in {kernel_s:.2f}s")

    # -- train through the normal entry point -------------------------
    hbm_mb = math.ceil(1.2 * N_SAMPLES * ds.augmented_bytes() / 2**20)
    args = train.parse_args([
        "--arch", "vit-huge", "--no-reduced", "--remat", "full",
        "--batch", str(BATCH), "--steps", str(EPOCHS * N_SAMPLES // BATCH),
        "--lr", "1e-4", "--ckpt-every", "0", "--executor", "device",
        "--dataset", "imagenet", "--samples", str(N_SAMPLES),
        "--device-cache-mb", str(hbm_mb)])
    served = []

    def keep_first(batch):
        if not served:
            served.append(batch)

    out = train.run(args, consume_hook=keep_first)
    cfg = out["cfg"]
    print(f"model: {cfg.name} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} d_ff={cfg.d_ff} "
          f"tokens={cfg.frontend_tokens} classes={cfg.n_classes} "
          f"layers={cfg.n_layers}")
    mem = out["memory"]
    step_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"train step: compile {out['compile_s']:.1f}s, "
          f"{step_bytes / 2**30:.2f} GiB (args+out-alias+temp), "
          f"HBM tier {hbm_mb} MiB")
    losses = [h["loss"] for h in out["history"]]
    print(f"steps: {len(losses)} losses: "
          + " ".join(f"{x:.4f}" for x in losses))
    if len(losses) != args.steps:
        fail(f"took {len(losses)} steps, asked for {args.steps}")
    if not all(math.isfinite(x) for x in losses):
        fail("a loss is not finite")

    h2d = out["h2d_by_epoch"]
    print("h2d bytes by epoch:", h2d)
    if len(h2d) != EPOCHS:
        fail(f"expected {EPOCHS} epochs of h2d records, got {h2d}")
    if h2d[-1] != 0:
        fail(f"the warm epoch moved {h2d[-1]} h2d bytes")

    stats = out["stats"]
    hbm = stats["hbm"]["augmented"]
    print(f"HBM tier: hits={hbm['hbm_hits']} "
          f"entries={hbm['hbm_entries']} "
          f"resident={stats['residency_counts']['hbm']}")
    if hbm["hbm_hits"] < N_SAMPLES:
        fail(f"only {hbm['hbm_hits']} HBM hits over {EPOCHS} epochs")
    errors = stats["telemetry"]["errors"]
    print(f"refill_errors={stats['refill_errors']} "
          f"telemetry errors={errors}")
    if stats["refill_errors"] or any(errors.values()):
        fail("refill or telemetry errors were counted")

    # -- served rows against the host reference -------------------
    first = served[0]
    worst = 0.0
    for row, sid in zip(np.asarray(first["images"][:PARITY_ROWS]),
                        first["ids"][:PARITY_ROWS].tolist()):
        ref = augment_np(ds.decode(ds.encoded(sid), sid), ds.crop_hw,
                         np.random.default_rng(_aug_seed(0, sid)))
        worst = max(worst, float(np.max(np.abs(row - ref))))
    print(f"host parity: {PARITY_ROWS} served rows of the first batch, "
          f"max |device - augment_np| = {worst:.3g} "
          f"(bound {PARITY_ATOL})")
    if not worst <= PARITY_ATOL:
        fail(f"served rows differ from the host reference by {worst}")

    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
