"""End-to-end training driver.

Wires the Seneca data service (MDP + ODS), the threaded DSI pipeline, the
model zoo, the optimizer, and fault tolerance into one runnable loop:

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b \
        --steps 200 --batch 32 --seq 128

The smoke-scale config is the default so the driver runs on CPU;
``--no-reduced`` selects the full config.
Image consumers are fed by the real Seneca image pipeline: the encoder
(--arch vit-huge) gets patch embeddings and class labels, a VLM (--arch
kimi-vl-a3b, internvl2-2b) the patch embeddings followed by the
config's ``text_tokens`` caption tokens of each sample, with next-token
labels on the text.  Text-only LM archs use the token pipeline
(synthetic corpus).  The image pipeline's device route is

    PYTHONPATH=src python -m repro.launch.train --arch vit-huge \
        --no-reduced --remat full --batch 32 --executor device \
        --dataset imagenet --samples 512 --device-cache-mb 353 --steps 32

fused decode+augment kernel for cold samples, an HBM cache tier that
serves warm samples with no host->device payload bytes, device collate
and a device-side patchify stub, so a batch reaches the train step
without a host round trip.  The feed builds the next batch on the
pipeline's prefetch thread while the step runs, and takes it with
``DSIPipeline.get(timeout=None)``.  A VLM trains on the same route:

    PYTHONPATH=src python -m repro.launch.train --arch kimi-vl-a3b \
        --executor device --samples 64 --batch 4 --steps 8 --ckpt-every 0

(the whole of kimi-vl-a3b does not fit one chip; the benchmark's
``bench/configs/kimi-vl-a3b.json`` trains one chip's share of it).
"""
from __future__ import annotations

import argparse
import functools
import itertools
import shutil
import tempfile
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import AZURE_NC96, GB, SenecaServer
from repro.configs import registry
from repro.configs.base import ModelConfig, ParallelismConfig
from repro.data.pipeline import EXECUTORS, DSIPipeline
from repro.data.storage import RemoteStorage
from repro.data.synthetic import caption_ids, imagenet_like, tiny
from repro.distributed.ft import FTConfig, ResilientTrainer
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build
from repro.train.optimizer import AdamW, warmup_cosine
from repro.train.step import build_train_step

IMAGE_DATASETS = {"tiny": tiny, "imagenet": imagenet_like}


def lm_batch_source(model, batch: int, seq: int, seed: int = 0):
    """Synthetic-corpus LM batches (deterministic token stream)."""
    rng = np.random.default_rng(seed)
    V = model.cfg.vocab_size

    def next_batch():
        toks = rng.integers(0, V, size=(batch, seq + 1), dtype=np.int64)
        b = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
             "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
        if model.cfg.family == "vlm":
            p = model.cfg.frontend_tokens
            b["tokens"] = b["tokens"][:, :seq - p]
            b["patch_embeds"] = jnp.asarray(
                rng.normal(size=(batch, p, model.cfg.d_model)),
                jnp.bfloat16)
            b["labels"] = jnp.asarray(toks[:, 1:seq + 1], jnp.int32)
        if model.cfg.family in ("encdec", "audio"):
            from repro.models.transformer import encdec_src_len
            b["src_embeds"] = jnp.asarray(
                rng.normal(size=(batch, encdec_src_len(seq),
                                 model.cfg.d_model)), jnp.bfloat16)
        return b

    return next_batch


@functools.partial(jax.jit, static_argnames=("tokens", "d_model"))
def patchify_stub(images: jax.Array, tokens: int,
                  d_model: int) -> jax.Array:
    """Stand-in for the vision frontend, on device: the flattened pixels
    of each (B,H,W,3) image, tiled to fill (B, tokens, d_model) bf16
    embeddings."""
    B = images.shape[0]
    flat = images.reshape(B, -1)
    reps = -(-tokens * d_model // flat.shape[1])
    emb = jnp.tile(flat, (1, reps))[:, :tokens * d_model]
    return emb.reshape(B, tokens, d_model).astype(jnp.bfloat16)


def image_batch_source(model, batch: int, seed: int = 0,
                       backend: str = "numpy", *, dataset=None,
                       executor: str = "per-sample",
                       device_cache_bytes: int = 0,
                       consume_hook: Optional[Callable] = None):
    """Real Seneca pipeline: storage -> 3-form cache -> ODS -> augment.

    ``dataset`` defaults to ``tiny(n=4096)``.  ``device_cache_bytes > 0``
    adds an HBM tier that holds the augmented form; with it the single
    job keeps its augmented rows across epochs (capacity admission, LRU
    eviction) — ODS's refcount eviction, whose threshold is the number
    of jobs, would drop each row on its first serve.

    A ``vlm`` model is fed, beside the patch embeddings, the caption of
    each sample (``text_tokens`` ids after the image tokens, a pure
    function of the dataset's seed and the sample id:
    :func:`repro.data.synthetic.caption_ids`) and next-token labels on
    the text positions, -1 on the image ones.

    The feed builds one batch ahead of its caller: its first call starts
    the pipeline's prefetch thread (``DSIPipeline.start_prefetch``),
    which runs the route for batch n+1 while the caller's step n runs,
    and each call takes the next built batch with
    ``pipe.get(timeout=None)``, which waits as long as that thread lives
    and re-raises its error.  The batches are those serial
    ``pipe.next_batch()`` calls give, in the same order; calls made
    before the feed's first one run on the caller and continue the same
    sequence.  ``patchify_stub``, the captions and ``consume_hook`` run
    on the caller, as each batch is handed out.  A
    ``Session.checkpoint_state()`` taken while the feed runs records the
    sampler up to three batches (two queued, one held) past the last
    batch the caller took.

    Returns (next_batch, pipeline, server); the server is the
    :class:`repro.api.SenecaServer` facade — open more sessions on it for
    concurrent jobs."""
    ds = tiny(n=4096) if dataset is None else dataset
    storage = RemoteStorage(ds, bandwidth=None)
    tier = {}
    if device_cache_bytes > 0:
        tier = dict(device_cache_bytes=device_cache_bytes,
                    hbm_split=(0.0, 0.0, 1.0), use_ods=False,
                    admission="capacity", eviction="lru")
    server = SenecaServer.for_dataset(ds, cache_bytes=int(0.2 * GB),
                                      hardware=AZURE_NC96, seed=seed,
                                      backend=backend, **tier)
    pipe = DSIPipeline(server.open_session(batch_size=batch), storage,
                       n_workers=4, executor=executor,
                       consume_hook=consume_hook)
    T, d = model.cfg.frontend_tokens, model.cfg.d_model
    n_classes = max(model.cfg.n_classes, 1)

    def take():
        # the first call starts the prefetch thread (later ones find it
        # running), so calls of pipe.next_batch() made before it, as a
        # tier fill's, run on the caller and continue the same ids
        pipe.start_prefetch()
        return pipe.get(timeout=None)

    def next_batch():
        raw = take()
        with pipe.times.span("patchify"):
            return {"patch_embeds": patchify_stub(raw["images"], T, d),
                    "labels": jnp.asarray(raw["labels"] % n_classes,
                                          jnp.int32)}

    if getattr(model.cfg, "family", None) != "vlm":
        return next_batch, pipe, server
    n_text, vocab = model.cfg.text_tokens, model.cfg.vocab_size

    def next_vlm_batch():
        raw = take()
        with pipe.times.span("patchify"):
            embeds = patchify_stub(raw["images"], T, d)
        with pipe.times.span("text"):
            text = caption_ids(ds.seed, raw["ids"], n_text + 1, vocab)
            labels = np.concatenate(
                [np.full((len(text), T), -1, np.int32), text[:, 1:]], 1)
            return {"patch_embeds": embeds,
                    "tokens": jnp.asarray(text[:, :-1]),
                    "labels": jnp.asarray(labels)}

    return next_vlm_batch, pipe, server


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen3-8b",
                    choices=registry.list_archs())
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-scale config (default); --no-reduced "
                         "selects the full one")
    ap.add_argument("--remat", default="none", choices=("none", "full"),
                    help="recompute each layer's activations in the "
                         "backward pass")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128,
                    help="text-only LM sequence length (a VLM's comes "
                         "from its config)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None,
                    help="resume from / checkpoint to this directory "
                         "(default: a fresh directory for this run, "
                         "removed when it ends)")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="steps between checkpoints; 0 disables them")
    ap.add_argument("--microbatches", type=int, default=1)
    image = ap.add_argument_group(
        "image pipeline (--arch vit-huge, kimi-vl-a3b, internvl2-2b)")
    image.add_argument("--executor", default="per-sample",
                       choices=EXECUTORS)
    image.add_argument("--dataset", default="tiny",
                       choices=sorted(IMAGE_DATASETS))
    image.add_argument("--samples", type=int, default=4096)
    image.add_argument("--device-cache-mb", type=float, default=0.0,
                       help="HBM cache tier size (device executor)")
    return ap.parse_args(argv)


def model_config(args: argparse.Namespace) -> ModelConfig:
    return registry.get_reduced(args.arch) if args.reduced \
        else registry.get(args.arch)


def run(args: argparse.Namespace,
        consume_hook: Optional[Callable] = None) -> Dict:
    """Train ``args.steps`` steps; returns the history, the step's
    compile seconds and memory analysis and, for image consumers
    (encoder and VLM archs), the pipeline's stage seconds, the server's
    final ``stats()`` and the host->device payload bytes of each epoch.
    ``consume_hook`` sees every raw batch the image pipeline serves.

    The step is compiled ahead of time on the first batch, with the
    parameters and optimizer state donated, so the compile time and the
    step's device memory are reported before training starts."""
    cfg = model_config(args)
    model = build(cfg)
    print(f"arch={cfg.name} params={model.n_params():,} "
          f"d_model={cfg.d_model} layers={cfg.n_layers} "
          f"(reduced={args.reduced})")
    params = model.init(jax.random.key(0))
    opt = AdamW(lr=args.lr,
                schedule=warmup_cosine(args.lr, 20, args.steps))
    opt_state = opt.init(params)
    parallel = ParallelismConfig(microbatches=args.microbatches,
                                 remat=args.remat)

    out: Dict = {"cfg": cfg}
    pipe = server = None
    if cfg.family in ("encoder", "vlm"):
        ds = IMAGE_DATASETS[args.dataset](n=args.samples)
        source, pipe, server = image_batch_source(
            model, args.batch, dataset=ds, executor=args.executor,
            device_cache_bytes=int(args.device_cache_mb * 2**20),
            consume_hook=consume_hook)
        print(f"seneca partition: {server.partition.label} "
              f"dataset={ds.name} n={ds.n_samples} "
              f"executor={args.executor}")
        source = _per_epoch_h2d(source, pipe.telemetry, ds.n_samples,
                                args.batch, out.setdefault("h2d_by_epoch",
                                                           []))
    else:
        source = lm_batch_source(model, args.batch, args.seq)

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro-train-ckpt-")
    try:
        first = source()
        t0 = time.monotonic()
        step = jax.jit(build_train_step(model, parallel, opt),
                       donate_argnums=(0, 1))
        compiled = step.lower(params, opt_state, first).compile()
        out["compile_s"] = time.monotonic() - t0
        out["memory"] = compiled.memory_analysis()
        print(f"train step compiled in {out['compile_s']:.1f}s; "
              f"memory: {out['memory']}")
        trainer = ResilientTrainer(
            step_fn=compiled, params=params, opt_state=opt_state,
            cfg=FTConfig(ckpt_dir=ckpt_dir, ckpt_every=args.ckpt_every),
            batch_source=_chain(first, source))
        del params, opt_state           # donated to the first step
        t0 = time.monotonic()
        hist = trainer.run(args.steps)
        dt = time.monotonic() - t0
        if pipe is not None:
            out.update(stage_s=pipe.times.as_dict(), stats=server.stats())
    finally:
        if args.ckpt_dir is None:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
        if pipe is not None:
            pipe.stop()
            server.close()
    if not hist:
        raise RuntimeError(
            f"no steps taken: {ckpt_dir} already holds a checkpoint at "
            f"step >= --steps {args.steps}; pass a fresh --ckpt-dir, or "
            f"omit it for a per-run directory")
    out["history"] = hist
    print(f"{len(hist)} steps in {dt:.1f}s "
          f"({len(hist) * args.batch / dt:.1f} samples/s)")
    print(f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
    if pipe is not None:
        print("pipeline stage seconds:", out["stage_s"])
        print("h2d bytes by epoch:", out["h2d_by_epoch"])
    return out


def _chain(first, source):
    """A batch source that yields ``first`` and then ``source()``."""
    it = itertools.chain([first], iter(source, None))
    return lambda: next(it)


def _per_epoch_h2d(source, telemetry, n_samples: int, batch: int,
                   record: list):
    """Wrap ``source`` to add the host->device payload bytes (the
    pipeline's "h2d" telemetry channel) recorded since its last batch to
    the epoch of the batch it hands out, in ``record``.  The feed builds
    up to three batches ahead, so the bytes of those built past an
    epoch's end count in that epoch."""
    mark = [0, telemetry.channel_total_bytes("h2d")]  # samples, h2d seen

    def next_batch():
        b = source()
        mark[0] += batch
        epoch = (mark[0] - 1) // n_samples
        if epoch == len(record):
            record.append(0)
        now = telemetry.channel_total_bytes("h2d")
        record[epoch] += now - mark[1]
        mark[1] = now
        return b

    return next_batch


def main() -> None:
    enable_compile_cache()
    out = run(parse_args())
    if "stats" in out:
        print("seneca stats:", out["stats"])


if __name__ == "__main__":
    main()
