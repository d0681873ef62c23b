"""Optimizer / train-step / compression unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import TRAIN_4K, ParallelismConfig
from repro.models.model import build, make_batch
from repro.train import compression
from repro.train.optimizer import (AdamW, Quantized, _dequantize,
                                   _dequantize_pos, _quantize,
                                   _quantize_pos, warmup_cosine)
from repro.train.step import build_train_step


def _setup(arch="qwen3-8b", bs=(4, 32)):
    cfg = registry.get_reduced(arch)
    m = build(cfg)
    params = m.init(jax.random.key(0))
    batch = make_batch(jax.random.key(1), m, TRAIN_4K, reduced_shape=bs)
    return m, params, batch


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16", "int8"])
def test_loss_decreases(state_dtype):
    m, params, batch = _setup()
    opt = AdamW(lr=1e-3, state_dtype=state_dtype, eps=1e-6)
    state = opt.init(params)
    step = jax.jit(build_train_step(m, ParallelismConfig(), opt))
    first = None
    for _ in range(15):
        params, state, metrics = step(params, state, batch)
        first = first if first is not None else float(metrics["loss"])
    assert float(metrics["loss"]) < first - 0.5


def test_microbatch_grads_match_full_batch():
    m, params, batch = _setup(bs=(4, 16))
    g_full = jax.grad(lambda p: m.loss(p, batch))(params)
    mbs = jax.tree.map(lambda x: x.reshape((2, 2) + x.shape[1:]), batch)
    g_acc = jax.tree.map(jnp.zeros_like, g_full)
    for i in range(2):
        mb = jax.tree.map(lambda x: x[i], mbs)
        g = jax.grad(lambda p: m.loss(p, mb))(params)
        g_acc = jax.tree.map(jnp.add, g_acc, g)
    g_acc = jax.tree.map(lambda x: x / 2, g_acc)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_acc)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=3e-2, rtol=3e-2)


def test_grad_clip_limits_norm():
    m, params, batch = _setup()
    opt = AdamW(lr=0.0, grad_clip=0.5)
    state = opt.init(params)
    step = build_train_step(m, ParallelismConfig(), opt)
    _, _, metrics = step(params, state, batch)
    assert float(metrics["grad_norm"]) > 0


def test_warmup_cosine_shape():
    f = warmup_cosine(1.0, warmup=10, total=100)
    assert float(f(jnp.int32(0))) == 0.0
    assert abs(float(f(jnp.int32(10))) - 1.0) < 0.11
    assert float(f(jnp.int32(100))) < 0.15
    assert float(f(jnp.int32(5))) < float(f(jnp.int32(10)))


def test_quantize_roundtrip_signed():
    x = jax.random.normal(jax.random.key(0), (1000,)) * 3.0
    q = _quantize(x)
    err = jnp.max(jnp.abs(_dequantize(q, x.shape) - x))
    assert float(err) <= float(jnp.max(jnp.abs(x))) / 127 + 1e-6


def test_quantize_pos_dynamic_range():
    """Fourth-root coding must resolve values 6 decades below blockmax."""
    x = jnp.concatenate([jnp.full((128,), 1e-6), jnp.full((128,), 1.0)])
    q = _quantize_pos(x)
    back = _dequantize_pos(q, x.shape)
    assert float(back[0]) > 0, "small v must not collapse to 0"
    np.testing.assert_allclose(np.asarray(back[-1]), 1.0, rtol=0.02)


def test_compression_error_bound():
    g = jax.random.normal(jax.random.key(1), (513,))
    r = jnp.zeros_like(g)
    q, scale, new_r = compression.compress(g, r)
    deq = compression.decompress(q, scale, g.shape)
    assert float(jnp.max(jnp.abs(deq + new_r - g))) < 1e-5  # exact split
    assert float(jnp.max(jnp.abs(new_r))) <= float(
        jnp.max(jnp.abs(scale))) + 1e-6


def test_error_feedback_is_unbiased_over_steps():
    """Repeatedly compressing the same gradient with EF transmits its full
    magnitude over time (residual does not grow)."""
    g = jax.random.normal(jax.random.key(2), (300,)) * 1e-3
    r = jnp.zeros_like(g)
    sent = jnp.zeros_like(g)
    for _ in range(50):
        q, s, r = compression.compress(g, r)
        sent = sent + compression.decompress(q, s, g.shape)
    np.testing.assert_allclose(np.asarray(sent / 50), np.asarray(g),
                               atol=1e-4)


# ----------------------------------------------------------------------
# launch/train.py driver and the compile-cache helper
def _train_argv(*extra):
    return ["--arch", "vit-huge", "--steps", "2", "--batch", "4",
            "--samples", "64", *extra]


def test_reduced_is_a_real_switch():
    from repro.launch import train
    assert train.parse_args(["--arch", "vit-huge"]).reduced
    full = train.model_config(train.parse_args(
        ["--arch", "vit-huge", "--no-reduced"]))
    assert (full.d_model, full.n_heads, full.d_ff, full.n_layers,
            full.frontend_tokens, full.n_classes) == \
        (1280, 16, 5120, 32, 197, 1000)
    small = train.model_config(train.parse_args(["--arch", "vit-huge"]))
    assert small.d_model < full.d_model


def test_per_run_checkpoint_dir_takes_every_step(tmp_path, monkeypatch):
    """Without --ckpt-dir each run checkpoints into a fresh directory of
    its own (removed at the end), so a second identical run does not
    resume the first and takes all its steps again."""
    import tempfile

    from repro.launch import train
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    for _ in range(2):
        out = train.run(train.parse_args(_train_argv()))
        assert len(out["history"]) == 2
    assert list(tmp_path.iterdir()) == []


def test_finished_checkpoint_dir_is_a_clear_error(tmp_path):
    """An explicit --ckpt-dir resumes; one that already holds the last
    step leaves nothing to train, which is an error that says so."""
    from repro.launch import train
    argv = _train_argv("--ckpt-dir", str(tmp_path / "ckpt"))
    assert len(train.run(train.parse_args(argv))["history"]) == 2
    with pytest.raises(RuntimeError, match="no steps taken"):
        train.run(train.parse_args(argv))


def test_device_route_warm_epoch_moves_no_h2d_bytes():
    """The device route through the driver: fused kernel for the cold
    epoch, HBM tier for the warm one — no host->device payload bytes in
    either, and every row of the warm epoch is an HBM hit."""
    from repro.launch import train
    out = train.run(train.parse_args([
        "--arch", "vit-huge", "--steps", "16", "--batch", "8",
        "--samples", "64", "--executor", "device", "--device-cache-mb",
        "4", "--ckpt-every", "0"]))
    assert out["h2d_by_epoch"] == [0, 0]
    assert out["stats"]["hbm"]["augmented"]["hbm_hits"] >= 64
    assert out["stats"]["refill_errors"] == 0
    assert all(np.isfinite(h["loss"]) for h in out["history"])


def test_compile_cache_leaves_env_setting_alone(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_fixed_dir_in_checkout(monkeypatch):
    """Unset env: the same in-checkout directory on every call, and git
    ignores it.  (jax.config.update is stubbed: tests never turn the
    persistent cache on.)"""
    from pathlib import Path

    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert first == second == str(root / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
