"""Per-arch smoke tests (reduced configs) + decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import TRAIN_4K, MoEConfig
from repro.models.model import build, make_batch
from repro.models.params import padded_vocab

ARCHS = registry.list_archs()


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    """One forward + one train step on CPU: shapes + no NaNs (deliverable
    f: reduced-config smoke test per assigned architecture)."""
    cfg = registry.get_reduced(arch)
    m = build(cfg)
    params = m.init(jax.random.key(0))
    batch = make_batch(jax.random.key(1), m, TRAIN_4K, reduced_shape=(2, 32))
    logits, aux = m.forward(params, {k: v for k, v in batch.items()
                                     if k != "labels"})
    if cfg.family == "encoder":
        assert logits.shape == (2, cfg.n_classes)
    else:
        assert logits.shape[0] == 2 and \
            logits.shape[-1] == padded_vocab(cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))

    loss = m.loss(params, batch)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: m.loss(p, batch))(params)
    gn = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


# latent attention has no decode cache (tests/test_kimi_vl.py)
@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if registry.get(a).has_decoder
                                  and registry.get(a).mla is None])
def test_decode_step_shapes(arch):
    cfg = registry.get_reduced(arch)
    m = build(cfg)
    params = m.init(jax.random.key(0))
    cache = m.init_cache(batch=2, s_max=64)
    logits, cache2 = m.decode_step(params, cache,
                                   jnp.ones((2, 1), jnp.int32),
                                   jnp.int32(3))
    assert logits.shape[:2] == (2, 1)
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("arch", ["qwen3-8b", "qwen1.5-32b",
                                  "seamless-m4t-large-v2"])
def test_prefill_decode_matches_forward(arch):
    """Attention-family consistency: prefill cache + decode_step(S) equals
    forward on the extended sequence (exactness, not allclose)."""
    cfg = registry.get_reduced(arch)
    m = build(cfg)
    params = m.init(jax.random.key(0))
    B, S = 2, 16
    batch = make_batch(jax.random.key(1), m, TRAIN_4K, reduced_shape=(B, S))
    batch.pop("labels", None)
    cache = m.init_cache(batch=B, s_max=S + 4)
    logits_pf, cache = m.prefill(params, batch, cache)
    full, _ = m.forward(params, batch)
    np.testing.assert_array_equal(np.asarray(logits_pf), np.asarray(full))

    nxt = jnp.full((B, 1), 3, jnp.int32)
    ext = dict(batch)
    ext["tokens"] = jnp.concatenate([batch["tokens"], nxt], axis=1)
    full2, _ = m.forward(params, ext)
    dec, _ = m.decode_step(params, cache, nxt, jnp.int32(S))
    np.testing.assert_allclose(
        np.asarray(full2[:, -1], np.float32),
        np.asarray(dec[:, 0], np.float32), atol=1e-2, rtol=1e-2)


def test_moe_decode_exact_without_drops():
    cfg = registry.get_reduced("deepseek-moe-16b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=100.0))
    m = build(cfg)
    params = m.init(jax.random.key(0))
    B, S = 2, 12
    batch = make_batch(jax.random.key(1), m, TRAIN_4K, reduced_shape=(B, S))
    batch.pop("labels", None)
    cache = m.init_cache(batch=B, s_max=S + 2)
    _, cache = m.prefill(params, batch, cache)
    nxt = jnp.full((B, 1), 5, jnp.int32)
    ext = {"tokens": jnp.concatenate([batch["tokens"], nxt], 1)}
    full2, _ = m.forward(params, ext)
    dec, _ = m.decode_step(params, cache, nxt, jnp.int32(S))
    np.testing.assert_allclose(np.asarray(full2[:, -1], np.float32),
                               np.asarray(dec[:, 0], np.float32),
                               atol=1e-3)


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "zamba2-1.2b"])
def test_ssm_decode_trajectory_matches_forward(arch):
    """Recurrent-state consistency: decoding token-by-token from scratch
    reproduces the chunked-SSD forward logits at every position."""
    cfg = registry.get_reduced(arch)
    m = build(cfg)
    params = m.init(jax.random.key(0))
    B, S = 2, 12
    toks = jax.random.randint(jax.random.key(1), (B, S), 0,
                              cfg.vocab_size, jnp.int32)
    full, _ = m.forward(params, {"tokens": toks})
    cache = m.init_cache(batch=B, s_max=S)
    outs = []
    for t in range(S):
        logits, cache = m.decode_step(params, cache, toks[:, t:t + 1],
                                      jnp.int32(t))
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    # bf16 logits: tolerance is ~2 ulp at logit scale (no growth over
    # positions = the recurrence itself is exact; see git history)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               atol=1.5e-1, rtol=5e-2)
    # and the trajectories agree on the argmax almost everywhere
    agree = np.mean(np.argmax(np.asarray(dec, np.float32), -1) ==
                    np.argmax(np.asarray(full, np.float32), -1))
    assert agree >= 0.9, agree


def test_vocab_padding_masked_in_loss():
    cfg = registry.get_reduced("qwen3-8b")
    m = build(cfg)
    params = m.init(jax.random.key(0))
    batch = make_batch(jax.random.key(1), m, TRAIN_4K, reduced_shape=(2, 16))
    logits, _ = m.forward(params, {"tokens": batch["tokens"]})
    # padded logits exist but must never win the softmax after masking
    assert logits.shape[-1] == padded_vocab(cfg.vocab_size)
    loss = m.loss(params, batch)
    assert float(loss) < jnp.log(padded_vocab(cfg.vocab_size)) + 1.0


def test_label_ignore_index():
    from repro.models.transformer import cross_entropy
    logits = jax.random.normal(jax.random.key(0), (2, 4, 32))
    labels = jnp.array([[1, 2, -1, -1], [3, -1, -1, -1]])
    ce = cross_entropy(logits, labels, 32)
    ce_full = cross_entropy(logits, jnp.abs(labels), 32)
    assert np.isfinite(float(ce)) and float(ce) != float(ce_full)


def test_param_counts_match_analytic():
    """ParamDef totals track ModelConfig.n_params within a few %."""
    for arch in ("qwen3-8b", "deepseek-7b", "mamba2-1.3b"):
        cfg = registry.get(arch)
        m = build(cfg)
        analytic = cfg.n_params()
        # padded vocab inflates the defs count; bound the gap
        defs = m.n_params()
        assert abs(defs - analytic) / analytic < 0.05, arch
