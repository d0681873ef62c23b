"""The plain references agree with the program where they must: the
parameters drawn from a seed, the served rows, and the forward pass,
at a small size on the CPU."""
import dataclasses

import benchpath  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference import synthetic_images as ref_data
from reference import vit_encoder as ref

SIZES = {"n_layers": 2, "d_model": 64, "n_heads": 4, "d_ff": 128,
         "frontend_tokens": 17, "n_classes": 16, "norm_eps": 1e-5}


def program_model():
    from repro.configs import registry
    from repro.models.model import build
    cfg = dataclasses.replace(
        registry.get("vit-huge"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=128, n_classes=16, frontend_tokens=17)
    return build(cfg)


def by_name(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(k.key) for k in path): x for path, x in flat}


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_parameters_drawn_from_the_seed_match_bit_for_bit(seed):
    prog = by_name(jax.jit(program_model().init)(jax.random.key(seed)))
    mine = ref.init_params(SIZES, seed)
    assert set(prog) == set(mine)
    for k in prog:
        assert prog[k].dtype == mine[k].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(prog[k], np.float32),
                                      np.asarray(mine[k], np.float32))


def test_served_row_and_label_match_the_dataset():
    from repro.data.augment import augment_np
    from repro.data.synthetic import SyntheticDataset
    ds = SyntheticDataset("t", 100, 24_000, (64, 64), (56, 56), 100,
                          seed=987)
    for sid in (0, 41, 99):
        img = ds.decode(ds.encoded(sid), sid)
        np.testing.assert_array_equal(
            img, ref_data.decoded(987, 24_000, (64, 64), sid))
        row = augment_np(img, (56, 56), np.random.default_rng(sid ^ 0x5EED))
        gap, _ = ref_data.closest_row(row, 987, 24_000, (64, 64), (56, 56),
                                      sid, range(2))
        assert gap < 1e-6
        assert ref_data.label(sid, 100) == ds.label(sid)


def test_forward_matches_the_program_in_float32():
    model = program_model()
    params = jax.jit(model.init)(jax.random.key(3))
    emb = jax.random.normal(jax.random.key(4), (4, 17, 64)).astype(
        jnp.bfloat16)
    labels = jnp.arange(4) % 16
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        prog = model.loss(p32, {"patch_embeds": emb, "labels": labels})
        mine = {k: x.astype(jnp.float32) for k, x in by_name(params).items()}
        z = ref.logits(mine, emb, SIZES, "f32")
    loss = jnp.mean(jax.nn.logsumexp(z, -1) - z[jnp.arange(4), labels])
    assert float(loss) == pytest.approx(float(prog), rel=1e-5)
