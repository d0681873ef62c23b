"""The kimi-vl-a3b configuration and its reference: the harness runs a
tiny copy of it to ``correct``, the float8 control and the half batch
fail, the reference agrees with the program, and the real configuration
is one the program builds at its sizes."""
import json
from pathlib import Path
from types import SimpleNamespace

import benchpath
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import harness, system
from benchlib.catalog import BENCH_DIR, Catalog

DATA = Path(benchpath.DATA)
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The tests' spec with one more cell: kimi-vl-tiny on tiny-cold."""
    spec = json.loads((DATA / "spec.json").read_text())
    spec["configs"].append({"name": "kimi-vl-tiny", "source": "test",
                            "file": "bench/tests/data/configs/"
                                    "kimi-vl-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "kimi-tiny", "config": "kimi-vl-tiny",
                              "traffic": "tiny-cold", "chips": 1,
                              "why": "test"})
    path = tmp_path_factory.mktemp("spec") / "spec.json"
    path.write_text(json.dumps(spec))
    return Catalog(path, dirs=[DATA]).cell("kimi-tiny")


def test_sound_tiny_run_is_correct(tiny):
    out = harness.run(tiny, 2147483711, 0.5, False, 0.0, DEVICE)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"samples_per_s", "step_ms_p90",
                                   "setup_s"}


def test_control_and_half_batch_fail_the_tiny_limits(tiny):
    import control
    r = control.readings(tiny, 7)
    limits = {k: v for k, v in tiny.config["limits"].items()
              if k in r["program"]}
    assert all(r["program"][k] <= v for k, v in limits.items()), r
    for name in ("control_fp8", "fault_half_batch"):
        assert any(r[name][k] > v for k, v in limits.items()), (name, r)


def test_program_loss_matches_the_reference_in_float32(tiny):
    """The program's model with float32 parameters and activations, and
    the reference, on one batch of the feed's own inputs."""
    from repro.data.synthetic import caption_ids
    from repro.models.model import build
    ref = tiny.reference
    cfg = system.model_config(tiny.config)
    sizes = tiny.config["sizes"]
    model = build(cfg)
    params = model.init(jax.random.key(11), dtype=jnp.float32)
    B, P, n = 3, cfg.frontend_tokens, cfg.text_tokens
    images = np.asarray(jax.random.uniform(jax.random.key(12),
                                           (B, 56, 56, 3)))
    ids = np.array([5, 17, 123456])
    emb, tokens, labels = ref.inputs(sizes, 99, images, ids)
    np.testing.assert_array_equal(
        tokens, caption_ids(99, ids, n + 1, cfg.vocab_size)[:, :-1])
    batch = {"patch_embeds": jnp.asarray(emb), "tokens": jnp.asarray(tokens),
             "labels": jnp.asarray(labels)}
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): x
            for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert [s[0] for s in ref.leaf_specs(sizes)] == list(flat)
    with jax.default_matmul_precision("highest"):
        prog = float(model.loss(params, batch))
        mine = float(ref.block_loss(flat, jnp.asarray(emb),
                                    jnp.asarray(tokens), jnp.asarray(labels),
                                    sizes, "f32", int(np.sum(labels >= 0)),
                                    B))
    assert mine == pytest.approx(prog, rel=1e-5)


def test_reference_draws_the_programs_parameters(tiny):
    from repro.models.model import build
    cfg = system.model_config(tiny.config)
    params = build(cfg).init(jax.random.key(31))
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): x
            for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    mine = tiny.reference.init_params(tiny.config["sizes"], 31)
    assert list(mine) == list(flat)
    for k in flat:
        assert mine[k].dtype == flat[k].dtype, k
        np.testing.assert_array_equal(np.asarray(mine[k]),
                                      np.asarray(flat[k]), err_msg=k)


def test_the_benchmark_config_is_the_programs_at_its_sizes():
    from repro.models.model import build
    cell = Catalog().cell("kimivl-cold")
    model = build(system.model_config(cell.config))
    harness._check_sizes(model, harness.sizes_of(cell.config))
    assert model.n_params() == 568_484_608
    assert cell.config["batch"] == 32
    assert Path(cell.reference.__file__) == BENCH_DIR / "reference" / \
        "kimi_vl.py"
    vith = {m.name for m in Catalog().cell("vith-cold").per_layer}
    assert {m.name for m in cell.per_layer} == \
        (vith - {"cache.hbm_hit_share"}) | {"feed.text_ms"}


def test_train_flops_per_sample_at_published_widths():
    """1,024 tokens of: MLA projections and causal attention in all 5
    layers, the 11,264-wide MLP in the first, router, shared experts and
    6 x 8 / 64 held-expert assignments in the other 4, and the head."""
    cell = Catalog().cell("kimivl-cold")
    proj = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    attn = 2 * proj + 1024 * 16 * (192 + 128)
    moe = 2 * (2048 * 64 + 3 * 2048 * 2816 + 3 * 2048 * 1408 * 0.75)
    per_token = 5 * attn + 2 * 3 * 2048 * 11264 + 4 * moe + 2 * 2048 * 20480
    got = cell.reference.train_flops_per_sample(cell.config["sizes"])
    assert got == pytest.approx(3 * 1024 * per_token, rel=1e-12)
    assert got == pytest.approx(1.774e12, rel=1e-3)


def test_text_reader_reads_the_span_per_batch_and_nothing_without_it():
    from benchlib.catalog import load_reader
    read = load_reader(BENCH_DIR / "metrics" / "feed.text_ms.py",
                       "feed.text_ms")
    run = SimpleNamespace(times_before={"batches": 2, "text": 0.5},
                          times_after={"batches": 6, "text": 0.9})
    assert read(run) == pytest.approx(100.0)
    run = SimpleNamespace(times_before={"batches": 2},
                          times_after={"batches": 6})
    assert read(run) is None
