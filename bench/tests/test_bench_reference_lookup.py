"""A configuration's reference and its FLOPs are found by the name the
configuration gives, and its sizes may name fields of a nested
configuration, so a new consumer architecture goes in as new files."""
import json
from pathlib import Path
from types import SimpleNamespace

import benchpath
import numpy as np
import pytest

from benchlib import harness, system
from benchlib.catalog import Catalog

DATA = Path(benchpath.DATA)
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
HP_KEYS = ("lr", "b1", "b2", "eps", "weight_decay", "grad_clip",
           "warmup_steps", "total_steps", "lr_floor")


def cell(name):
    return Catalog(DATA / "spec.json", dirs=[DATA]).cell(name)


def test_a_reference_only_the_tests_hold_runs_to_correct(monkeypatch):
    c = cell("tiny-recorded")
    assert Path(c.reference.__file__) == \
        DATA / "reference" / "vit_encoder_recorded.py"
    setup_ids = []
    real_setup = harness.setup

    def setup(*a, **kw):
        sys_, prog, batches = real_setup(*a, **kw)
        setup_ids.extend(b["ids"].tolist() for b in batches)
        return sys_, prog, batches
    monkeypatch.setattr(harness, "setup", setup)
    out = harness.run(c, 12345, 0.5, False, 0.0, DEVICE)
    assert out["correct"], out["checks"]
    sizes = c.config["sizes"]
    assert len(setup_ids) == harness.SETUP_STEPS
    assert c.reference.calls == [("train_flops_per_sample", sizes),
                                 ("check_steps", sizes, setup_ids)]


@pytest.mark.parametrize("reference", ["no_such_reference", None])
def test_missing_reference_fails_in_the_catalog_before_build(
        tmp_path, monkeypatch, reference):
    spec = json.loads((DATA / "spec.json").read_text())
    config = json.loads((DATA / "configs" / "vit-tiny.json").read_text())
    config["name"] = "vit-unchecked"
    if reference is None:
        del config["reference"]
    else:
        config["reference"] = reference
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "vit-unchecked.json").write_text(
        json.dumps(config))
    spec["workloads"].append({"name": "unchecked", "config": "vit-unchecked",
                              "traffic": "tiny-cold", "chips": 1,
                              "why": "test"})
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    built = []
    monkeypatch.setattr(system, "build", lambda *a, **kw: built.append(a))
    with pytest.raises(LookupError, match="'vit-unchecked'") as e:
        Catalog(tmp_path / "spec.json", dirs=[tmp_path, DATA]).cell(
            "unchecked")
    assert str(tmp_path) in str(e.value) and str(DATA) in str(e.value)
    assert built == []


def test_nested_override_replaces_only_the_keys_it_names():
    cfg = system.model_config({"model": {
        "base": "deepseek-moe-16b",
        "overrides": {"moe": {"n_experts": 8}, "n_layers": 5}}})
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.n_shared,
            cfg.moe.d_ff_expert) == (8, 6, 2, 1408)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (5, 2048, 102_400)


@pytest.mark.parametrize("sizes, ok", [
    ({"moe.n_experts": 8}, True),
    ({"moe.n_experts": 9}, False),
    ({"moe.top_k": 6, "n_layers": 28, "d_model": 2048}, True),
    ({"d_model": 1024}, False),
])
def test_check_sizes_reads_dotted_keys(sizes, ok):
    cfg = system.model_config({"model": {
        "base": "deepseek-moe-16b", "overrides": {"moe": {"n_experts": 8}}}})
    model = SimpleNamespace(cfg=cfg)
    if ok:
        harness._check_sizes(model, sizes)
    else:
        with pytest.raises(ValueError):
            harness._check_sizes(model, sizes)


def parent_inputs(config, traffic, key_seed, ref_rows, ids):
    """What ``harness.reference_steps`` handed ``vit_encoder.train_steps``
    before the reference was found by name, kept as it was."""
    from reference import synthetic_images as ref_data
    from reference import vit_encoder as ref
    sz = dict(config["sizes"])
    o = config["optimizer"]
    hp = {k: o[k] for k in HP_KEYS}
    batches = []
    for rows, sid in zip(ref_rows, ids):
        emb = ref.patch_embeds(rows.astype(np.float32), sz["frontend_tokens"],
                               sz["d_model"])
        labels = np.asarray(
            [ref_data.label(int(s), int(traffic["dataset"]["n_classes"]))
             % sz["n_classes"] for s in sid], np.int32)
        batches.append((emb, labels))
    return sz | {"norm_eps": config["sizes"]["norm_eps"]}, hp, key_seed, \
        batches


@pytest.mark.parametrize("kw", [{}, {"precision": "fp8"}, {"use_rows": 4}],
                         ids=["f32", "fp8", "half"])
@pytest.mark.parametrize("name", ["tiny-cold", "tiny-warm"])
def test_vit_encoder_inputs_are_the_parents_bit_for_bit(monkeypatch, name,
                                                         kw):
    c = cell(name)
    seeds = system.seeds(2**31 + 11)
    rng = np.random.default_rng(5)
    B, (h, w) = int(c.config["batch"]), c.traffic["dataset"]["crop_hw"]
    n = int(c.traffic["dataset"]["n_samples"])
    rows = [rng.random((B, h, w, 3)) for _ in range(harness.SETUP_STEPS)]
    ids = [rng.integers(0, n, B) for _ in range(harness.SETUP_STEPS)]
    got = {}

    def train_steps(sizes, hp, key_seed, batches, **kwargs):
        got.update(sizes=sizes, hp=hp, key_seed=key_seed, batches=batches,
                   kwargs=kwargs)
        return {"losses": []}
    monkeypatch.setattr(c.reference, "train_steps", train_steps)
    assert c.reference.check_steps(c.config, c.traffic, seeds, rows, ids,
                                   **kw) == {"losses": []}
    sizes, hp, key_seed, batches = parent_inputs(c.config, c.traffic,
                                                 seeds["params"], rows, ids)
    assert got["sizes"] == sizes and got["hp"] == hp
    assert got["key_seed"] == key_seed
    assert got["kwargs"] == {"precision": "f32", "use_rows": 0} | kw
    assert len(got["batches"]) == len(batches)
    for (emb, labels), (emb0, labels0) in zip(got["batches"], batches):
        assert emb.dtype == emb0.dtype and labels.dtype == labels0.dtype
        np.testing.assert_array_equal(np.asarray(emb).view(np.uint16),
                                      np.asarray(emb0).view(np.uint16))
        np.testing.assert_array_equal(labels, labels0)
