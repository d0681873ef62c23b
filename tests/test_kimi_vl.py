"""Kimi-VL-A3B's language model at a small size on the CPU: latent
attention, the sigmoid router, the layer that holds a share of the
experts, the leading dense layer, the step's counters and the feed of
images with captions."""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import TRAIN_4K, ParallelismConfig
from repro.data.synthetic import caption_ids, tiny
from repro.models import layers as lyr
from repro.models import moe as moe_mod
from repro.models.model import build, make_batch
from repro.models.params import init_params
from repro.train.optimizer import AdamW
from repro.train.step import build_train_step

ARCH = "kimi-vl-a3b"


def small(**moe):
    cfg = registry.get_reduced(ARCH)
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe))


def f32_init(defs, seed):
    return init_params(jax.random.key(seed), defs, jnp.float32)


# -- latent attention ------------------------------------------------------

def _rope_pairs(x, theta):
    """Naive rotary embedding of x (S, e), position t, pair (i, i + e/2)."""
    S, e = x.shape
    half = e // 2
    out = np.empty_like(x)
    for t in range(S):
        for i in range(half):
            a = t * theta ** (-i / half)
            c, s = math.cos(a), math.sin(a)
            out[t, i] = x[t, i] * c - x[t, i + half] * s
            out[t, i + half] = x[t, i + half] * c + x[t, i] * s
    return out


def test_mla_matches_a_naive_per_head_loop():
    cfg = registry.get_reduced(ARCH)
    a, H, d = cfg.mla, cfg.n_heads, cfg.d_model
    nope, rope, r, vd = (a.qk_nope_head_dim, a.qk_rope_head_dim,
                         a.kv_lora_rank, a.v_head_dim)
    p = f32_init(lyr.mla_defs(cfg), 1)
    p["kv_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.key(2), (r,))
    B, S = 2, 7
    x = jax.random.normal(jax.random.key(3), (B, S, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(lyr.mla_attention(p, x, cfg,
                                           positions=jnp.arange(S)))

    P = {k: np.asarray(v, np.float64) for k, v in p.items()}
    X = np.asarray(x, np.float64)
    want = np.zeros((B, S, d))
    for b in range(B):
        kv = X[b] @ P["wkv_a"]
        lat = kv[:, :r]
        lat = lat / np.sqrt(np.mean(lat * lat, -1, keepdims=True)
                            + cfg.norm_eps) * P["kv_norm"]
        k_rope = _rope_pairs(kv[:, r:], cfg.rope_theta)
        heads = []
        for h in range(H):
            q = X[b] @ P["wq"][:, h * (nope + rope):(h + 1) * (nope + rope)]
            q = np.concatenate([q[:, :nope],
                                _rope_pairs(q[:, nope:], cfg.rope_theta)], 1)
            kvb = lat @ P["wkv_b"][:, h * (nope + vd):(h + 1) * (nope + vd)]
            k = np.concatenate([kvb[:, :nope], k_rope], 1)
            v = kvb[:, nope:]
            o = np.zeros((S, vd))
            for t in range(S):
                s = np.array([q[t] @ k[u] for u in range(t + 1)])
                s = s / math.sqrt(nope + rope)
                w = np.exp(s - s.max())
                o[t] = (w / w.sum()) @ v[:t + 1]
            heads.append(o)
        want[b] = np.concatenate(heads, 1) @ P["wo"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# -- the router and the held experts ----------------------------------------

def test_sigmoid_router_selects_by_biased_score_weights_by_unbiased():
    T, d, E, k, scale = 64, 16, 12, 3, 2.446
    x = jax.random.normal(jax.random.key(0), (T, d), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (d, E), jnp.float32)
    bias = jnp.linspace(-0.4, 0.4, E)
    with jax.default_matmul_precision("highest"):
        top_e, top_w, scores = moe_mod._route_sigmoid(x, w, bias, k, scale)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(w, np.float64))))
    np.testing.assert_allclose(np.asarray(scores), s, rtol=1e-5)
    want = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :k]
    np.testing.assert_array_equal(np.sort(np.asarray(top_e), 1),
                                  np.sort(want, 1))
    picked = np.take_along_axis(s, np.asarray(top_e), 1)
    np.testing.assert_allclose(np.asarray(top_w),
                               picked / picked.sum(1, keepdims=True) * scale,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(top_w).sum(1), scale, rtol=1e-5)
    # the bias moves the selection and not the weights: without it the
    # last experts are chosen less often
    plain = np.argsort(-s, axis=1)[:, :k]
    assert (want >= E - 3).sum() > (plain >= E - 3).sum()


def _dense_held(x, top_e, top_w, e_start, wg, wu, wo):
    """Each held expert on every token, weighted by its gate there."""
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    for j in range(wg.shape[0]):
        gate = np.where(np.asarray(top_e) == e_start + j,
                        np.asarray(top_w), 0).sum(1)
        g = x @ np.asarray(wg[j], np.float64)
        u = x @ np.asarray(wu[j], np.float64)
        h = g / (1 + np.exp(-g)) * u
        out += gate[:, None] * (h @ np.asarray(wo[j], np.float64))
    return out


@pytest.mark.parametrize("favoured", ["all-held", "one-expert"])
def test_held_experts_drop_no_assignment_under_skewed_routing(favoured):
    """Every token routed to held experts (the most rows the layer can
    get), or to one expert: each assignment is computed."""
    cfg = small(n_experts=16, top_k=4, n_held=4)
    e = cfg.moe
    p = f32_init(moe_mod.moe_defs(cfg), 5)
    T = 96
    x = jax.random.normal(jax.random.key(6), (T, cfg.d_model), jnp.float32)
    bias = jnp.zeros(e.n_experts).at[:4].set(50.0) if favoured == \
        "all-held" else jnp.zeros(e.n_experts).at[2].set(50.0)
    with jax.default_matmul_precision("highest"):
        top_e, top_w, _ = moe_mod._route_sigmoid(x, p["router"], bias,
                                                 e.top_k, e.routed_scaling)
        got, sizes = moe_mod._held_experts(x, top_e, top_w, 0, p["we_gate"],
                                           p["we_up"], p["we_out"])
    if favoured == "all-held":
        assert np.asarray(sizes).tolist() == [T] * 4
    else:
        assert int(sizes[2]) == T
    assert int(np.sum(sizes)) == int(np.sum(np.asarray(top_e) < 4))
    want = _dense_held(x, top_e, top_w, 0, p["we_gate"], p["we_up"],
                       p["we_out"])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def _unwritten_past_the_groups(monkeypatch):
    """``jax.lax.ragged_dot`` as the chip runs it: rows outside every
    group are left unwritten (NaN here), forward and backward."""
    real = jax.lax.ragged_dot

    def spoil(x, n):
        return jnp.where((jnp.arange(x.shape[0]) >= n)[:, None], jnp.nan, x)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, group_sizes):
        return spoil(real(lhs, rhs, group_sizes), jnp.sum(group_sizes))

    def fwd(lhs, rhs, group_sizes):
        return ragged_dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, group_sizes = res
        _, pull = jax.vjp(lambda a, b: real(a, b, group_sizes), lhs, rhs)
        d_lhs, d_rhs = pull(g)
        return spoil(d_lhs, jnp.sum(group_sizes)), d_rhs, None

    ragged_dot.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)


def test_held_experts_ignore_rows_past_the_groups(monkeypatch):
    """Grouped matmuls that leave rows outside their groups unwritten:
    the output and its gradients are the dense computation's."""
    _unwritten_past_the_groups(monkeypatch)
    cfg = small(n_experts=16, top_k=4, n_held=4)
    e = cfg.moe
    p = f32_init(moe_mod.moe_defs(cfg), 5)
    T = 96
    x = jax.random.normal(jax.random.key(6), (T, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        top_e, top_w, _ = moe_mod._route_sigmoid(
            x, p["router"], p["router_bias"], e.top_k, e.routed_scaling)
        assert 0 < int(np.sum(np.asarray(top_e) < 4)) < T * e.top_k // 2

        def program(x, wg, wu, wo):
            return moe_mod._held_experts(x, top_e, top_w, 0, wg, wu, wo)[0]

        def dense(x, wg, wu, wo):
            gate = jnp.stack([jnp.sum(jnp.where(top_e == j, top_w, 0), 1)
                              for j in range(4)], 1)
            h = jax.nn.silu(jnp.einsum("td,jdf->tjf", x, wg)) \
                * jnp.einsum("td,jdf->tjf", x, wu)
            return jnp.einsum("tjf,jfd,tj->td", h, wo, gate)

        args = (x, p["we_gate"], p["we_up"], p["we_out"])
        np.testing.assert_allclose(np.asarray(jax.jit(program)(*args)),
                                   np.asarray(dense(*args)),
                                   rtol=1e-4, atol=1e-5)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(program(*a) ** 2),
                                 argnums=(0, 1, 2, 3)))(*args)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) ** 2),
                        argnums=(0, 1, 2, 3))(*args)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def _routing_with_held(n, T, k, n_experts, n_held, seed):
    """top_e (T, k), k distinct experts a token, exactly n of the T * k
    assignments on experts [0, n_held); top_w (T, k) positive."""
    rng = np.random.default_rng(seed)
    held = np.zeros(T * k, bool)
    held[rng.choice(T * k, n, replace=False)] = True
    held = held.reshape(T, k)
    top_e = np.empty((T, k), np.int32)
    for t in range(T):
        top_e[t, held[t]] = rng.permutation(n_held)[:held[t].sum()]
        top_e[t, ~held[t]] = n_held + rng.permutation(
            n_experts - n_held)[:(~held[t]).sum()]
    top_w = rng.uniform(0.1, 1.0, (T, k)).astype(np.float32)
    return jnp.asarray(top_e), jnp.asarray(top_w)


def _dense_layer(x, top_e, top_w, wg, wu, wo):
    """The held experts [0, G) on every token, weighted by their gates."""
    gate = jnp.stack([jnp.sum(jnp.where(top_e == j, top_w, 0), 1)
                      for j in range(wg.shape[0])], 1)
    h = jax.nn.silu(jnp.einsum("td,jdf->tjf", x, wg)) \
        * jnp.einsum("td,jdf->tjf", x, wu)
    return jnp.einsum("tjf,jfd,tj->td", h, wo, gate)


# 4 held of 16 experts, T * k = 48 * 4 = 192 rows in chunks of 16
CHUNK = 16


@pytest.mark.parametrize("n", [0, CHUNK - 5, CHUNK, 2 * CHUNK + 1, 192],
                         ids=["none", "below-a-chunk", "one-chunk",
                              "chunks-and-one", "every-row"])
def test_chunked_held_experts_match_the_dense_layer(monkeypatch, n):
    """The loop over chunks of sorted rows, at every kind of held count
    and with rows past the groups left unwritten: the output and the
    gradients for the rows, the three expert weights and the routing
    weights are the dense computation's."""
    _unwritten_past_the_groups(monkeypatch)
    cfg = small(n_experts=16, top_k=4, n_held=4)
    p = f32_init(moe_mod.moe_defs(cfg), 5)
    T = 48
    x = jax.random.normal(jax.random.key(6), (T, cfg.d_model), jnp.float32)
    top_e, top_w = _routing_with_held(n, T, 4, 16, 4, seed=n)

    def program(x, top_w, wg, wu, wo):
        return moe_mod._held_experts(x, top_e, top_w, 0, wg, wu, wo,
                                     chunk=CHUNK)

    def dense(x, top_w, wg, wu, wo):
        return _dense_layer(x, top_e, top_w, wg, wu, wo)

    args = (x, top_w, p["we_gate"], p["we_up"], p["we_out"])
    with jax.default_matmul_precision("highest"):
        got, sizes = jax.jit(program)(*args)
        assert int(np.sum(sizes)) == n
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense(*args)),
                                   rtol=1e-4, atol=1e-5)
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(program(*a)[0] ** 2),
            argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) ** 2),
                        argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def test_chunked_held_experts_train_under_scan_and_checkpoint(monkeypatch):
    """Layers scanned under ``checkpoint``, as the step runs them, with
    each layer's routing a traced input: every gradient is the dense
    layers'."""
    _unwritten_past_the_groups(monkeypatch)
    cfg = small(n_experts=16, top_k=4, n_held=4)
    T, L = 48, 3
    stack = jax.tree.map(lambda *a: jnp.stack(a), *[
        f32_init(moe_mod.moe_defs(cfg), 20 + i) for i in range(L)])
    routes = [_routing_with_held(n, T, 4, 16, 4, seed=n)
              for n in (CHUNK + 3, 0, 150)]
    top_e = jnp.stack([r[0] for r in routes])
    top_w = jnp.stack([r[1] for r in routes])
    x = jax.random.normal(jax.random.key(13), (T, cfg.d_model), jnp.float32)

    def run(layer):
        def loss(x, top_w, wg, wu, wo):
            def body(h, xs):
                te, tw, g, u, o = xs
                return h + layer(h, te, tw, g, u, o), None
            h, _ = jax.lax.scan(jax.checkpoint(body), x,
                                (top_e, top_w, wg, wu, wo))
            return jnp.sum(h ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
            x, top_w, stack["we_gate"], stack["we_up"], stack["we_out"])

    with jax.default_matmul_precision("highest"):
        got = run(lambda h, te, tw, g, u, o: moe_mod._held_experts(
            h, te, tw, 0, g, u, o, chunk=CHUNK)[0])
        want = run(_dense_layer)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight chips each hold 2 of 16 experts: their parts of the layer,
    with the shared experts counted once, add up to the uncut layer."""
    uncut = small(n_experts=16, top_k=4, n_held=0)
    share = small(n_experts=16, top_k=4, n_held=2)
    p = f32_init(moe_mod.moe_defs(uncut), 7)
    p["router_bias"] = 0.02 * jax.random.normal(jax.random.key(9), (16,))
    B, S = 2, 24
    x = jax.random.normal(jax.random.key(8), (B, S, uncut.d_model),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, whole_aux = moe_mod.moe_ffn(p, x, uncut)
        first = {k: (v[:2] if k.startswith("we_") else v)
                 for k, v in p.items()}
        total, aux = moe_mod.moe_ffn(first, x, share)
        x2d = x.reshape(B * S, -1)
        top_e, top_w, _ = moe_mod._route_sigmoid(
            x2d, p["router"], p["router_bias"], 4, share.moe.routed_scaling)
        for r in range(1, 8):
            part, sizes = moe_mod._held_experts(
                x2d, top_e, top_w, 2 * r, p["we_gate"][2 * r:2 * r + 2],
                p["we_up"][2 * r:2 * r + 2], p["we_out"][2 * r:2 * r + 2])
            assert int(np.sum(sizes)) > 0
            total = total + part.reshape(B, S, -1)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    # the router and its balance loss are the whole layer's on every share
    assert float(aux["loss"]) == pytest.approx(float(whole_aux["loss"]),
                                               rel=1e-6)
    assert float(whole_aux["held"]) == 1.0
    assert 0.0 < float(aux["held"]) < 1.0


# -- the model -------------------------------------------------------------

def test_n_params_agrees_with_the_model():
    full = registry.get(ARCH)
    cut = dataclasses.replace(full, n_layers=5, vocab_size=20480,
                              moe=dataclasses.replace(full.moe, n_held=8))
    for cfg in (full, cut):
        assert cfg.n_params() == build(cfg).n_params()
    assert build(cut).n_params() == 568_484_608
    # published widths in the registry
    assert (full.d_model, full.n_heads, full.d_ff, full.n_layers,
            full.vocab_size, full.first_dense_layers) == \
        (2048, 16, 11264, 27, 163840, 1)
    assert (full.moe.n_experts, full.moe.top_k, full.moe.n_shared,
            full.moe.d_ff_expert, full.moe.router,
            full.moe.routed_scaling) == (64, 6, 2, 1408, "sigmoid", 2.446)
    assert (full.mla.kv_lora_rank, full.mla.qk_nope_head_dim,
            full.mla.qk_rope_head_dim, full.mla.v_head_dim) == \
        (512, 128, 64, 128)


def test_leading_dense_layer_then_moe_layers():
    cfg = registry.get_reduced(ARCH)
    shapes = jax.tree.map(lambda a: a.shape, build(cfg).abstract())
    assert shapes["dense"]["mlp"]["wi_gate"] == (1, cfg.d_model, cfg.d_ff)
    assert "moe" not in shapes["dense"]
    assert shapes["blocks"]["moe"]["we_gate"] == \
        (cfg.n_layers - 1, cfg.moe.n_held, cfg.d_model, cfg.moe.d_ff_expert)
    assert shapes["blocks"]["moe"]["router"][-1] == cfg.moe.n_experts
    assert shapes["blocks"]["attn"]["wkv_b"][0] == cfg.n_layers - 1


def test_a_held_share_needs_the_sigmoid_router():
    """The softmax router dispatches over every expert, so a held share
    is refused where the config is made, not in a shape error later."""
    moe = registry.get("deepseek-moe-16b").moe
    with pytest.raises(ValueError, match="sigmoid"):
        dataclasses.replace(moe, n_held=4)
    with pytest.raises(ValueError, match="n_experts"):
        small(n_held=17)
    with pytest.raises(ValueError, match="router"):
        dataclasses.replace(moe, router="softmax2")


def test_dry_run_lays_it_out_for_training_only():
    """The multi-pod dry-run takes the arch, with experts over 'model',
    and only for the training shape: latent attention has no cache."""
    from repro.configs.base import ALL_SHAPES, shape_applicable
    assert ARCH in registry.ASSIGNED_ARCHS
    cfg = registry.get(ARCH)
    assert registry.default_parallelism(cfg, TRAIN_4K).ep
    ok = {s.name for s in ALL_SHAPES if shape_applicable(cfg, s)[0]}
    assert ok == {"train_4k"}
    assert len(registry.cells()) == 4 * len(registry.ASSIGNED_ARCHS)


def test_latent_attention_has_no_decode_cache():
    m = build(registry.get_reduced(ARCH))
    with pytest.raises(NotImplementedError):
        m.init_cache(batch=2, s_max=16)


def _step_metrics(arch):
    cfg = registry.get_reduced(arch)
    m = build(cfg)
    params = m.init(jax.random.key(0))
    opt = AdamW(lr=1e-3)
    batch = make_batch(jax.random.key(1), m, TRAIN_4K, reduced_shape=(2, 32))
    step = jax.jit(build_train_step(m, ParallelismConfig(remat="full"), opt))
    return step(params, opt.init(params), batch)[2]


@pytest.mark.parametrize("arch, counted", [
    (ARCH, True), ("deepseek-moe-16b", True), ("vit-huge", False),
    ("qwen3-8b", False)])
def test_step_counts_routing_for_moe_configs_only(arch, counted):
    metrics = _step_metrics(arch)
    keys = {"moe_held_share", "moe_load_max_over_mean"}
    assert keys <= set(metrics) if counted else not keys & set(metrics)
    if counted:
        held = float(metrics["moe_held_share"])
        assert (0.0 < held < 1.0) if arch == ARCH else held == 1.0
        assert float(metrics["moe_load_max_over_mean"]) >= 1.0


@pytest.mark.parametrize("arch, n_held", [
    (ARCH, 4), (ARCH, 0), ("deepseek-moe-16b", 0), ("vit-huge", 0),
    ("qwen3-8b", 0)])
def test_step_counts_the_rows_moved(arch, n_held):
    """``moe_rows_share``: absent without MoE, 1 where a layer holds
    every expert, else the held share rounded up to whole chunks."""
    cfg = registry.get_reduced(arch)
    if cfg.moe is not None and cfg.moe.router == "sigmoid":
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, n_held=n_held))
    m = build(cfg)
    params = m.init(jax.random.key(0))
    B, S = 4, 256
    batch = make_batch(jax.random.key(1), m, TRAIN_4K, reduced_shape=(B, S))
    metrics = jax.jit(m.loss_and_stats)(params, batch)[1]
    if cfg.moe is None:
        assert "moe_rows_share" not in metrics
        return
    rows = float(metrics["moe_rows_share"])
    held = float(metrics["moe_held_share"])
    e = cfg.moe
    if e.held == e.n_experts:
        assert rows == 1.0 and held == 1.0
        return
    C = moe_mod._chunk_rows(B * S * e.top_k, e.held, e.n_experts)
    assert C < B * S * e.top_k * held       # more than one chunk a layer
    assert held <= rows < held + C / (B * S * e.top_k) + 1e-6, (held, rows)
    # whole chunks: the layers' trips add up to a whole number
    trips = rows * (cfg.n_layers - cfg.first_dense_layers) \
        * B * S * e.top_k / C
    assert abs(trips - round(trips)) < 1e-3, trips


def test_step_carries_the_named_scopes():
    cfg = registry.get_reduced(ARCH)
    m = build(cfg)
    opt = AdamW()
    params = m.abstract()
    batch = jax.eval_shape(lambda: make_batch(
        jax.random.key(1), m, TRAIN_4K, reduced_shape=(2, 32)))
    text = jax.jit(build_train_step(m, ParallelismConfig(), opt)).lower(
        params, jax.eval_shape(opt.init, params), batch).as_text(
            debug_info=True)
    found = set(re.findall(r"[/\"](mla|moe\.route|moe\.experts|moe\.shared)/",
                           text))
    assert found == {"mla", "moe.route", "moe.experts", "moe.shared"}


# -- the feed --------------------------------------------------------------

def test_caption_ids_are_a_pure_function_of_seed_and_id():
    ids = np.array([3, 99, 1_281_166, 3])
    a = caption_ids(1234, ids, 40, 20480)
    assert a.shape == (4, 40) and a.dtype == np.int32
    np.testing.assert_array_equal(a, caption_ids(1234, ids, 40, 20480))
    np.testing.assert_array_equal(a[0], a[3])
    np.testing.assert_array_equal(a[1, :10],
                                  caption_ids(1234, ids[1:2], 10, 20480)[0])
    assert not np.array_equal(a, caption_ids(1235, ids, 40, 20480))
    assert 0 <= a.min() and a.max() < 20480
    # Zipf(1): rank 0 takes 1/H(20480) of the draws, about 9.5 %
    big = caption_ids(7, np.arange(200), 1000, 20480)
    assert np.mean(big == 0) == pytest.approx(0.0952, abs=0.005)
    assert np.mean(big == 1) == pytest.approx(0.0476, abs=0.004)


def test_vlm_feed_is_images_then_captions_with_next_token_labels():
    from repro.launch.train import image_batch_source
    cfg = registry.get_reduced(ARCH)
    ds = tiny(n=64)
    raws = []
    source, pipe, server = image_batch_source(
        build(cfg), 4, dataset=ds, executor="device",
        consume_hook=raws.append)
    try:
        batch = source()
    finally:
        pipe.stop()
        server.close()
    P, n = cfg.frontend_tokens, cfg.text_tokens
    assert batch["patch_embeds"].shape == (4, P, cfg.d_model)
    text = caption_ids(ds.seed, raws[0]["ids"], n + 1, cfg.vocab_size)
    np.testing.assert_array_equal(np.asarray(batch["tokens"]), text[:, :-1])
    labels = np.asarray(batch["labels"])
    assert labels.shape == (4, P + n)
    assert (labels[:, :P] == -1).all()
    np.testing.assert_array_equal(labels[:, P:], text[:, 1:])
    assert pipe.times.text > 0.0


def test_train_driver_feeds_the_vlm_from_seneca():
    from repro.launch import train
    out = train.run(train.parse_args([
        "--arch", ARCH, "--steps", "3", "--batch", "4", "--samples", "64",
        "--executor", "device", "--ckpt-every", "0"]))
    assert len(out["history"]) == 3
    assert all(np.isfinite(h["loss"]) for h in out["history"])
    assert out["stage_s"]["batches"] >= 3 and out["stage_s"]["text"] > 0.0
    assert 0.0 < out["history"][0]["moe_held_share"] < 1.0
