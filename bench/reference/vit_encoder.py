"""Plain reference of the ViT classifier the benchmark trains, and of its
AdamW step, in float32 at the highest matmul precision.

Imports nothing of the program under test.  It follows the
configuration file, which states the architecture as the program builds
it: pre-norm encoder blocks with RMS norms (eps 1e-5, a scale and no
bias), bidirectional multi-head attention without biases, a gated SiLU
MLP (``silu(x W_gate) * (x W_up) W_down``), a final RMS norm, a linear
head on token 0, and a mean cross-entropy over the batch.  Parameters
are stored in bfloat16 and drawn from the seed in the order and with the
scales the configuration gives (``init_params``); every other number is
float32.  The optimizer is AdamW with global-norm clipping, a linear
warm-up into a cosine decay, and decoupled weight decay on every stored
array of rank two or more.

``precision="fp8"`` computes every matmul on operands rounded to
float8 (e4m3 forward, e5m2 for the incoming gradient) with one scale
per tensor: the control that the comparison has to refuse.

The benchmark reaches this module through a configuration's
``"reference": "vit_encoder"`` and calls ``check_steps`` and
``train_flops_per_sample``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# float8 formats as (exponent bits, mantissa bits, largest finite value),
# rounded with ``lax.reduce_precision``: an explicit rounding that the
# compiler may not drop, as it may drop a float32 -> float8 -> float32
# round trip of converts when excess precision is allowed
E4M3 = (4, 3, 240.0)
E5M2 = (5, 2, 57344.0)


def leaf_specs(sizes: Dict) -> List[Tuple[str, Tuple[int, ...], str, float]]:
    """(name, shape, init, scale) of every parameter, in the order the
    seed's keys are handed out (the sorted nesting of the names)."""
    L, d, f = sizes["n_layers"], sizes["d_model"], sizes["d_ff"]
    T, C = sizes["frontend_tokens"], sizes["n_classes"]
    out_scale = 1.0 / math.sqrt(2 * L)
    return [
        ("blocks/attn/wk", (L, d, d), "normal", 1.0),
        ("blocks/attn/wo", (L, d, d), "normal", out_scale),
        ("blocks/attn/wq", (L, d, d), "normal", 1.0),
        ("blocks/attn/wv", (L, d, d), "normal", 1.0),
        ("blocks/ln1", (L, d), "ones", 1.0),
        ("blocks/ln2", (L, d), "ones", 1.0),
        ("blocks/mlp/wi_gate", (L, d, f), "normal", 1.0),
        ("blocks/mlp/wi_up", (L, d, f), "normal", 1.0),
        ("blocks/mlp/wo", (L, f, d), "normal", out_scale),
        ("final_norm", (d,), "ones", 1.0),
        ("head", (d, C), "normal", 1.0),
        ("pos_embed", (T, d), "embed", 1.0),
    ]


def _leaf(key, shape, init, scale):
    if init == "ones":
        return jnp.ones(shape, jnp.bfloat16)
    if init == "embed":
        return (jax.random.normal(key, shape, F32) * 0.02 * scale
                ).astype(jnp.bfloat16)
    std = scale / np.sqrt(shape[-2] if len(shape) >= 2 else shape[-1])
    return (jax.random.normal(key, shape, F32) * std).astype(jnp.bfloat16)


def init_params(sizes: Dict, key_seed: int) -> Dict[str, jax.Array]:
    specs = leaf_specs(sizes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(specs))
        return {name: _leaf(k, shape, init, scale)
                for k, (name, shape, init, scale) in zip(keys, specs)}
    return make(jax.random.key(key_seed))


# -- matmuls -------------------------------------------------------------

def _fake_quant(x, fmt):
    e, m, largest = fmt
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / largest, 1.0)
    return jax.lax.reduce_precision(x / scale, exponent_bits=e,
                                    mantissa_bits=m) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return jnp.einsum(spec, _fake_quant(a, E4M3),
                      _fake_quant(b, E4M3), precision=HIGHEST)


def _fp8_fwd(spec, a, b):
    return _fp8_einsum(spec, a, b), (a, b)


def _fp8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(
        lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
        _fake_quant(a, E4M3), _fake_quant(b, E4M3))
    return vjp(_fake_quant(g, E5M2))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def _einsum(precision: str):
    if precision == "fp8":
        return _fp8_einsum
    return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)


# -- the model -----------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def logits(p: Dict, emb: jax.Array, sizes: Dict, precision: str):
    """Class logits of ``emb`` (b, T, d) under float32 parameters ``p``."""
    mm = _einsum(precision)
    H, eps = sizes["n_heads"], sizes["norm_eps"]
    b, T, d = emb.shape
    hd = d // H
    x = emb.astype(F32) + p["pos_embed"]

    def layer(x, lp):
        h = _rms(x, lp["ln1"], eps)
        q = mm("btd,de->bte", h, lp["wq"]).reshape(b, T, H, hd)
        k = mm("btd,de->bte", h, lp["wk"]).reshape(b, T, H, hd)
        v = mm("btd,de->bte", h, lp["wv"]).reshape(b, T, H, hd)
        s = mm("bqhe,bkhe->bhqk", q, k) / math.sqrt(hd)
        a = mm("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), v)
        x = x + mm("bte,ed->btd", a.reshape(b, T, d), lp["wo"])
        h = _rms(x, lp["ln2"], eps)
        g = mm("btd,df->btf", h, lp["wi_gate"])
        u = mm("btd,df->btf", h, lp["wi_up"])
        x = x + mm("btf,fd->btd", jax.nn.silu(g) * u, lp["wo_mlp"])
        return x, None

    stacked = {"ln1": p["blocks/ln1"], "ln2": p["blocks/ln2"],
               "wq": p["blocks/attn/wq"], "wk": p["blocks/attn/wk"],
               "wv": p["blocks/attn/wv"], "wo": p["blocks/attn/wo"],
               "wi_gate": p["blocks/mlp/wi_gate"],
               "wi_up": p["blocks/mlp/wi_up"], "wo_mlp": p["blocks/mlp/wo"]}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)
    x = _rms(x, p["final_norm"], eps)
    return mm("bd,dc->bc", x[:, 0], p["head"])


def patch_embeds(images: np.ndarray, tokens: int, d_model: int) -> jax.Array:
    """The configuration's stand-in for a patch embedding: each image's
    flattened pixels, repeated to fill (tokens, d_model), in bfloat16."""
    B = images.shape[0]
    flat = np.asarray(images, np.float32).reshape(B, -1)
    reps = -(-tokens * d_model // flat.shape[1])
    emb = np.tile(flat, (1, reps))[:, :tokens * d_model]
    return jnp.asarray(emb.reshape(B, tokens, d_model), jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision",
                                             "total"))
def _block_grad(p, emb, labels, *, sizes_key, precision, total):
    sizes = dict(sizes_key)

    def loss(p):
        z = logits(p, emb, sizes, precision)
        nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, labels[:, None], -1)[:, 0]
        return jnp.sum(nll) / total
    return jax.value_and_grad(loss)(p)


def loss_and_grads(p: Dict, emb: jax.Array, labels: np.ndarray,
                   sizes: Dict, precision: str, use_rows: int = 0):
    """Mean loss and float32 gradients over the batch; ``use_rows`` > 0
    takes only the first ``use_rows`` rows, the mean over those alone.
    Each layer's activations are recomputed in the backward pass, so
    the whole batch fits at once."""
    B = use_rows or emb.shape[0]
    loss, grads = _block_grad(p, emb[:B], jnp.asarray(labels[:B]),
                              sizes_key=tuple(sorted(sizes.items())),
                              precision=precision, total=B)
    return float(loss), grads


@functools.partial(jax.jit, static_argnames=("hp_key",),
                   donate_argnums=(0, 2, 3))
def _adamw(p, g, m, v, step, *, hp_key):
    hp = dict(hp_key)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    s = step.astype(F32)
    warm = s / max(hp["warmup_steps"], 1)
    prog = jnp.clip((s - hp["warmup_steps"])
                    / max(hp["total_steps"] - hp["warmup_steps"], 1), 0, 1)
    cos = hp["lr_floor"] + (1 - hp["lr_floor"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    lr = hp["lr"] * jnp.where(s < hp["warmup_steps"], warm, cos)
    b1c = 1.0 - hp["b1"] ** s
    b2c = 1.0 - hp["b2"] ** s
    new_p, new_m, new_v, clipped = {}, {}, {}, {}
    for k in p:
        gk = g[k] * scale
        clipped[k] = jnp.sqrt(jnp.sum(gk * gk))
        mk = hp["b1"] * m[k] + (1 - hp["b1"]) * gk
        vk = hp["b2"] * v[k] + (1 - hp["b2"]) * gk * gk
        delta = (mk / b1c) / (jnp.sqrt(vk / b2c) + hp["eps"])
        if p[k].ndim >= 2:
            delta = delta + hp["weight_decay"] * p[k]
        # parameters are stored in bfloat16: round every update to it
        new_p[k] = jax.lax.reduce_precision(p[k] - lr * delta,
                                            exponent_bits=8, mantissa_bits=7)
        new_m[k], new_v[k] = mk, vk
    return new_p, new_m, new_v, clipped


@jax.jit
def _leaf_norms(t):
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for k, x in t.items()}


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))


def train_steps(sizes: Dict, hp: Dict, key_seed: int, batches, *,
                precision: str = "f32", use_rows: int = 0) -> Dict:
    """Run the first ``len(batches)`` AdamW steps from the seed's
    parameters over ``batches`` (each ``(embeds, labels)``).

    Returns the loss of each step, each leaf's norm of its first
    gradient before and after clipping, and each leaf's norm of its
    change over all the steps."""
    initial = init_params(sizes, key_seed)
    p = {k: x.astype(F32) for k, x in initial.items()}
    initial = jax.device_get(initial)
    m = v = None
    hp_key = tuple(sorted(hp.items()))
    losses, out = [], {}
    with jax.default_matmul_precision("highest"):
        for i, (emb, labels) in enumerate(batches):
            # the moments wait on the host while the gradients are taken,
            # so that a ViT-H step fits one chip's memory
            loss, g = loss_and_grads(p, emb, labels, sizes, precision,
                                     use_rows)
            losses.append(loss)
            if m is None:
                m = {k: jnp.zeros(x.shape, F32) for k, x in p.items()}
                v = {k: jnp.zeros(x.shape, F32) for k, x in p.items()}
            else:
                m, v = jax.device_put((m, v))
            if i == 0:
                out["grad_raw"] = {k: float(x)
                                   for k, x in _leaf_norms(g).items()}
            p, m, v, clipped = _adamw(
                p, g, m, v, jnp.asarray(i + 1, jnp.int32), hp_key=hp_key)
            del g
            if i + 1 < len(batches):
                m, v = jax.device_get((m, v))
            if i == 0:
                out["grad"] = {k: float(x) for k, x in clipped.items()}
    del m, v
    change = {k: float(_diff_norm(p[k], initial[k])) for k in p}
    out.update(losses=losses, change=change)
    return out


# -- what the benchmark calls ---------------------------------------------

def check_steps(config: Dict, traffic: Dict, seeds: Dict, rows, ids, *,
                precision: str = "f32", use_rows: int = 0) -> Dict:
    """The set-up steps of a run, from the seed's parameters, over the
    reference's rows (``rows``, one array a step) of the sample ``ids``:
    as ``train_steps`` returns them.  Each batch is the rows' patch
    embedding and each sample's label folded into the head's classes;
    the optimizer is the configuration's."""
    from reference import synthetic_images as ref_data
    sz = dict(config["sizes"])
    o = config["optimizer"]
    hp = {k: o[k] for k in ("lr", "b1", "b2", "eps", "weight_decay",
                            "grad_clip", "warmup_steps", "total_steps",
                            "lr_floor")}
    batches = []
    for r, sid in zip(rows, ids):
        emb = patch_embeds(r.astype(np.float32), sz["frontend_tokens"],
                           sz["d_model"])
        labels = np.asarray(
            [ref_data.label(int(s), int(traffic["dataset"]["n_classes"]))
             % sz["n_classes"] for s in sid], np.int32)
        batches.append((emb, labels))
    return train_steps(sz, hp, seeds["params"], batches,
                       precision=precision, use_rows=use_rows)


def train_flops_per_sample(sizes: Dict) -> float:
    """Model FLOPs of one training sample: forward plus backward (twice
    the forward), recomputation not counted.

    Per token and layer the forward multiplies by every matmul parameter
    once (4 d^2 for q, k, v, o and 3 d f for the gated MLP, 2 FLOPs each)
    and attends to all T tokens (2 T d for the scores, 2 T d for the
    weighted sum; the encoder is not causal).  The head runs on token 0
    only (2 d C)."""
    L, d, f = sizes["n_layers"], sizes["d_model"], sizes["d_ff"]
    T, C = sizes["frontend_tokens"], sizes["n_classes"]
    per_token_layer = 2 * (4 * d * d + 3 * d * f) + 4 * T * d
    forward = T * L * per_token_layer + 2 * d * C
    return 3.0 * forward
