"""Plain reference of Kimi-VL-A3B's language model as the benchmark
trains it, and of its AdamW step, in float32 at the highest matmul
precision.

Imports nothing of the program under test.  It follows the
configuration file (``sizes``), which states the chip's share of the
model (arXiv:2504.07491; the Hugging Face config.json of
moonshotai/Kimi-VL-A3B-Instruct):

* a sequence is ``frontend_tokens`` image embeddings (each image's
  flattened pixels, repeated to fill them, in bfloat16) followed by
  ``text_tokens`` caption ids; the caption of a sample is Zipf(1) over
  the vocabulary, a pure function of the data seed, the sample id and
  the position (``captions``); labels are the next caption id on text
  positions and nothing on image positions;
* pre-norm blocks with RMS norms (a scale, no bias).  Attention is
  multi-head latent attention, causal: per head q is ``qk_nope_head_dim``
  dims without position and ``qk_rope_head_dim`` rotary ones (rotate-half
  convention, theta ``rope_theta``); ``x W_kv_a`` gives a latent of
  ``kv_lora_rank``, RMS-normed and mapped by ``W_kv_b`` to each head's
  unrotated key and value, and one rotary key shared by the heads;
  scores are scaled by 1/sqrt(qk head dim);
* the first ``first_dense_layers`` blocks have a gated SiLU MLP of
  ``d_ff``; the rest are mixtures of experts: a router scores all
  ``moe.n_experts`` experts as ``sigmoid(x W_r)``, selects the top
  ``moe.top_k`` by score plus a correction bias (zero), and weights
  each selection by its score normalized over the k and times
  ``moe.routed_scaling``.  Only experts [0, ``moe.n_held``) exist (the
  chip's share); each is computed here on every token and weighted by
  that token's gate for it, zero where it was not selected.  Two shared
  experts are one gated MLP of ``moe.n_shared * moe.d_ff_expert``;
* the balance loss is DeepSeek-V3's sequence-wise one
  (arXiv:2412.19437 §2.1.2) over all experts, times
  ``moe.aux_loss_weight``, summed over the MoE layers;
* the loss is the mean cross-entropy over the labelled positions, over
  the vocabulary of ``vocab_size`` ids, plus the balance losses.

Parameters are stored in bfloat16 (the correction bias in float32) and
drawn from the seed in the program's leaf order and names
(``leaf_specs``); every other number is float32.  The batch is computed
in blocks of ``BLOCK_ROWS`` sequences, their gradients summed, so that
the set-up steps fit one chip.  The optimizer, the float8 control and
the leaf norms are ``vit_encoder``'s.

The benchmark reaches this module through a configuration's
``"reference": "kimi_vl"`` and calls ``check_steps`` and
``train_flops_per_sample``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference import vit_encoder as common

F32 = jnp.float32
BLOCK_ROWS = 4


def leaf_specs(sizes: Dict) -> List[Tuple[str, Tuple[int, ...], str, float]]:
    """(name, shape, init, scale) of every parameter, in the order the
    seed's keys are handed out (the sorted nesting of the names)."""
    L, k = sizes["n_layers"], sizes["first_dense_layers"]
    d, H, V = sizes["d_model"], sizes["n_heads"], sizes["vocab_size"]
    r = sizes["mla.kv_lora_rank"]
    qk = sizes["mla.qk_nope_head_dim"] + sizes["mla.qk_rope_head_dim"]
    vd, nope = sizes["mla.v_head_dim"], sizes["mla.qk_nope_head_dim"]
    E, held = sizes["moe.n_experts"], sizes["moe.n_held"]
    f, fs = sizes["moe.d_ff_expert"], sizes["moe.n_shared"] * \
        sizes["moe.d_ff_expert"]
    out_scale = 1.0 / math.sqrt(2 * L)
    if V % 2048:
        raise ValueError(f"vocab_size {V} is not a multiple of 2048: the "
                         f"program would pad its embedding and head")

    def attn(n):
        return [(("attn", "kv_norm"), (n, r), "ones", 1.0),
                (("attn", "wkv_a"), (n, d, r + sizes["mla.qk_rope_head_dim"]),
                 "normal", 1.0),
                (("attn", "wkv_b"), (n, r, H * (nope + vd)), "normal", 1.0),
                (("attn", "wo"), (n, H * vd, d), "normal", out_scale),
                (("attn", "wq"), (n, d, H * qk), "normal", 1.0),
                (("ln1",), (n, d), "ones", 1.0),
                (("ln2",), (n, d), "ones", 1.0)]

    n = L - k
    specs = [(("blocks",) + p, s, i, c) for p, s, i, c in attn(n) + [
        (("moe", "router"), (n, d, E), "normal", 0.1),
        (("moe", "router_bias"), (n, E), "zeros", 1.0),
        (("moe", "we_gate"), (n, held, d, f), "normal", 1.0),
        (("moe", "we_out"), (n, held, f, d), "normal", out_scale),
        (("moe", "we_up"), (n, held, d, f), "normal", 1.0),
        (("moe", "ws_gate"), (n, d, fs), "normal", 1.0),
        (("moe", "ws_out"), (n, fs, d), "normal", out_scale),
        (("moe", "ws_up"), (n, d, fs), "normal", 1.0)]]
    specs += [(("dense",) + p, s, i, c) for p, s, i, c in attn(k) + [
        (("mlp", "wi_gate"), (k, d, sizes["d_ff"]), "normal", 1.0),
        (("mlp", "wi_up"), (k, d, sizes["d_ff"]), "normal", 1.0),
        (("mlp", "wo"), (k, sizes["d_ff"], d), "normal", out_scale)]]
    specs += [(("embed", "head"), (d, V), "normal", 1.0),
              (("embed", "tok"), (V, d), "embed", 1.0),
              (("final_norm",), (d,), "ones", 1.0)]
    return [("/".join(p), s, i, c) for p, s, i, c in sorted(specs)]


def _leaf(key, shape, init, scale):
    if init == "zeros":
        return jnp.zeros(shape, F32)
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


# -- the data --------------------------------------------------------------

_GOLDEN, _M1, _M2 = 0x9E3779B97F4A7C15, 0xBF58476D1CE4E5B9, 0x94D049BB133111EB


def _splitmix(z: np.ndarray) -> np.ndarray:
    z = z + np.uint64(_GOLDEN)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(_M1)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(_M2)
    return z ^ (z >> np.uint64(31))


def captions(data_seed: int, ids, length: int, vocab: int) -> np.ndarray:
    """(len(ids), length) caption ids: for each sample and position, a
    64-bit splitmix hash of (seed, id, position), its upper 32 bits
    modulo the total weight of Zipf(1) over ``vocab`` ranks (rank j
    weighs floor(2**24 / (j + 1))), and the rank whose cumulative
    weight first exceeds that."""
    cum = np.cumsum((1 << 24) // np.arange(1, vocab + 1, dtype=np.int64))
    seed = _splitmix(np.full(1, data_seed, np.uint64))
    sample = _splitmix(seed ^ np.asarray(ids, np.int64).astype(np.uint64))
    z = _splitmix(sample[:, None] + np.arange(length, dtype=np.uint64))
    r = (z >> np.uint64(32)).astype(np.int64) % cum[-1]
    return np.searchsorted(cum, r, side="right").astype(np.int32)


def image_embeds(images: np.ndarray, tokens: int, d_model: int
                 ) -> np.ndarray:
    """Each image's flattened pixels, repeated to fill (tokens,
    d_model), rounded to bfloat16 and held as float32."""
    B = images.shape[0]
    flat = np.asarray(images, np.float32).reshape(B, -1)
    reps = -(-tokens * d_model // flat.shape[1])
    emb = np.tile(flat, (1, reps))[:, :tokens * d_model]
    return np.asarray(jnp.asarray(emb.reshape(B, tokens, d_model),
                                  jnp.bfloat16).astype(F32))


# -- the model -------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary embedding of x (b, T, h, e) at positions 0..T-1: the first
    and second halves of the last axis are the pairs."""
    T, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(lp, x, sz, mm):
    b, T, d = x.shape
    H, eps = sz["n_heads"], sz["norm_eps"]
    nope, rope = sz["mla.qk_nope_head_dim"], sz["mla.qk_rope_head_dim"]
    r, vd = sz["mla.kv_lora_rank"], sz["mla.v_head_dim"]
    q = mm("btd,de->bte", x, lp["wq"]).reshape(b, T, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                               sz["rope_theta"])], -1)
    kv = mm("btd,de->bte", x, lp["wkv_a"])
    latent = _rms(kv[..., :r], lp["kv_norm"], eps)
    k_rope = _rope(kv[:, :, None, r:], sz["rope_theta"])
    kvb = mm("btr,re->bte", latent, lp["wkv_b"]).reshape(b, T, H, nope + vd)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_rope, (b, T, H, rope))], -1)
    v = kvb[..., nope:]
    s = mm("bqhe,bkhe->bhqk", q, k) / math.sqrt(nope + rope)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = mm("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), v)
    return mm("bte,ed->btd", a.reshape(b, T, H * vd), lp["wo"])


def _gated(x, wg, wu, wo, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, wg))
              * mm("td,df->tf", x, wu), wo)


def _experts(lp, x, sz, mm):
    """(routed and shared experts' output, the sequence-wise balance
    loss summed over the block's sequences)."""
    b, T, d = x.shape
    E, k = sz["moe.n_experts"], sz["moe.top_k"]
    x2 = x.reshape(b * T, d)
    scores = jax.nn.sigmoid(mm("td,de->te", x2, lp["router"]))
    _, top = jax.lax.top_k(scores + lp["router_bias"], k)
    picked = jnp.take_along_axis(scores, top, -1)
    weight = picked / jnp.sum(picked, -1, keepdims=True) \
        * sz["moe.routed_scaling"]
    out = _gated(x2, lp["ws_gate"], lp["ws_up"], lp["ws_out"], mm)
    for e in range(sz["moe.n_held"]):
        gate = jnp.sum(jnp.where(top == e, weight, 0.0), -1)
        out = out + gate[:, None] * _gated(
            x2, lp["we_gate"][e], lp["we_up"][e], lp["we_out"][e], mm)
    chosen = jnp.sum(jax.nn.one_hot(top, E, dtype=F32), 1).reshape(b, T, E)
    f = jnp.sum(chosen, 1) * E / (k * T)
    p = jnp.mean((scores / jnp.sum(scores, -1, keepdims=True)
                  ).reshape(b, T, E), 1)
    return out.reshape(b, T, d), jnp.sum(f * p)


def _block(lp, x, sz, mm, moe):
    eps = sz["norm_eps"]
    x = x + _attention(lp["attn"], _rms(x, lp["ln1"], eps), sz, mm)
    h = _rms(x, lp["ln2"], eps)
    if moe:
        y, balance = _experts(lp["moe"], h, sz, mm)
    else:
        b, T, d = h.shape
        m = lp["mlp"]
        y = _gated(h.reshape(b * T, d), m["wi_gate"], m["wi_up"], m["wo"],
                   mm).reshape(b, T, d)
        balance = jnp.zeros((), F32)
    return x + y, balance


def _stack(p: Dict, prefix: str) -> Dict:
    out: Dict = {}
    for name, x in p.items():
        if name.startswith(prefix + "/"):
            node = out
            *path, leaf = name[len(prefix) + 1:].split("/")
            for key in path:
                node = node.setdefault(key, {})
            node[leaf] = x
    return out


def block_loss(p: Dict, emb, tokens, labels, sz: Dict, precision: str,
               n_labels: int, n_rows: int):
    """This block's share of the batch loss: its cross-entropy summed
    over labelled positions over ``n_labels``, plus the balance loss of
    its sequences over ``n_rows``, times the weight."""
    mm = common._einsum(precision)
    x = jnp.concatenate([emb, p["embed/tok"][tokens]], 1)
    balance = jnp.zeros((), F32)
    for prefix, moe in (("dense", False), ("blocks", True)):
        def layer(carry, lp, moe=moe):
            x, bal = carry
            x, b = _block(lp, x, sz, mm, moe)
            return (x, bal + b), None
        (x, balance), _ = jax.lax.scan(jax.checkpoint(layer), (x, balance),
                                       _stack(p, prefix))
    x = _rms(x, p["final_norm"], sz["norm_eps"])
    z = mm("btd,dv->btv", x, p["embed/head"])
    keep = labels >= 0
    nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
        z, jnp.where(keep, labels, 0)[..., None], -1)[..., 0]
    return (jnp.sum(jnp.where(keep, nll, 0.0)) / n_labels
            + sz["moe.aux_loss_weight"] * balance / n_rows)


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision",
                                             "n_labels", "n_rows"),
                   donate_argnums=(0,))
def _block_grad(acc, p, emb, tokens, labels, *, sizes_key, precision,
                n_labels, n_rows):
    loss, g = jax.value_and_grad(block_loss)(
        p, emb, tokens, labels, dict(sizes_key), precision, n_labels, n_rows)
    return loss, jax.tree.map(jnp.add, acc, g)


def loss_and_grads(p: Dict, batch, sizes: Dict, precision: str,
                   use_rows: int = 0):
    """Mean loss and float32 gradients over the batch (``(embeds,
    tokens, labels)``), in blocks of ``BLOCK_ROWS`` sequences;
    ``use_rows`` > 0 takes only the first ``use_rows`` sequences, the
    mean over those alone."""
    emb, tokens, labels = batch
    B = use_rows or emb.shape[0]
    n_labels = int(np.sum(labels[:B] >= 0))
    key = tuple(sorted(sizes.items()))
    grads = jax.tree.map(jnp.zeros_like, p)
    loss = 0.0
    for i in range(0, B, BLOCK_ROWS):
        j = min(i + BLOCK_ROWS, B)
        part, grads = _block_grad(
            grads, p, jnp.asarray(emb[i:j]), jnp.asarray(tokens[i:j]),
            jnp.asarray(labels[i:j]), sizes_key=key, precision=precision,
            n_labels=n_labels, n_rows=B)
        loss += float(part)
    return loss, grads


def train_steps(sizes: Dict, hp: Dict, key_seed: int, batches, *,
                precision: str = "f32", use_rows: int = 0) -> Dict:
    """Run the first ``len(batches)`` AdamW steps from the seed's
    parameters over ``batches`` (each ``(embeds, tokens, labels)``).

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
        for i, batch in enumerate(batches):
            # the moments wait on the host while the gradients are taken
            loss, g = loss_and_grads(p, batch, sizes, precision, use_rows)
            losses.append(loss)
            if m is None:
                m = {k: jnp.zeros(x.shape, F32) for k, x in p.items()}
                v = {k: jnp.zeros(x.shape, F32) for k, x in p.items()}
            else:
                m, v = jax.device_put((m, v))
            if i == 0:
                out["grad_raw"] = {k: float(x) for k, x in
                                   common._leaf_norms(g).items()}
            p, m, v, clipped = common._adamw(
                p, g, m, v, jnp.asarray(i + 1, jnp.int32), hp_key=hp_key)
            del g
            if i + 1 < len(batches):
                m, v = jax.device_get((m, v))
            if i == 0:
                out["grad"] = {k: float(x) for k, x in clipped.items()}
    del m, v
    change = {k: float(common._diff_norm(p[k], initial[k])) for k in p}
    out.update(losses=losses, change=change)
    return out


# -- what the benchmark calls ---------------------------------------------

def inputs(sizes: Dict, data_seed: int, rows: np.ndarray, ids) -> Tuple:
    """(embeds, tokens, labels) of one batch: the rows' image
    embeddings, then each sample's caption and next-token labels."""
    P, n = sizes["frontend_tokens"], sizes["text_tokens"]
    text = captions(data_seed, ids, n + 1, sizes["vocab_size"])
    labels = np.concatenate([np.full((len(text), P), -1, np.int32),
                             text[:, 1:]], 1)
    return (image_embeds(rows, P, sizes["d_model"]), text[:, :-1], labels)


def check_steps(config: Dict, traffic: Dict, seeds: Dict, rows, ids, *,
                precision: str = "f32", use_rows: int = 0) -> Dict:
    """The set-up steps of a run, from the seed's parameters, over the
    reference's rows (``rows``, one array a step) of the sample ``ids``
    and their captions: as ``train_steps`` returns them.  The optimizer
    is the configuration's."""
    sz = dict(config["sizes"])
    o = config["optimizer"]
    hp = {k: o[k] for k in ("lr", "b1", "b2", "eps", "weight_decay",
                            "grad_clip", "warmup_steps", "total_steps",
                            "lr_floor")}
    batches = [inputs(sz, seeds["data"], r, sid) for r, sid in zip(rows, ids)]
    return train_steps(sz, hp, seeds["params"], batches,
                       precision=precision, use_rows=use_rows)


def train_flops_per_sample(sizes: Dict) -> float:
    """Model FLOPs of one training sample: forward plus backward (twice
    the forward), recomputation not counted.

    Per token the forward multiplies by every matmul parameter it uses
    once (2 FLOPs each): in every layer the latent-attention projections;
    in the dense layers the gated MLP; in the MoE layers the router, the
    shared experts and the held experts at the tokens they are expected
    to see if routing were uniform, ``top_k * n_held / n_experts``
    assignments a token; and the output head at every position.
    Attention is causal, so it counts half the score and value products
    of a full sequence: T * H * (qk head dim + v head dim) a token."""
    L, k = sizes["n_layers"], sizes["first_dense_layers"]
    d, H = sizes["d_model"], sizes["n_heads"]
    T = sizes["frontend_tokens"] + sizes["text_tokens"]
    r, vd = sizes["mla.kv_lora_rank"], sizes["mla.v_head_dim"]
    nope, rope = sizes["mla.qk_nope_head_dim"], sizes["mla.qk_rope_head_dim"]
    E, f = sizes["moe.n_experts"], sizes["moe.d_ff_expert"]
    proj = d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd) \
        + H * vd * d
    attn = 2 * proj + T * H * (nope + rope + vd)
    dense = 2 * 3 * d * sizes["d_ff"]
    moe = 2 * (d * E + 3 * d * f * sizes["moe.n_shared"]
               + 3 * d * f * sizes["moe.top_k"] * sizes["moe.n_held"] / E)
    forward = T * (L * attn + k * dense + (L - k) * moe
                   + 2 * d * sizes["vocab_size"])
    return 3.0 * forward
