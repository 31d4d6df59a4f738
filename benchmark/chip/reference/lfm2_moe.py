"""Plain reference of LFM2-MoE (Liquid AI; `model_type` `lfm2_moe`,
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json):
forward pass, next-token loss, gradients, Adam, and the weights made
from a seed.

Straightforward `jax.numpy`, float32, every product at precision
"highest", no kernels, no sorting: the expert layer is a loop over the
experts held with a mask. It imports nothing of paddle_tpu and takes
nothing the program made; the harness hands the program the weights
`make_params` makes from the seed, and this file makes them again for
itself. (The helpers that round a product's operands for the controls
are transformer2017.py's.)

A layer is `h = x + Mixer(RMSNorm(x)); y = h + FF(RMSNorm(h))`; after
the last layer one more RMSNorm, then the output head. `layer_types`
gives the mixers ("conv": gated short convolution; "attention":
grouped-query attention with RMSNorm on q and k and rotary positions);
the first `n_dense_layers` layers have a gated feed-forward, the others
a routed expert layer: `s = sigmoid(x W_g)`, the `top_k` experts with
the largest `s + b` are chosen, their weights are `s / (sum of the
chosen s + 1e-6)` times `routed_scaling`, and the output is the
weighted sum of the chosen experts' `W2(silu(W1 x) * W3 x)`.

Departures from the published description, each because the program
under test does the same and the two have to compute one function:
  * the share of one rank of an expert-parallel job: each expert layer
    holds experts `first_held .. first_held + experts_held` of
    `n_experts`, routes over all of them and returns the held experts'
    part of the result; what the absent experts would add is left out
    and the partial result goes on to the next layer. `experts_held` =
    `n_experts` is the published layer;
  * the vocabulary is the rank's slice: ids, logits and loss are over
    `vocab` rows;
  * the output head is the embedding table (tied), the family's
    convention; the catalog row does not say;
  * the router's bias `b` is a fixed buffer drawn from the seed (the
    family updates it outside the gradient to balance the load); there
    is no auxiliary loss;
  * the loss is the mean over every position of a batch of whole
    sequences, no document boundaries, no padding;
  * attention is computed a block of queries at a time (every block
    against all keys up to its end), and each layer is recomputed in
    the backward pass, so that 8,192 positions fit in float32; neither
    changes a value;
  * Adam's bias correction is folded into the step size, epsilon
    outside the root, at a constant rate, as transformer2017.py has it.

`fault` plants what the controls need (benchmark/chip/controls_lfm2.py)
and is never set for the reference itself: "experts_left_out" (the
expert layers return nothing), "softmax_router" (softmax scores, no
bias), "conv_looks_ahead" (the convolution's window ends one position
after the present).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .transformer2017 import PRECISIONS, _mm, _rounded, _store, _to_bf16

FAULTS = (None, "experts_left_out", "softmax_router", "conv_looks_ahead")
PERIOD = ("attention", "conv", "conv", "conv")
ATTENTION_BLOCK = 1024


def layer_types(cfg):
    """The published pattern: leading dense layers are "conv", then
    attention, conv, conv, conv repeated."""
    if cfg.get("layer_types"):
        return list(cfg["layer_types"])
    dense = cfg["n_dense_layers"]
    return ["conv"] * dense + [PERIOD[i % 4]
                               for i in range(cfg["n_layers"] - dense)]


# ---------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------
def layer_shapes(cfg, i):
    """name -> (shape, kind) of layer i; kind is "matrix" (normal, 1 /
    fan-in: the last axis but one), "one", "filter", "router" or
    "bias"."""
    d = cfg["d_model"]
    head = d // cfg["n_heads"]
    out = {f"l{i}.norm1.g": ((d,), "one"), f"l{i}.norm2.g": ((d,), "one")}
    if layer_types(cfg)[i] == "conv":
        out[f"l{i}.conv.w_in"] = ((d, 3 * d), "matrix")
        out[f"l{i}.conv.k"] = ((d, cfg["conv_taps"]), "filter")
        out[f"l{i}.conv.w_out"] = ((d, d), "matrix")
    else:
        kv = cfg["n_kv_heads"] * head
        out[f"l{i}.attn.wq"] = ((d, d), "matrix")
        out[f"l{i}.attn.wk"] = ((d, kv), "matrix")
        out[f"l{i}.attn.wv"] = ((d, kv), "matrix")
        out[f"l{i}.attn.wo"] = ((d, d), "matrix")
        out[f"l{i}.attn.qnorm.g"] = ((head,), "one")
        out[f"l{i}.attn.knorm.g"] = ((head,), "one")
    if i < cfg["n_dense_layers"]:
        f = cfg["d_dense"]
        out[f"l{i}.ff.w1"] = ((d, f), "matrix")
        out[f"l{i}.ff.w3"] = ((d, f), "matrix")
        out[f"l{i}.ff.w2"] = ((f, d), "matrix")
    else:
        f, n = cfg["d_expert"], cfg["experts_held"]
        out[f"l{i}.moe.wg"] = ((d, cfg["n_experts"]), "router")
        out[f"l{i}.moe.b"] = ((cfg["n_experts"],), "bias")
        out[f"l{i}.moe.w1"] = ((n, d, f), "matrix")
        out[f"l{i}.moe.w3"] = ((n, d, f), "matrix")
        out[f"l{i}.moe.w2"] = ((n, f, d), "matrix")
    return out


def top_shapes(cfg):
    d = cfg["d_model"]
    return {"emb": ((cfg["vocab"], d), "embedding"),
            "out_norm.g": ((d,), "one")}


def buffers(cfg):
    """Leaves that are not trained: the routers' biases."""
    return {f"l{i}.moe.b" for i in range(cfg["n_dense_layers"],
                                         cfg["n_layers"])}


def _init(key, shapes, cfg):
    out = {}
    for j, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, j)
        if kind == "one":
            out[name] = jnp.ones(shape, jnp.float32)
            continue
        if kind == "bias":
            scale = cfg.get("bias_scale", 0.02)
        elif kind == "router":
            scale = shape[0] ** -0.5 * cfg.get("router_gain", 1.0)
        else:   # fan-in: rows of a matrix, width of a table, taps
            scale = shape[-2 if kind == "matrix" else -1] ** -0.5
        out[name] = jax.random.normal(k, shape, jnp.float32) * scale
    return out


def _freeze(cfg):
    keys = ("d_model", "n_heads", "n_kv_heads", "n_layers",
            "n_dense_layers", "d_dense", "d_expert", "n_experts",
            "experts_held", "vocab", "conv_taps")
    return tuple((k, cfg[k]) for k in keys) + (
        ("layer_types", tuple(layer_types(cfg))),
        ("router_gain", cfg.get("router_gain", 1.0)),
        ("bias_scale", cfg.get("bias_scale", 0.02)))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _make_part(lo, hi, frozen, part):
    cfg = dict(frozen)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    shapes = top_shapes(cfg) if part < 0 else layer_shapes(cfg, part)
    return _init(jax.random.fold_in(key, part + 1), shapes, cfg)


def seed_words(seed):
    """The seed as a traced pair of 31-bit words, so that every seed
    shares one compiled program."""
    seed = int(seed)
    return jnp.uint32(seed % (1 << 31)), jnp.uint32(seed >> 31)


def make_part(lo, hi, cfg, part):
    """The weights of layer `part`, or of the embedding and the last
    norm for part -1, on the default device in one jitted call."""
    return _make_part(lo, hi, _freeze(cfg), part)


def make_params(seed, cfg):
    """Every weight, a layer at a time."""
    lo, hi = seed_words(seed)
    out = {}
    for part in range(-1, cfg["n_layers"]):
        out.update(make_part(lo, hi, cfg, part))
    return out


# ---------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------
def rms_norm(x, g, eps, precision):
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return _store(x * inv * g, precision)


def rotary(x, theta):
    """x [B, T, H, D]: x * cos + rotate_half(x) * sin, the default
    rotary embedding with frequencies theta^(-2i/D)."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                / d))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angle) + rot * jnp.sin(angle)


def conv_mixer(p, pre, u, cfg, precision, fault=None):
    d, taps = cfg["d_model"], cfg["conv_taps"]
    bcz = _mm("btd,de->bte", u, p[f"{pre}.conv.w_in"], precision)
    b, c, z = bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:]
    v = b * z
    t = v.shape[1]
    ahead = 1 if fault == "conv_looks_ahead" else 0
    padded = jnp.pad(v, ((0, 0), (taps - 1 - ahead, ahead), (0, 0)))
    k = p[f"{pre}.conv.k"]
    conv = sum(padded[:, j:j + t] * k[:, j] for j in range(taps))
    return _mm("btd,de->bte", _store(c * conv, precision),
               p[f"{pre}.conv.w_out"], precision)


def _attend_block(q, k, v, start, scale, precision):
    """Queries start .. start + len(q) against keys 0 .. start +
    len(q), causal."""
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) * scale
    keep = (start + jnp.arange(q.shape[1]))[:, None] \
        >= jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(keep[None, None], scores, -1e30)
    probs = _store(jax.nn.softmax(scores, axis=-1), precision)
    return _mm("bhqk,bkhd->bqhd", probs, v, precision)


def attention_mixer(p, pre, u, cfg, precision):
    b, t, d = u.shape
    h, hkv = cfg["n_heads"], cfg["n_kv_heads"]
    head = d // h
    q = _mm("btd,de->bte", u, p[f"{pre}.attn.wq"], precision)
    k = _mm("btd,de->bte", u, p[f"{pre}.attn.wk"], precision)
    v = _mm("btd,de->bte", u, p[f"{pre}.attn.wv"], precision)
    q = q.reshape(b, t, h, head)
    k = k.reshape(b, t, hkv, head)
    v = v.reshape(b, t, hkv, head)
    eps = cfg["norm_eps"]
    q = rotary(rms_norm(q, p[f"{pre}.attn.qnorm.g"], eps, precision),
               cfg["rope_theta"])
    k = rotary(rms_norm(k, p[f"{pre}.attn.knorm.g"], eps, precision),
               cfg["rope_theta"])
    # a key-value head serves h / hkv query heads
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    block = min(t, cfg.get("attention_block", ATTENTION_BLOCK))
    one = jax.checkpoint(functools.partial(
        _attend_block, scale=head ** -0.5, precision=precision),
        static_argnums=(3,))
    ctx = jnp.concatenate(
        [one(q[:, s:s + block], k[:, :s + block], v[:, :s + block], s)
         for s in range(0, t, block)], axis=1)
    return _mm("btd,de->bte", _store(ctx, precision).reshape(b, t, d),
               p[f"{pre}.attn.wo"], precision)


def _gated(x, w1, w3, w2, precision):
    h = _store(jax.nn.silu(_mm("nd,df->nf", x, w1, precision))
               * _mm("nd,df->nf", x, w3, precision), precision)
    return _mm("nf,fd->nd", h, w2, precision)


def dense_ff(p, pre, u, precision):
    b, t, d = u.shape
    return _gated(u.reshape(b * t, d), p[f"{pre}.ff.w1"],
                  p[f"{pre}.ff.w3"], p[f"{pre}.ff.w2"],
                  precision).reshape(b, t, d)


def route(p, pre, x, cfg, precision, fault=None):
    """(chosen experts [N, k], their weights [N, k]) of tokens x
    [N, D]."""
    logits = _mm("nd,de->ne", x, p[f"{pre}.moe.wg"], precision)
    if fault == "softmax_router":
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, cfg["top_k"])
    else:
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(p[f"{pre}.moe.b"]),
            cfg["top_k"])
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk", True):
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    return chosen, weight * cfg.get("routed_scaling", 1.0)


def moe_ff(p, pre, u, cfg, precision, fault=None):
    """The held experts' part of the expert layer, and the experts each
    token chose. A loop over the experts held; each computes every
    token and a mask keeps the tokens that chose it."""
    b, t, d = u.shape
    x = u.reshape(b * t, d)
    chosen, weight = route(p, pre, x, cfg, precision, fault)
    out = jnp.zeros_like(x)
    if fault != "experts_left_out":
        for e in range(cfg["experts_held"]):
            mine = jnp.where(chosen == cfg.get("first_held", 0) + e,
                             weight, 0.0).sum(-1)
            out = out + mine[:, None] * _gated(
                x, p[f"{pre}.moe.w1"][e], p[f"{pre}.moe.w3"][e],
                p[f"{pre}.moe.w2"][e], precision)
    return _store(out, precision).reshape(b, t, d), chosen


def layer(p, x, i, cfg, precision, fault=None):
    """Layer i on the residual stream x [B, T, D]; returns (y, chosen
    experts or None)."""
    pre, eps = f"l{i}", cfg["norm_eps"]
    u = rms_norm(x, p[f"{pre}.norm1.g"], eps, precision)
    if layer_types(cfg)[i] == "conv":
        mixed = conv_mixer(p, pre, u, cfg, precision, fault)
    else:
        mixed = attention_mixer(p, pre, u, cfg, precision)
    h = _store(x + mixed, precision)
    u = rms_norm(h, p[f"{pre}.norm2.g"], eps, precision)
    if i < cfg["n_dense_layers"]:
        ff, chosen = dense_ff(p, pre, u, precision), None
    else:
        ff, chosen = moe_ff(p, pre, u, cfg, precision, fault)
    return _store(h + ff, precision), chosen


def forward(p, ids, cfg, precision="highest", fault=None):
    """(logits [B, T, vocab], {layer: chosen experts [B*T, k]}); each
    layer is recomputed in a backward pass."""
    if precision == "bf16":
        p = {k: _rounded(v, _to_bf16) for k, v in p.items()}
    x = _store(p["emb"][ids], precision)
    chosen = {}
    for i in range(cfg["n_layers"]):
        names = layer_shapes(cfg, i)
        x, c = jax.checkpoint(
            lambda pl, x_, i=i: layer(pl, x_, i, cfg, precision, fault))(
            {n: p[n] for n in names}, x)
        if c is not None:
            chosen[i] = c
    x = rms_norm(x, p["out_norm.g"], cfg["norm_eps"], precision)
    return _mm("btd,vd->btv", x, p["emb"], precision), chosen


def loss_mean(p, ids, label, cfg, precision="highest", fault=None):
    """Mean next-token cross-entropy over every position, and the
    chosen experts."""
    logits, chosen = forward(p, ids, cfg, precision, fault)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, label[..., None], -1)[..., 0]
    return jnp.mean(lse - picked), chosen


# ---------------------------------------------------------------------
# training steps
# ---------------------------------------------------------------------
_MODEL_KEYS = ("d_model", "n_heads", "n_kv_heads", "n_layers",
               "n_dense_layers", "d_dense", "d_expert", "n_experts",
               "top_k", "experts_held", "first_held", "vocab",
               "conv_taps", "rope_theta", "norm_eps", "norm_topk",
               "routed_scaling", "attention_block")


def _freeze_model(cfg):
    return tuple((k, cfg[k]) for k in _MODEL_KEYS if k in cfg) + (
        ("layer_types", tuple(layer_types(cfg))),)


@functools.lru_cache(maxsize=None)
def _grad_fn(frozen, precision, fault):
    cfg = dict(frozen)
    return jax.jit(jax.value_and_grad(
        lambda p, ids, label: loss_mean(p, ids, label, cfg, precision,
                                        fault), has_aux=True))


def loss_and_grads(p, batch, cfg, precision="highest", fault=None):
    """(loss, gradient by leaf, chosen experts by layer) of one batch
    {"ids", "label"}. The buffers get no gradient."""
    (loss, chosen), g = _grad_fn(_freeze_model(cfg), precision, fault)(
        p, batch["ids"], batch["label"])
    for name in buffers(cfg):
        g.pop(name)
    return loss, g, chosen


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam_apply(p, g, m, v, lr_t, b1, b2, eps):
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    p = jax.tree.map(
        lambda p_, m_, v_: p_ - lr_t * m_ / (jnp.sqrt(v_) + eps),
        p, m, v)
    return p, m, v


def _leaf_norms(tree):
    return {k: float(jnp.linalg.norm(v)) for k, v in tree.items()}


def train_steps(p0, batches, cfg, precision="highest", fault=None):
    """Follow the first len(batches) steps from weights `p0`, which
    are given up (the update is made in place). Returns the losses, the
    first step's gradient norm by leaf, the first step's chosen experts
    by layer, and the weights after the last step."""
    frozen = buffers(cfg)
    fixed = {k: p0[k] for k in frozen}
    p = {k: v for k, v in p0.items() if k not in frozen}
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    b1, b2 = cfg["adam_beta1"], cfg["adam_beta2"]
    losses, grad_norms, chosen = [], None, None
    for step, batch in enumerate(batches, start=1):
        loss, g, c = loss_and_grads({**p, **fixed}, batch, cfg, precision,
                                    fault)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms, chosen = _leaf_norms(g), c
        lr_t = cfg["learning_rate"] * math.sqrt(1 - b2 ** step) \
            / (1 - b1 ** step)
        p, m, v = _adam_apply(p, g, m, v, jnp.float32(lr_t),
                              jnp.float32(b1), jnp.float32(b2),
                              jnp.float32(cfg["adam_eps"]))
        del g
    return losses, grad_norms, chosen, {**p, **fixed}


__all__ = ["PRECISIONS", "FAULTS", "make_params", "make_part", "seed_words",
           "forward", "loss_mean", "loss_and_grads", "train_steps",
           "layer_types", "buffers"]
