"""Plain reference of the 2017 Transformer ("Attention Is All You Need",
arXiv:1706.03762): encoder, decoder, label-smoothed loss, gradients,
Adam under the Noam schedule, and the weights made from a seed.

Straightforward `jax.numpy`, float32, matmul precision "highest", no
kernels, no cache, no batching tricks. It imports nothing of
paddle_tpu and takes nothing the program made: the harness hands the
program the weights that `init_params` makes from the seed, and this
file makes them again for itself.

Departures from the paper, each because the program under test does
the same and the two have to compute one function:
  * sinusoidal positions interleave sin (even columns) and cos (odd),
    as tensor2tensor does, where the paper's text concatenates;
  * attention projections carry no bias, feed-forward layers do;
  * source and target embeddings and the output projection are three
    separate tables (the paper shares them);
  * Adam's bias correction is folded into the step size
    (lr_t = lr * sqrt(1 - b2^t) / (1 - b1^t)), epsilon outside the
    root, as Kingma & Ba's section 2 "more efficient" form has it.

`precision` selects how every matrix product rounds its operands. The
reference itself is "highest". The lower ones exist for the controls
of benchmark/chip/compare.py (the step a later PR would be tempted
by), never for the reference:
  "highest"  float32 operands, full precision
  "bf16_ops" operands rounded to bfloat16, float32 accumulation
             (what a default-precision float32 matmul on the TPU does)
  "bf16"     bfloat16 weights and activations throughout
  "fp8"      operands scaled per tensor and rounded to 4 exponent and 3
             mantissa bits, float32 accumulation
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
PRECISIONS = ("highest", "bf16_ops", "bf16", "fp8")


# ---------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------
def param_shapes(cfg):
    """name -> (shape, kind); kind is "matrix", "embedding", "one" or
    "zero". Layer names: enc{i}.* and dec{i}.*."""
    d, f, v = cfg["d_model"], cfg["d_inner"], cfg["vocab"]
    shapes = {"src_emb": ((v, d), "embedding"),
              "tgt_emb": ((v, d), "embedding"),
              "out_proj": ((d, v), "matrix")}

    def attn(p):
        for w in ("wq", "wk", "wv", "wo"):
            shapes[f"{p}.{w}"] = ((d, d), "matrix")

    def norm(p):
        shapes[f"{p}.g"] = ((d,), "one")
        shapes[f"{p}.b"] = ((d,), "zero")

    def ffn(p):
        shapes[f"{p}.w1"] = ((d, f), "matrix")
        shapes[f"{p}.b1"] = ((f,), "zero")
        shapes[f"{p}.w2"] = ((f, d), "matrix")
        shapes[f"{p}.b2"] = ((d,), "zero")

    for i in range(cfg["n_layers"]):
        attn(f"enc{i}.self")
        norm(f"enc{i}.ln1")
        ffn(f"enc{i}.ffn")
        norm(f"enc{i}.ln2")
        attn(f"dec{i}.self")
        norm(f"dec{i}.ln1")
        attn(f"dec{i}.cross")
        norm(f"dec{i}.ln2")
        ffn(f"dec{i}.ffn")
        norm(f"dec{i}.ln3")
    return shapes


def init_params(key, cfg):
    """Every weight from the key: Glorot-uniform matrices, N(0, 1/d)
    embeddings, unit gains, zero biases. Two optional keys shape what a
    model of random weights says, for the cells that compare served
    tokens: `init_gain` ({"wo": 0.25}) scales the matrices of that
    name, and the output column of every id in `silent_ids` is zero,
    so that this id is never the model's first choice."""
    gains = dict(cfg.get("init_gain", ()))
    out = {}
    for i, (name, (shape, kind)) in enumerate(
            sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if kind == "matrix":
            lim = math.sqrt(6.0 / (shape[0] + shape[1]))
            out[name] = jax.random.uniform(k, shape, jnp.float32,
                                           -lim, lim)
            gain = gains.get(name.rsplit(".", 1)[-1])
            if gain is not None:
                out[name] = out[name] * gain
        elif kind == "embedding":
            out[name] = jax.random.normal(k, shape, jnp.float32) \
                * (shape[1] ** -0.5)
        elif kind == "one":
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = jnp.zeros(shape, jnp.float32)
    for i in cfg.get("silent_ids", ()):
        out["out_proj"] = out["out_proj"].at[:, i].set(0.0)
    return out


def make_params(seed, cfg):
    """The weights on the default device, in one jitted call. The seed
    is a traced pair of 31-bit words, so every seed shares one compiled
    program."""
    seed = int(seed)
    return _make_params(jnp.uint32(seed % (1 << 31)),
                        jnp.uint32(seed >> 31), _freeze(cfg))


def _freeze(cfg):
    return tuple(sorted((k, cfg[k]) for k in
                        ("d_model", "d_inner", "vocab", "n_layers"))) + (
        ("init_gain", tuple(sorted(dict(
            cfg.get("init_gain", ())).items()))),
        ("silent_ids", tuple(cfg.get("silent_ids", ()))))


@functools.partial(jax.jit, static_argnums=2)
def _make_params(lo, hi, frozen):
    return init_params(jax.random.fold_in(jax.random.PRNGKey(lo), hi),
                       dict(frozen))


# ---------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------
def _rounded(x, to):
    """`x` rounded by `to` in the forward pass; the backward pass sees
    the identity (the rounding of a gradient is the matrix product's
    business, not the cast's)."""
    return x + jax.lax.stop_gradient(to(x) - x)


def _to_bf16(x):
    # reduce_precision, not a pair of casts: XLA removes a cast to
    # bfloat16 and back as excess precision it may keep
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _to_fp8(x):
    """e4m3 with one scale a tensor: the largest magnitude lands on
    240, the largest finite value of 4 exponent and 3 mantissa bits."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _round_operand(x, precision):
    x = x.astype(jnp.float32)
    if precision == "highest":
        return x
    if precision in ("bf16_ops", "bf16"):
        return _rounded(x, _to_bf16)
    if precision == "fp8":
        return _rounded(x, _to_fp8)
    raise ValueError(f"unknown precision {precision!r}; one of "
                     f"{PRECISIONS}")


def _mm(spec, a, b, precision):
    """einsum with the operands rounded as `precision` says; the
    product itself always runs float32 at "highest", so the only
    rounding is the one named."""
    out = jnp.einsum(spec, _round_operand(a, precision),
                     _round_operand(b, precision),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return _store(out, precision)


def _store(x, precision):
    """What an activation is kept in between operations."""
    return _rounded(x, _to_bf16) if precision == "bf16" else x


def positions(length, d_model):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)
    angle = pos / jnp.power(10000.0, dim / d_model)
    table = jnp.zeros((length, d_model), jnp.float32)
    table = table.at[:, 0::2].set(jnp.sin(angle))
    return table.at[:, 1::2].set(jnp.cos(angle))


def layer_norm(x, g, b, precision):
    mean = x.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return _store((x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b,
                  precision)


def attention(p, prefix, x_q, x_kv, n_heads, causal, precision):
    b, tq, d = x_q.shape
    tk, dh = x_kv.shape[1], d // n_heads
    q = _mm("btd,de->bte", x_q, p[f"{prefix}.wq"], precision)
    k = _mm("btd,de->bte", x_kv, p[f"{prefix}.wk"], precision)
    v = _mm("btd,de->bte", x_kv, p[f"{prefix}.wv"], precision)
    q = q.reshape(b, tq, n_heads, dh)
    k = k.reshape(b, tk, n_heads, dh)
    v = v.reshape(b, tk, n_heads, dh)
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) * (dh ** -0.5)
    if causal:
        keep = jnp.tril(jnp.ones((tq, tk), bool))
        scores = jnp.where(keep[None, None], scores, -1e30)
    probs = _store(jax.nn.softmax(scores, axis=-1), precision)
    ctx = _mm("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, tq, d)
    return _mm("btd,de->bte", ctx, p[f"{prefix}.wo"], precision)


def ffn(p, prefix, x, precision):
    h = _mm("btd,df->btf", x, p[f"{prefix}.w1"], precision) \
        + p[f"{prefix}.b1"]
    h = _store(jax.nn.relu(h), precision)
    return _store(_mm("btf,fd->btd", h, p[f"{prefix}.w2"], precision)
                  + p[f"{prefix}.b2"], precision)


def embed(table, ids, precision):
    d = table.shape[1]
    x = table[ids] * (d ** 0.5) + positions(ids.shape[1], d)[None]
    return _store(x, precision)


def encode(p, src, cfg, precision="highest"):
    h = cfg["n_heads"]
    x = embed(p["src_emb"], src, precision)
    for i in range(cfg["n_layers"]):
        a = attention(p, f"enc{i}.self", x, x, h, False, precision)
        x = layer_norm(x + a, p[f"enc{i}.ln1.g"], p[f"enc{i}.ln1.b"],
                       precision)
        f = ffn(p, f"enc{i}.ffn", x, precision)
        x = layer_norm(x + f, p[f"enc{i}.ln2.g"], p[f"enc{i}.ln2.b"],
                       precision)
    return x


def decode(p, enc, tgt, cfg, precision="highest"):
    """Teacher-forced decoder: logits [B, T, V] for target inputs
    `tgt` (position 0 holds the start token)."""
    h = cfg["n_heads"]
    x = embed(p["tgt_emb"], tgt, precision)
    for i in range(cfg["n_layers"]):
        a = attention(p, f"dec{i}.self", x, x, h, True, precision)
        x = layer_norm(x + a, p[f"dec{i}.ln1.g"], p[f"dec{i}.ln1.b"],
                       precision)
        c = attention(p, f"dec{i}.cross", x, enc, h, False, precision)
        x = layer_norm(x + c, p[f"dec{i}.ln2.g"], p[f"dec{i}.ln2.b"],
                       precision)
        f = ffn(p, f"dec{i}.ffn", x, precision)
        x = layer_norm(x + f, p[f"dec{i}.ln3.g"], p[f"dec{i}.ln3.b"],
                       precision)
    return _mm("btd,dv->btv", x, p["out_proj"], precision)


def forward_logits(p, src, tgt, cfg, precision="highest"):
    if precision == "bf16":
        p = {k: _rounded(v, _to_bf16) for k, v in p.items()}
    return decode(p, encode(p, src, cfg, precision), tgt, cfg,
                  precision)


def loss_sum(p, src, tgt, label, cfg, precision="highest"):
    """Sum over tokens of the label-smoothed cross-entropy
    (cfg["label_smooth_eps"], uniform over the vocabulary)."""
    logits = forward_logits(p, src, tgt, cfg, precision)
    eps = cfg["label_smooth_eps"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, label[..., None], -1)[..., 0]
    per_tok = (1.0 - eps) * (lse - picked) \
        + eps * (lse - logits.mean(-1))
    return per_tok.sum()


# ---------------------------------------------------------------------
# one training step, in blocks of rows so that it fits
# ---------------------------------------------------------------------
def loss_and_grads(p, batch, cfg, precision="highest", block_rows=16):
    """Mean loss over the batch's tokens and its gradient, summed
    over blocks of `block_rows` rows (one jitted program, called once
    a block)."""
    src, tgt, label = batch["src_ids"], batch["tgt_ids"], batch["label"]
    rows = src.shape[0]
    block_rows = min(block_rows, rows)
    if rows % block_rows:
        raise ValueError(f"{rows} rows do not divide into blocks of "
                         f"{block_rows}")
    fn = _block_grad(_freeze_train(cfg), precision)
    total, grads = None, None
    for r in range(0, rows, block_rows):
        sl = slice(r, r + block_rows)
        l, g = fn(p, src[sl], tgt[sl], label[sl])
        total = l if total is None else total + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n_tok = float(rows * label.shape[1])
    return total / n_tok, jax.tree.map(lambda g: g / n_tok, grads)


def _freeze_train(cfg):
    return tuple(sorted((k, cfg[k]) for k in
                        ("d_model", "d_inner", "vocab", "n_layers",
                         "n_heads", "label_smooth_eps")))


@functools.lru_cache(maxsize=None)
def _block_grad(frozen, precision):
    cfg = dict(frozen)
    return jax.jit(jax.value_and_grad(
        lambda p, s, t, y: loss_sum(p, s, t, y, cfg, precision)))


def noam_lr(step, cfg):
    """Learning rate of step `step` (1-based), section 5.3."""
    return cfg["d_model"] ** -0.5 * min(
        step ** -0.5, step * cfg["warmup_steps"] ** -1.5)


@jax.jit
def _adam_apply(p, g, m, v, lr_t, b1, b2, eps):
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    p = jax.tree.map(
        lambda p_, m_, v_: p_ - lr_t * m_ / (jnp.sqrt(v_) + eps),
        p, m, v)
    return p, m, v


def adam_step(p, g, m, v, step, cfg):
    b1, b2 = cfg["adam_beta1"], cfg["adam_beta2"]
    lr_t = noam_lr(step, cfg) * math.sqrt(1 - b2 ** step) \
        / (1 - b1 ** step)
    return _adam_apply(p, g, m, v, jnp.float32(lr_t), jnp.float32(b1),
                       jnp.float32(b2), jnp.float32(cfg["adam_eps"]))


def train_steps(p0, batches, cfg, precision="highest", block_rows=16):
    """Follow the first len(batches) steps from weights `p0`. Returns
    the losses, the first step's gradient and the weights after the
    last step."""
    p = p0
    m = jax.tree.map(jnp.zeros_like, p0)
    v = jax.tree.map(jnp.zeros_like, p0)
    losses, first_grad = [], None
    for i, batch in enumerate(batches, start=1):
        loss, g = loss_and_grads(p, batch, cfg, precision, block_rows)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = g
        p, m, v = adam_step(p, g, m, v, i, cfg)
    return losses, first_grad, p
