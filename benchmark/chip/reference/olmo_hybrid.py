"""Plain reference of Olmo-Hybrid-7B (AllenAI; `model_type`
`olmo_hybrid`,
https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json):
the forward pass of a decoder-only hybrid of gated delta-rule linear
attention and full attention, each layer with a SwiGLU MLP, and the
weights made from a seed.

Straightforward `jax.numpy`, float32, every product at precision
"highest", no cache, no chunked form, no batching: one sequence, a
layer at a time, the delta rule a `lax.scan` over positions in its
recurrent form, attention a block of query rows at a time. It imports
nothing of paddle_tpu and takes nothing the program made; the harness
hands the program the weights `make_*` makes from the seed, and this
file makes them again for itself. (The helpers that round a product's
operands for the control are transformer2017.py's.)

A layer is Olmo 2's post-norm pair, by the pattern string `layers`
(`L` linear, `F` full): `h = x + RMSNorm(mixer(x))`, `out = h +
RMSNorm(W_down(silu(W_gate h) * W_up h))`, RMS norm with `norm_eps`;
after the last layer one more RMS norm and an untied head.

`L` (Gated DeltaNet): H heads, keys of Dk, values of Dv, kernel K.
`[q | k | v | z | b | a] = x W_in`; q, k and v each through a causal
depthwise convolution of K taps (no bias) and SiLU; per head `q <-
q / |q| Dk^-1/2`, `k <- k / |k|` (eps 1e-6 under the root); `beta =
2 sigmoid(b)` (`linear_allow_neg_eigval`: beta in (0, 2)); `g =
-exp(A_log) softplus(a + dt_bias)`, `alpha = exp(g)`;
`S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T`
with S [Dk, Dv] float32, `o_t = S_t^T q_t`; `y = RMSNorm_head(o) *
silu(z)`, the norm over each head's Dv numbers with one weight of Dv
that every head shares, the gate after it; out `y W_o`.

`F`: H heads of Dh over as many key-value heads (`n_kv_heads` may
group them), no bias; QK-norm, an RMS norm over the whole q and the
whole k projection; rotary positions (rotate-half layout, theta
`rope_theta`); causal softmax at scale Dh^-1/2; out `W_o`.

Departures from the published description, each because the config is
silent and the program under test does the same (the configuration
file's `assumed` gives each reason): the post-norm placement of the
Olmo 2/3 family; `rope_theta` 500,000; the convolution over q, k and v
separately without bias; the gated output norm with SiLU on the gate;
`A_log` and `dt_bias` drawn as Mamba-2 draws them; a float32 state; the
end token's column of the head zero; the initial scales.

`fault` plants what the controls need (benchmark/chip/controls_olmoh.py)
and is never set for the reference itself: "state_not_reset" (every
linear layer starts from the state and the convolution tail that the
same sequence left behind, as a lane would that was not reset on
admission), "state_bf16" (the state rounded to bfloat16 after every
position), "no_erase" (`S <- alpha S + beta k v^T`: plain gated linear
attention), "beta_not_doubled" (beta in (0, 1)), "no_qk_l2" (q and k
not normalised; q keeps its scale). `ghost` marks positions that no
attention layer may see as keys: the driver inserts them behind every
prefill chunk for the control "padded positions advance the state" and
reads nothing at them; they take the position of the real token before
them. The sequence is padded at its end (to a power of two, or past
`PAD_TO` to a whole number of `PAD_TO` positions), which neither the
state nor any earlier position sees, so that lengths share a
compilation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .transformer2017 import (PRECISIONS, _mm, _rounded,  # noqa: F401
                              _to_bf16)

FAULTS = (None, "state_not_reset", "state_bf16", "no_erase",
          "beta_not_doubled", "no_qk_l2")
NEG = -1e30
L2_EPS = 1e-6
PAD_TO = 1024       # the sequence's length is padded to a multiple
Q_BLOCK = 512       # query rows of attention at a time


# ---------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------
def conv_width(cfg):
    return cfg["lin_heads"] * (2 * cfg["lin_key_dim"]
                               + cfg["lin_value_dim"])


def layer_shapes(cfg, i):
    """name -> (shape, kind) of layer i, under the program's own
    parameter names; kind is "matrix" (normal, 1 / fan-in: the last
    axis but one), "one", "conv" (normal, 1 / kernel), and the float32
    leaves of the recurrence: "dt_bias" (the inverse softplus of a step
    drawn log-uniform between `time_step_min` and `time_step_max`),
    "a_log" (log of a decay drawn uniform in [1, 16])."""
    d, f = cfg["d_model"], cfg["d_ff"]
    p = f"o{i}_"
    kind = cfg["layers"][i]
    if kind == "L":
        h = cfg["lin_heads"]
        hv = h * cfg["lin_value_dim"]
        out = {p + "in_proj.w": ((d, conv_width(cfg) + hv + 2 * h),
                                 "matrix"),
               p + "conv.w": ((conv_width(cfg), cfg["conv_kernel"]),
                              "conv"),
               p + "dt_bias": ((h,), "dt_bias"),
               p + "A_log": ((h,), "a_log"),
               p + "gdn_norm.w": ((cfg["lin_value_dim"],), "one"),
               p + "out_proj.w": ((hv, d), "matrix")}
    elif kind == "F":
        hq = cfg["n_heads"] * cfg["head_dim"]
        hkv = cfg["n_kv_heads"] * cfg["head_dim"]
        out = {p + "qkv.w": ((d, hq + 2 * hkv), "matrix"),
               p + "q_norm.w": ((hq,), "one"),
               p + "k_norm.w": ((hkv,), "one"),
               p + "o.w": ((hq, d), "matrix")}
    else:
        raise ValueError(f"layer {i} of {cfg['layers']!r}: L or F")
    out[p + "attn_post_norm.w"] = ((d,), "one")
    out[p + "gate_up.w"] = ((d, 2 * f), "matrix")
    out[p + "down.w"] = ((f, d), "matrix")
    out[p + "ff_post_norm.w"] = ((d,), "one")
    return out


def top_shapes(cfg):
    d, v = cfg["d_model"], cfg["vocab"]
    return {"olmoh_emb": ((v, d), "embedding"),
            "olmoh_out_norm.w": ((d,), "one"),
            "olmoh_head.w": ((d, v), "matrix")}


def _key(seed, stream):
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.random.fold_in(key, stream)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, std, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _init(key, shapes, cfg):
    """The leaves of `shapes`, stored in `weight_dtype` (bfloat16 at
    the cell's size) as the program holds them; the recurrence's own
    leaves float32."""
    dtype = jnp.dtype(cfg.get("weight_dtype", "bfloat16"))
    out = {}
    for j, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, j)
        if kind == "one":
            out[name] = jnp.ones(shape, dtype)
        elif kind == "a_log":
            out[name] = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0))
        elif kind == "dt_bias":
            lo, hi = (np.log(cfg.get("time_step_min", 1e-3)),
                      np.log(cfg.get("time_step_max", 0.1)))
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, lo, hi)),
                cfg.get("time_step_floor", 1e-4))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            std = {"embedding": cfg.get("emb_scale", 1.0),
                   "conv": shape[-1] ** -0.5}.get(kind, shape[-2] ** -0.5)
            out[name] = _normal(k, std, shape, dtype)
    return out


def make_layer(seed, cfg, i):
    return _init(_key(seed, 100 + i), layer_shapes(cfg, i), cfg)


def make_top(seed, cfg):
    """Embedding, final norm and head; `silent_ids` zeroes those ids'
    columns of the head, so that a reply never ends early."""
    top = _init(_key(seed, 1), top_shapes(cfg), cfg)
    for tok in cfg.get("silent_ids", ()):
        top["olmoh_head.w"] = top["olmoh_head.w"].at[:, tok].set(0)
    return top


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------
def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def rope(x, positions, theta):
    """x [T, H, D] turned by positions [T] (rotate-half layout)."""
    dim = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions.astype(jnp.float32)[:, None] * inv
    angle = jnp.concatenate([angle, angle], -1)[:, None]
    half = dim // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(angle) + rot * jnp.sin(angle)


def gdn_mixer(x, live, p, prefix, cfg, precision, fault):
    """x [T, D] -> (the mixer's output [T, D], the state [H, Dk, Dv]
    behind the last live position). `live` [T]: positions that enter
    the recurrence (the padding at the end does not)."""
    h, dk, dv = cfg["lin_heads"], cfg["lin_key_dim"], cfg["lin_value_dim"]
    taps, w = cfg["conv_kernel"], conv_width(cfg)
    t = x.shape[0]
    proj = _mm("td,df->tf", x, p[prefix + "in_proj.w"], precision)
    qkv_in, z = proj[:, :w], proj[:, w:w + h * dv]
    b, a = proj[:, w + h * dv:w + h * dv + h], proj[:, w + h * dv + h:]
    wf = p[prefix + "conv.w"].astype(jnp.float32)
    beta = jax.nn.sigmoid(b)
    if cfg.get("allow_neg_eigval", True) and fault != "beta_not_doubled":
        beta = 2.0 * beta
    g = -jnp.exp(p[prefix + "A_log"]) * jax.nn.softplus(
        a + p[prefix + "dt_bias"])
    norm = (lambda v: v) if fault == "no_qk_l2" else l2norm

    def run(tail, s0):
        seq = jnp.concatenate([tail, qkv_in], 0)
        qkv = jax.nn.silu(sum(seq[j:j + t] * wf[:, j] for j in range(taps)))
        q = norm(qkv[:, :h * dk].reshape(t, h, dk)) * dk ** -0.5
        k = norm(qkv[:, h * dk:2 * h * dk].reshape(t, h, dk))
        v = qkv[:, 2 * h * dk:].reshape(t, h, dv)

        def step(s, row):
            q_t, k_t, v_t, b_t, g_t, on = row
            s_in = s
            s = jnp.exp(g_t)[:, None, None] * s
            if fault == "no_erase":
                s = s + b_t[:, None, None] * k_t[:, :, None] \
                    * v_t[:, None, :]
            else:
                read = jnp.sum(k_t[:, :, None] * s, 1)          # S^T k
                s = s + k_t[:, :, None] \
                    * (b_t[:, None] * (v_t - read))[:, None, :]
            if fault == "state_bf16":
                s = _rounded(s, _to_bf16)
            o = jnp.sum(q_t[:, :, None] * s, 1)                 # S^T q
            return jnp.where(on, s, s_in), o

        s, o = jax.lax.scan(step, s0, (q, k, v, beta, g, live))
        n = jnp.sum(live)
        return jax.lax.dynamic_slice_in_dim(seq, n, taps - 1, 0), s, o

    tail = jnp.zeros((taps - 1, w), jnp.float32)
    s0 = jnp.zeros((h, dk, dv), jnp.float32)
    if fault == "state_not_reset":
        tail, s0, _ = run(tail, s0)
    _, s_end, o = run(tail, s0)
    y = rms_norm(o, p[prefix + "gdn_norm.w"], cfg["norm_eps"]) \
        * jax.nn.silu(z.reshape(t, h, dv))
    return _mm("tf,fd->td", y.reshape(t, h * dv), p[prefix + "out_proj.w"],
               precision), s_end


def attention(x, ghost, positions, p, prefix, cfg, precision):
    hq, hkv, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    t = x.shape[0]
    qkv = _mm("td,df->tf", x, p[prefix + "qkv.w"], precision)
    q = rms_norm(qkv[:, :hq * dh], p[prefix + "q_norm.w"], cfg["norm_eps"])
    k = rms_norm(qkv[:, hq * dh:(hq + hkv) * dh], p[prefix + "k_norm.w"],
                 cfg["norm_eps"])
    v = qkv[:, (hq + hkv) * dh:].reshape(t, hkv, dh)
    theta = cfg["rope_theta"]
    size = min(Q_BLOCK, t)
    q = rope(q.reshape(t, hq, dh), positions, theta).reshape(
        t // size, size, hkv, hq // hkv, dh)
    k = rope(k.reshape(t, hkv, dh), positions, theta)
    cols = jnp.arange(t)

    def rows(blk):
        qb, first = blk
        s = _mm("qkrd,skd->krqs", qb, k, precision) * dh ** -0.5
        seen = (cols[None, :] <= first + jnp.arange(size)[:, None]) \
            & ~ghost[None, :]
        pr = jax.nn.softmax(jnp.where(seen[None, None], s, NEG), -1)
        return _mm("krqs,skd->qkrd", pr, v, precision)

    ctx = jax.lax.map(rows, (q, jnp.arange(0, t, size)))
    return _mm("tf,fd->td", ctx.reshape(t, hq * dh), p[prefix + "o.w"],
               precision)


def mlp(x, p, prefix, cfg, precision):
    gu = _mm("td,df->tf", x, p[prefix + "gate_up.w"], precision)
    f = cfg["d_ff"]
    return _mm("tf,fd->td", jax.nn.silu(gu[:, :f]) * gu[:, f:],
               p[prefix + "down.w"], precision)


@functools.lru_cache(maxsize=None)
def _fns(cfg_items, precision, fault):
    """The jitted layer functions of one configuration."""
    cfg = {k: v for k, v in cfg_items}
    eps = cfg["norm_eps"]

    def layer(x, ghost, live, positions, p, prefix, kind):
        if kind == "L":
            y, s_end = gdn_mixer(x, live, p, prefix, cfg, precision, fault)
        else:
            y, s_end = attention(x, ghost, positions, p, prefix, cfg,
                                 precision), None
        h = x + rms_norm(y, p[prefix + "attn_post_norm.w"], eps)
        out = h + rms_norm(mlp(h, p, prefix, cfg, precision),
                           p[prefix + "ff_post_norm.w"], eps)
        return out, s_end

    def head(x, top):
        return _mm("td,dv->tv", rms_norm(x, top["olmoh_out_norm.w"], eps),
                   top["olmoh_head.w"], precision)

    return {"layer": jax.jit(layer, static_argnames=("prefix", "kind")),
            "head": jax.jit(head)}


MODEL_KEYS = ("d_model", "layers", "lin_heads", "lin_key_dim",
              "lin_value_dim", "conv_kernel", "allow_neg_eigval",
              "n_heads", "n_kv_heads", "head_dim", "d_ff", "rope_theta",
              "norm_eps", "vocab", "silent_ids", "emb_scale",
              "weight_dtype", "time_step_min", "time_step_max",
              "time_step_floor")


def model_cfg(c):
    return {k: c[k] for k in MODEL_KEYS if k in c}


def _hashable(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if not isinstance(v, dict)))


def forward(cfg, seed, tokens, want, precision="highest", fault=None,
            ghost=None):
    """One sequence `tokens` [T] through the model from zero state.
    `want`: the positions to report (ascending). `ghost` [T] bool:
    positions no attention layer sees as keys, which take the position
    of the token before them (None: none). Returns {"logits" [n, V]
    float32, "states" [linear layers, H, Dk, Dv] float32: each layer's
    state behind the last token}."""
    if precision not in PRECISIONS or fault not in FAULTS:
        raise ValueError(f"precision {precision!r} / fault {fault!r}")
    cfg = model_cfg(cfg)
    fn = _fns(_hashable(cfg), precision, fault)
    tokens = np.asarray(tokens, np.int64)
    n = len(tokens)
    t = -(-n // PAD_TO) * PAD_TO if n > PAD_TO \
        else max(16, 1 << (n - 1).bit_length())
    ghost = np.zeros(n, bool) if ghost is None \
        else np.asarray(ghost, bool)
    positions = np.maximum(np.cumsum(~ghost) - 1, 0)
    pad = t - n
    tokens = np.concatenate([tokens, np.zeros(pad, np.int64)])
    ghost = jnp.asarray(np.concatenate([ghost, np.ones(pad, bool)]))
    live = jnp.asarray(np.arange(t) < n)
    positions = jnp.asarray(np.concatenate(
        [positions, positions[-1] + 1 + np.arange(pad)]))
    want = jnp.asarray(np.asarray(want, np.int64))
    top = make_top(seed, cfg)
    x = top["olmoh_emb"].astype(jnp.float32)[jnp.asarray(tokens)]
    states = []
    for i, kind in enumerate(cfg["layers"]):
        p = make_layer(seed, cfg, i)
        x, s_end = fn["layer"](x, ghost, live, positions, p,
                               prefix=f"o{i}_", kind=kind)
        if kind == "L":
            states.append(np.asarray(s_end, np.float32))
        del p
    logits = fn["head"](x[want], top)
    return {"logits": np.asarray(logits, np.float32),
            "states": np.stack(states) if states else None}
