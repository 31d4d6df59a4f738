"""Plain reference of Nemotron-3-Super (NVIDIA; `model_type`
`nemotron_h`,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json):
the forward pass of a decoder-only hybrid of state-space mixers
(Mamba-2), grouped-query attention and latent expert layers, and the
weights made from a seed.

Straightforward `jax.numpy`, float32, every product at precision
"highest", no cache, no chunked form, no batching: one sequence, a
layer at a time, the recurrence a `lax.scan` over positions. It imports
nothing of paddle_tpu and takes nothing the program made; the harness
hands the program the weights `make_*` makes from the seed, and this
file makes them again for itself. (The helpers that round a product's
operands for the control are transformer2017.py's.)

A layer is `h = h + mixer(RMSNorm(h))`, one mixer a layer by the
pattern string `layers` (`M`, `*`, `E`), RMS norm with `norm_eps`;
after the last layer one more RMS norm and an untied head.

`M` (Mamba-2): H heads of P, G groups, state N, kernel K. `[z | xBC |
dt] = u W_in`; `xBC = silu(conv1d_causal_depthwise(xBC, K) + b)`, split
into `x [H, P]`, `B [G, N]`, `C [G, N]` (head h reads group h // (H /
G)); `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`;
`S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`, `y_t = S_t C_t + D x_t`;
`y = group_rms_norm(y * silu(z)) * w` in G groups; out `y W_out`.

`*`: `n_heads` query heads over `n_kv_heads` key-value heads of
`head_dim`, scale `head_dim^-1/2`, causal, no rotary embedding, no
bias.

`E`: `s = sigmoid(u W_g)` over `n_experts`, the `top_k` largest of `s +
b`, weights `s_e / (sum of the chosen s + 1e-6) * routed_scaling`;
latent `l = u W_down`; expert e `relu(l W1_e)^2 W2_e`; routed part
`(sum over the chosen experts held of w_e expert_e(l)) W_up`; shared
expert `relu(u V1)^2 V2`; out routed + shared.

Departures from the published model, each because the program under
test does the same and the two have to compute one function:
  * the share of one rank of an expert-parallel job: an expert layer
    holds experts `first_held .. first_held + experts_held` of
    `n_experts`, routes over all of them and returns the held experts'
    part (through the whole W_up) plus the shared expert;
    `experts_held` = `n_experts` is the published layer;
  * the vocabulary is the rank's slice: ids and logits over `vocab`;
  * the router's bias `b` is a fixed buffer drawn from the seed;
  * no rotary embedding in the attention layers (the `nemotron_h`
    reference code applies none; the config's `rope_theta` is unread by
    that model type);
  * the multi-token-prediction module is not part of the main model's
    logits and is left out.

`fault` plants what the controls need (benchmark/chip/
controls_nemotron.py) and is never set for the reference itself:
"state_not_reset" (every state-space layer starts from the state and
the convolution tail that the same sequence left behind, as a lane
would that was not reset on admission), "state_bf16" (the scan state
rounded to bfloat16 after every position), "shared_expert_left_out",
"latent_up_left_out" (the routed part not projected up: it lands on
the first `d_latent` numbers of the hidden state). `ghost` marks
positions that no attention layer may see as keys: the driver inserts
them behind every prefill chunk for the control "padded positions
advance the state" and reads nothing at them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .transformer2017 import (PRECISIONS, _mm, _rounded,  # noqa: F401
                              _to_bf16)

FAULTS = (None, "state_not_reset", "state_bf16", "shared_expert_left_out",
          "latent_up_left_out")
NEG = -1e30


# ---------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------
def conv_width(cfg):
    return cfg["ssm_heads"] * cfg["ssm_head_dim"] \
        + 2 * cfg["ssm_groups"] * cfg["ssm_state"]


def layer_shapes(cfg, i):
    """name -> (shape, kind) of layer i, under the program's own
    parameter names; kind is "matrix" (normal, 1 / fan-in: the last
    axis but one), "experts" (the same a held expert, each drawn from
    its own number so that every rank's share is a slice of one
    layer), "one", "conv" (normal, 1 / kernel), "router", "bias", and
    the float32 leaves of the recurrence: "dt_bias" (the inverse
    softplus of a step drawn log-uniform between `time_step_min` and
    `time_step_max`), "a_log" (log of a decay drawn uniform in [1,
    16]), "skip" (one)."""
    d = cfg["d_model"]
    p = f"n{i}_"
    out = {p + "norm.w": ((d,), "one")}
    kind = cfg["layers"][i]
    if kind == "M":
        h = cfg["ssm_heads"]
        d_inner, w = h * cfg["ssm_head_dim"], conv_width(cfg)
        out[p + "in_proj.w"] = ((d, d_inner + w + h), "matrix")
        out[p + "conv.w"] = ((w, cfg["conv_kernel"]), "conv")
        out[p + "conv.b"] = ((w,), "bias")
        out[p + "dt_bias"] = ((h,), "dt_bias")
        out[p + "A_log"] = ((h,), "a_log")
        out[p + "D"] = ((h,), "skip")
        out[p + "ssm_norm.w"] = ((d_inner,), "one")
        out[p + "out_proj.w"] = ((d_inner, d), "matrix")
    elif kind == "*":
        hq = cfg["n_heads"] * cfg["head_dim"]
        hkv = cfg["n_kv_heads"] * cfg["head_dim"]
        out[p + "q.w"] = ((d, hq), "matrix")
        out[p + "k.w"] = ((d, hkv), "matrix")
        out[p + "v.w"] = ((d, hkv), "matrix")
        out[p + "o.w"] = ((hq, d), "matrix")
    elif kind == "E":
        f, n, lat = cfg["d_expert"], cfg["experts_held"], cfg["d_latent"]
        out[p + "moe_gate.w"] = ((d, cfg["n_experts"]), "router")
        out[p + "moe_bias"] = ((cfg["n_experts"],), "bias")
        out[p + "lat_down.w"] = ((d, lat), "matrix")
        out[p + "lat_up.w"] = ((lat, d), "matrix")
        out[p + "moe_w13"] = ((n, lat, f), "experts")
        out[p + "moe_w2"] = ((n, f, lat), "experts")
        out[p + "sh_w1.w"] = ((d, cfg["d_shared"]), "matrix")
        out[p + "sh_w2.w"] = ((cfg["d_shared"], d), "matrix")
    else:
        raise ValueError(f"layer {i} of {cfg['layers']!r}: M, * or E")
    return out


def top_shapes(cfg):
    d, v = cfg["d_model"], cfg["vocab"]
    return {"nem_emb": ((v, d), "embedding"),
            "nem_out_norm.w": ((d,), "one"),
            "nem_head.w": ((d, v), "matrix")}


def _key(seed, stream):
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.random.fold_in(key, stream)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, std, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal_each(key, first, std, shape, dtype):
    """shape[0] tensors of shape[1:], tensor e from `key` folded with
    `first + e`: a rank's share is a slice of the whole layer's."""
    keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(
        first + jnp.arange(shape[0]))
    return jax.vmap(lambda k: (jax.random.normal(
        k, shape[1:], jnp.float32) * std).astype(dtype))(keys)


def _init(key, shapes, cfg):
    """The leaves of `shapes`, stored in `weight_dtype` (bfloat16 at
    the cell's size) as the program holds them; the recurrence's own
    leaves float32."""
    dtype = jnp.dtype(cfg.get("weight_dtype", "bfloat16"))
    gain = cfg.get("init_gain", {})
    out = {}
    for j, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, j)
        if kind == "one":
            out[name] = jnp.ones(shape, dtype)
            continue
        if kind == "skip":
            out[name] = jnp.ones(shape, jnp.float32)
            continue
        if kind == "a_log":
            out[name] = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0))
            continue
        if kind == "dt_bias":
            lo, hi = (np.log(cfg.get("time_step_min", 1e-3)),
                      np.log(cfg.get("time_step_max", 0.1)))
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, lo, hi)),
                cfg.get("time_step_floor", 1e-4))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
            continue
        if kind == "bias":
            std = cfg.get("bias_scale", 0.02)
        elif kind == "embedding":
            std = cfg.get("emb_scale", 1.0)
        elif kind == "router":
            std = shape[0] ** -0.5 * cfg.get("router_gain", 1.0)
        elif kind == "conv":
            std = shape[1] ** -0.5
        else:
            std = shape[-2] ** -0.5
        std *= gain.get(name.split("_", 1)[1], 1.0)
        if kind == "experts":
            out[name] = _normal_each(k, cfg["first_held"], std, shape,
                                     dtype)
        else:
            out[name] = _normal(k, std, shape, dtype)
    return out


def make_layer(seed, cfg, i):
    return _init(_key(seed, 100 + i), layer_shapes(cfg, i), cfg)


def make_top(seed, cfg):
    """Embedding, final norm and head; `silent_ids` zeroes those ids'
    columns of the head, so that a reply never ends early."""
    top = _init(_key(seed, 1), top_shapes(cfg), cfg)
    for tok in cfg.get("silent_ids", ()):
        top["nem_head.w"] = top["nem_head.w"].at[:, tok].set(0)
    return top


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------
def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(u, p, prefix, cfg, precision):
    """(chosen [T, top_k] int32, weights [T, top_k]) of the router."""
    s = jax.nn.sigmoid(_mm("td,de->te", u, p[prefix + "moe_gate.w"],
                           precision))
    _, idx = jax.lax.top_k(s + p[prefix + "moe_bias"].astype(jnp.float32),
                           cfg["top_k"])
    w = jnp.take_along_axis(s, idx, -1)
    if cfg.get("norm_topk", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return idx.astype(jnp.int32), w * cfg.get("routed_scaling", 1.0)


def experts_part(lat, idx, w, p, prefix, cfg, precision):
    """The held experts' part of the result in the latent width: a loop
    over the experts held, each with the weight a token gave it (0
    where not chosen)."""
    def one(j, out):
        g = jnp.sum(jnp.where(idx == cfg["first_held"] + j, w, 0.0), -1)
        h = relu2(_mm("tl,lf->tf", lat, p[prefix + "moe_w13"][j],
                      precision))
        return out + g[:, None] * _mm("tf,fl->tl", h,
                                      p[prefix + "moe_w2"][j], precision)

    return jax.lax.fori_loop(0, cfg["experts_held"], one,
                             jnp.zeros_like(lat))


def ssm_mixer(u, p, prefix, cfg, precision, fault):
    """u [T, D] (normed) -> (the mixer's output [T, D], the scan state
    [H, P, N] behind the last position)."""
    h, hp, g, n = (cfg["ssm_heads"], cfg["ssm_head_dim"],
                   cfg["ssm_groups"], cfg["ssm_state"])
    d_inner, taps = h * hp, cfg["conv_kernel"]
    t = u.shape[0]
    zxd = _mm("td,df->tf", u, p[prefix + "in_proj.w"], precision)
    z, xbc_in, dt = (zxd[:, :d_inner],
                     zxd[:, d_inner:d_inner + conv_width(cfg)],
                     zxd[:, d_inner + conv_width(cfg):])
    wf = p[prefix + "conv.w"].astype(jnp.float32)
    dt = jax.nn.softplus(dt + p[prefix + "dt_bias"])
    a = -jnp.exp(p[prefix + "A_log"])
    skip = p[prefix + "D"]

    def run(tail, s0):
        seq = jnp.concatenate([tail, xbc_in], 0)
        conv = sum(seq[j:j + t] * wf[:, j] for j in range(taps))
        xbc = jax.nn.silu(conv + p[prefix + "conv.b"].astype(jnp.float32))
        x = xbc[:, :d_inner].reshape(t, h, hp)
        bm = jnp.repeat(xbc[:, d_inner:d_inner + g * n].reshape(t, g, n),
                        h // g, axis=1)                     # [T, H, N]
        cm = jnp.repeat(xbc[:, d_inner + g * n:].reshape(t, g, n),
                        h // g, axis=1)

        def step(s, row):
            x_t, b_t, c_t, dt_t = row
            s = jnp.exp(dt_t * a)[:, None, None] * s \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            if fault == "state_bf16":
                s = _rounded(s, _to_bf16)
            y = jnp.sum(s * c_t[:, None, :], -1) + skip[:, None] * x_t
            return s, y

        s, y = jax.lax.scan(step, s0, (x, bm, cm, dt))
        return seq[t:], s, y.reshape(t, d_inner)

    tail = jnp.zeros((taps - 1, conv_width(cfg)), jnp.float32)
    s0 = jnp.zeros((h, hp, n), jnp.float32)
    if fault == "state_not_reset":
        tail, s0, _ = run(tail, s0)
    _, s_end, y = run(tail, s0)
    v = (y * jax.nn.silu(z)).reshape(t, g, -1)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                          + cfg["norm_eps"])
    y = v.reshape(t, d_inner) * p[prefix + "ssm_norm.w"].astype(jnp.float32)
    return _mm("tf,fd->td", y, p[prefix + "out_proj.w"], precision), s_end


def attention(u, ghost, p, prefix, cfg, precision):
    hq, hkv, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    t = u.shape[0]
    q = _mm("td,df->tf", u, p[prefix + "q.w"], precision).reshape(
        t, hkv, hq // hkv, dh)
    k = _mm("td,df->tf", u, p[prefix + "k.w"], precision).reshape(
        t, hkv, dh)
    v = _mm("td,df->tf", u, p[prefix + "v.w"], precision).reshape(
        t, hkv, dh)
    s = _mm("tkrd,skd->krts", q, k, precision) * dh ** -0.5
    seen = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]) \
        & ~ghost[None, :]
    pr = jax.nn.softmax(jnp.where(seen[None, None], s, NEG), -1)
    ctx = _mm("krts,skd->tkrd", pr, v, precision).reshape(t, hq * dh)
    return _mm("tf,fd->td", ctx, p[prefix + "o.w"], precision)


def expert_layer(u, p, prefix, cfg, precision, fault):
    """u [T, D] (normed) -> (routed part, shared expert's part, chosen):
    the layer's output is their sum."""
    idx, w = route(u, p, prefix, cfg, precision)
    lat = _mm("td,dl->tl", u, p[prefix + "lat_down.w"], precision)
    routed = experts_part(lat, idx, w, p, prefix, cfg, precision)
    if fault == "latent_up_left_out":
        routed = jnp.pad(routed, ((0, 0), (0, u.shape[1] - lat.shape[1])))
    else:
        routed = _mm("tl,ld->td", routed, p[prefix + "lat_up.w"],
                     precision)
    shared = _mm("tf,fd->td", relu2(_mm(
        "td,df->tf", u, p[prefix + "sh_w1.w"], precision)),
        p[prefix + "sh_w2.w"], precision)
    if fault == "shared_expert_left_out":
        shared = jnp.zeros_like(shared)
    return routed, shared, idx


@functools.lru_cache(maxsize=None)
def _fns(cfg_items, precision, fault):
    """The jitted layer functions of one configuration."""
    cfg = {k: v for k, v in cfg_items}
    eps = cfg["norm_eps"]

    def layer(x, ghost, p, prefix, kind):
        u = rms_norm(x, p[prefix + "norm.w"], eps)
        if kind == "M":
            y, s_end = ssm_mixer(u, p, prefix, cfg, precision, fault)
            return x + y, s_end
        if kind == "*":
            return x + attention(u, ghost, p, prefix, cfg, precision), None
        routed, shared, idx = expert_layer(u, p, prefix, cfg, precision,
                                           fault)
        return x + routed + shared, idx

    def head(x, top):
        return _mm("td,dv->tv", rms_norm(x, top["nem_out_norm.w"], eps),
                   top["nem_head.w"], precision)

    return {"layer": jax.jit(layer, static_argnames=("prefix", "kind")),
            "head": jax.jit(head)}


MODEL_KEYS = ("d_model", "layers", "ssm_heads", "ssm_head_dim",
              "ssm_groups", "ssm_state", "conv_kernel", "n_heads",
              "n_kv_heads", "head_dim", "n_experts", "top_k", "d_expert",
              "d_latent", "d_shared", "norm_topk", "routed_scaling",
              "norm_eps", "experts_held", "first_held", "vocab",
              "init_gain", "silent_ids", "emb_scale", "router_gain",
              "bias_scale", "weight_dtype", "time_step_min",
              "time_step_max", "time_step_floor")


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
    positions no attention layer sees as keys (None: none). Returns
    {"logits" [n, V] float32, "chosen" [expert layers, n, top_k] int32
    (ascending), "states" [state-space layers, H, P, N] float32: each
    layer's scan state behind the last token}."""
    if precision not in PRECISIONS or fault not in FAULTS:
        raise ValueError(f"precision {precision!r} / fault {fault!r}")
    cfg = model_cfg(cfg)
    fn = _fns(_hashable(cfg), precision, fault)
    tokens = np.asarray(tokens, np.int64)
    want = jnp.asarray(np.asarray(want, np.int64))
    ghost = jnp.zeros((len(tokens),), bool) if ghost is None \
        else jnp.asarray(np.asarray(ghost, bool))
    top = make_top(seed, cfg)
    x = top["nem_emb"].astype(jnp.float32)[jnp.asarray(tokens)]
    chosen, states = [], []
    for i, kind in enumerate(cfg["layers"]):
        p = make_layer(seed, cfg, i)
        x, more = fn["layer"](x, ghost, p, prefix=f"n{i}_", kind=kind)
        if kind == "E":
            chosen.append(np.sort(np.asarray(more[want]), -1)
                          .astype(np.int32))
        elif kind == "M":
            states.append(np.asarray(more, np.float32))
        del p
    logits = fn["head"](x[want], top)
    return {"logits": np.asarray(logits, np.float32),
            "chosen": np.stack(chosen) if chosen else None,
            "states": np.stack(states) if states else None}


def moe_layer_parts(cfg, seed, x, i, experts=None, precision="highest"):
    """Expert layer i on rows x [T, D] (the residual stream before the
    layer): (held experts' part through W_up, shared expert's part,
    chosen). `experts` = (first, count) overrides the held range, for
    the test that adds the ranks' shares up."""
    cfg = model_cfg(cfg)
    if experts is not None:
        cfg = {**cfg, "first_held": experts[0], "experts_held": experts[1]}
    p = make_layer(seed, cfg, i)
    prefix = f"n{i}_"
    u = rms_norm(jnp.asarray(x, jnp.float32), p[prefix + "norm.w"],
                 cfg["norm_eps"])
    return expert_layer(u, p, prefix, cfg, precision, None)
