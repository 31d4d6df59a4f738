"""Plain reference of GLM-5.2 (zai-org; `model_type` `glm_moe_dsa`,
https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json): the
forward pass of a decoder-only stack with latent attention (MLA), the
learned sparse-attention indexer with shared selections (DSA with
IndexShare) and routed experts beside a shared expert, and the weights
made from a seed.

Straightforward `jax.numpy`, float32, every product at precision
"highest", no cache, no kernels, no batching: one sequence, a layer at
a time, a block of rows at a time so that 36,864 positions fit beside
nothing else. Keys and values are expanded a head, and a query's
selection is the `index_topk` largest of its indexer scores by a full
sort. It imports nothing of paddle_tpu and takes nothing the program
made; the harness hands the program the weights `make_*` makes from the
seed, and this file makes them again for itself. (The helpers that
round a product's operands for the control are transformer2017.py's.)

A layer is `h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h))`, RMS
norm with `norm_eps`, no biases; after the last layer one more RMS
norm and an untied head.

Latent attention (every layer): `c_Q = RMSNorm(x W_DQ)`; `q = c_Q W_UQ`,
`n_heads` heads of `[q_nope | q_rope]`; `[c_KV | k_r] = x W_DKV`,
`c = RMSNorm(c_KV)`, `k_r` rotated and shared by all heads; a head's
key and value are `[k_nope,h | v_h] = c W_UKV,h`. Query t scores
position s of its selected set S_t by `(q_nope.k_nope + q_rope.k_r) /
sqrt(qk_nope + qk_rope)`, softmax over S_t, output `concat_h(sum p v_h)
W_O`. Rotary positions turn the pairs (2i, 2i+1) of a vector by
`pos * theta^(-2i/dim)` (`rope_interleave`).

Indexer (layers whose `indexer_types` entry is "full"): `q^I = c_Q
W^I_q`, `index_n_heads` heads of `index_head_dim`; `k^I = LayerNorm(x
W^I_k)`, one a position; `w = x W^I_w` scaled by `index_n_heads^-0.5 *
index_head_dim^-0.5`; rotary on the first `index_rope_dim` numbers of
`q^I` and `k^I`. `I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])` for
s <= t; S_t is the `index_topk` positions of largest `I[t, .]`, all of
them while t < `index_topk`. A "shared" layer owns no indexer and uses
the S_t of the nearest "full" layer below it.

Feed-forward: the first `n_dense_layers` layers a gated SiLU of width
`d_dense`; the others `s = sigmoid(x W_g)` over `n_experts`, the
`top_k` largest of `s + b`, weights `s_e / (sum of the chosen s + 1e-6)
* routed_scaling`, the weighted sum of the chosen experts' gated SiLU
of width `d_expert`, plus a shared expert of the same form.

Departures from the published model, each because the program under
test does the same and the two have to compute one function:
  * the share of one rank of an expert-parallel job: an expert layer
    holds experts `first_held .. first_held + experts_held` of
    `n_experts`, routes over all of them and returns the held experts'
    part of the result plus the shared expert; what the absent experts
    would add is left out. `experts_held` = `n_experts` is the
    published layer;
  * the vocabulary is the rank's slice: ids and logits over `vocab`;
  * the router's bias `b` is a fixed buffer drawn from the seed;
  * the indexer's key norm is a LayerNorm (with bias) and the rotated
    part is the first `index_rope_dim` numbers, as in the public
    DeepSeek-V3.2 reference code; the source's FP8 rounding of `q^I`
    and `k^I` and the orthogonal rotation before it are not
    reproduced (the rotation cancels in the product);
  * rotary pairs are turned in place; implementations that first move
    the pairs to the two halves of the vector permute q and k alike
    and give the same products;
  * the multi-token-prediction layer is not part of the main model's
    logits and is left out.

`fault` plants what the controls need (benchmark/chip/controls_glm.py)
and is never set for the reference itself: "selection_left_out" (every
query attends every position up to its own), "shared_selects_itself"
(a "shared" layer scores with the indexer weights of the "full" layer
below it on its own hidden state), "shared_expert_left_out".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .transformer2017 import PRECISIONS, _mm  # noqa: F401

FAULTS = (None, "selection_left_out", "shared_selects_itself",
          "shared_expert_left_out")
NEG = -1e30


def layer_kinds(cfg):
    """[(ffn, indexer)] a layer: ffn "dense" or "moe", indexer "full"
    or "shared"."""
    kinds = list(cfg["indexer_types"])
    if len(kinds) != cfg["n_layers"] or kinds[0] != "full":
        raise ValueError(f"indexer_types {kinds} for {cfg['n_layers']} "
                         f"layers; the first has to be 'full'")
    return [("dense" if i < cfg["n_dense_layers"] else "moe", kinds[i])
            for i in range(cfg["n_layers"])]


def indexer_owner(cfg, i):
    """The layer whose selection layer i uses."""
    kinds = cfg["indexer_types"]
    while kinds[i] != "full":
        i -= 1
    return i


# ---------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------
def layer_shapes(cfg, i):
    """name -> (shape, kind) of layer i, under the program's own
    parameter names; kind is "matrix" (normal, 1 / fan-in: the last
    axis but one), "experts" (the same a held expert, each drawn from
    its own number so that every rank's share is a slice of one
    layer), "one", "zero", "router" or "bias"."""
    d, h = cfg["d_model"], cfg["n_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    p = f"g{i}_"
    out = {p + "norm1.w": ((d,), "one"), p + "norm2.w": ((d,), "one"),
           p + "q_a.w": ((d, rq), "matrix"),
           p + "q_a_norm.w": ((rq,), "one"),
           p + "q_b.w": ((rq, h * (dn + dr)), "matrix"),
           p + "kv_a.w": ((d, rkv + dr), "matrix"),
           p + "kv_a_norm.w": ((rkv,), "one"),
           p + "kv_b.w": ((rkv, h * (dn + dv)), "matrix"),
           p + "o.w": ((h * dv, d), "matrix")}
    ffn, indexer = layer_kinds(cfg)[i]
    if indexer == "full":
        hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
        out[p + "idx_q.w"] = ((rq, hi * di), "matrix")
        out[p + "idx_k.w"] = ((d, di), "matrix")
        out[p + "idx_k_norm.w"] = ((di,), "one")
        out[p + "idx_k_norm.b"] = ((di,), "zero")
        out[p + "idx_w.w"] = ((d, hi), "matrix")
    if ffn == "dense":
        f = cfg["d_dense"]
        out[p + "ff_w13.w"] = ((d, 2 * f), "matrix")
        out[p + "ff_w2.w"] = ((f, d), "matrix")
    else:
        f, n = cfg["d_expert"], cfg["experts_held"]
        fs = f * cfg.get("n_shared_experts", 1)
        out[p + "moe_gate.w"] = ((d, cfg["n_experts"]), "router")
        out[p + "moe_bias"] = ((cfg["n_experts"],), "bias")
        out[p + "moe_w13"] = ((n, d, 2 * f), "experts")
        out[p + "moe_w2"] = ((n, f, d), "experts")
        out[p + "sh_w13.w"] = ((d, 2 * fs), "matrix")
        out[p + "sh_w2.w"] = ((fs, d), "matrix")
    return out


def top_shapes(cfg):
    d, v = cfg["d_model"], cfg["vocab"]
    return {"glm_emb": ((v, d), "embedding"),
            "glm_out_norm.w": ((d,), "one"),
            "glm_head.w": ((d, v), "matrix")}


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
    the cell's size) as the program holds them."""
    dtype = jnp.dtype(cfg.get("weight_dtype", "bfloat16"))
    gain = cfg.get("init_gain", {})
    out = {}
    for j, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
        if kind in ("one", "zero"):
            out[name] = jnp.full(shape, float(kind == "one"), dtype)
            continue
        if kind == "bias":
            std = cfg.get("bias_scale", 0.02)
        elif kind == "embedding":
            std = cfg.get("emb_scale", 1.0)
        elif kind == "router":
            std = shape[0] ** -0.5 * cfg.get("router_gain", 1.0)
        else:
            std = shape[-2] ** -0.5
        std *= gain.get(name.split("_", 1)[1], 1.0)
        k = jax.random.fold_in(key, j)
        if kind == "experts":
            out[name] = jnp.stack([
                _normal(jax.random.fold_in(k, cfg["first_held"] + e), std,
                        shape[1:], dtype) for e in range(shape[0])])
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
        top["glm_head.w"] = top["glm_head.w"].at[:, tok].set(0)
    return top


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------
def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def layer_norm(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def rope(x, pos, theta):
    """Pairs (2i, 2i+1) of the last axis turned by pos * theta^(-2i /
    dim); `pos` has the shape of x's leading axis."""
    dim = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = pos.astype(jnp.float32)[:, None] * inv
    angle = angle.reshape(angle.shape[:1] + (1,) * (x.ndim - 2)
                          + angle.shape[1:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def swiglu(x, w13, w2, precision):
    h = _mm("td,df->tf", x, w13, precision)
    f = h.shape[-1] // 2
    return _mm("tf,fd->td", jax.nn.silu(h[:, :f]) * h[:, f:], w2,
               precision)


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


def experts_part(u, idx, w, p, prefix, cfg, precision):
    """The held experts' part of the result: a loop over the experts
    held, each with the weight a token gave it (0 where not chosen)."""
    out = jnp.zeros_like(u)
    for j in range(cfg["experts_held"]):
        g = jnp.sum(jnp.where(idx == cfg["first_held"] + j, w, 0.0), -1)
        y = swiglu(u, p[prefix + "moe_w13"][j], p[prefix + "moe_w2"][j],
                   precision)
        out = out + g[:, None] * y
    return out


# ---------------------------------------------------------------------
# the forward pass of one sequence, a block of rows at a time
# ---------------------------------------------------------------------
KEY_BUCKET = 4096   # a block's keys are cut to a multiple of this


@functools.lru_cache(maxsize=None)
def _fns(cfg_items, precision, fault):
    """The jitted block functions of one configuration."""
    cfg = {k: (list(v) if isinstance(v, tuple) else v)
           for k, v in cfg_items}
    cfg["init_gain"] = {}
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    h = cfg["n_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rkv = cfg["kv_lora_rank"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    ri = cfg["index_rope_dim"]
    topk = cfg["index_topk"]
    scale = (dn + dr) ** -0.5

    def mm(spec, a, b):
        return _mm(spec, a, b, precision)

    def keys(x, pos, p, prefix):
        """x [B, D] -> (k [B,H,dn+dr], v [B,H,dv]) a head."""
        u = rms_norm(x, p[prefix + "norm1.w"], eps)
        ckv = mm("td,dr->tr", u, p[prefix + "kv_a.w"])
        c = rms_norm(ckv[:, :rkv], p[prefix + "kv_a_norm.w"], eps)
        kr = rope(ckv[:, rkv:], pos, theta)
        kv = mm("tr,rf->tf", c, p[prefix + "kv_b.w"]).reshape(
            -1, h, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(kr[:, None], (len(x), h, dr))],
            -1)
        return k, kv[..., dn:]

    def index_keys(x, pos, p, own, prefix):
        """k^I [B, di]: layer `own`'s indexer on layer `prefix`'s
        rows (the two differ only under the fault)."""
        u = rms_norm(x, p[prefix + "norm1.w"], eps)
        ki = layer_norm(mm("td,df->tf", u, p[own + "idx_k.w"]),
                        p[own + "idx_k_norm.w"], p[own + "idx_k_norm.b"],
                        1e-6)
        return jnp.concatenate([rope(ki[..., :ri], pos, theta),
                                ki[..., ri:]], -1)

    def queries(x, pos, p, prefix):
        """x [B, D] -> (q [B,H,dn+dr], c_Q [B, rq], u [B, D])."""
        u = rms_norm(x, p[prefix + "norm1.w"], eps)
        cq = rms_norm(mm("td,dr->tr", u, p[prefix + "q_a.w"]),
                      p[prefix + "q_a_norm.w"], eps)
        q = mm("tr,rf->tf", cq, p[prefix + "q_b.w"]).reshape(
            -1, h, dn + dr)
        return jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], pos, theta)], -1), cq, u

    def select(u, cq, pos, ki_all, p, own):
        """The selection of a block of queries as a mask [B, T] over
        the positions given: the `topk` largest scores among s <= t, by
        a full sort."""
        t_all = ki_all.shape[0]
        causal = jnp.arange(t_all)[None, :] <= pos[:, None]
        if fault == "selection_left_out":
            return causal
        qi = mm("tr,rf->tf", cq, p[own + "idx_q.w"]).reshape(-1, hi, di)
        qi = jnp.concatenate([rope(qi[..., :ri], pos, theta),
                              qi[..., ri:]], -1)
        w = mm("td,dh->th", u, p[own + "idx_w.w"]) \
            * (hi ** -0.5 * di ** -0.5)
        sc = jax.nn.relu(mm("thd,sd->ths", qi, ki_all))
        score = jnp.einsum("ths,th->ts", sc, w,
                           precision=jax.lax.Precision.HIGHEST)
        score = jnp.where(causal, score, NEG)
        order = jnp.argsort(-score, axis=-1)[:, :min(topk, t_all)]
        mask = jnp.zeros(score.shape, bool).at[
            jnp.arange(len(pos))[:, None], order].set(True)
        return mask & causal

    def attend(x, q, k_all, v_all, mask, p, prefix):
        """x + attention of a block of queries over the keys given
        under `mask` [B, T]."""
        s = mm("thd,shd->hts", q, k_all) * scale
        s = jnp.where(mask[None], s, NEG)
        pr = jax.nn.softmax(s, -1)
        ctx = mm("hts,shd->thd", pr, v_all).reshape(len(q), h * dv)
        return x + mm("tf,fd->td", ctx, p[prefix + "o.w"])

    def ffn(x, p, prefix, kind):
        """x [B, D] after attention -> (x + FFN, chosen or None)."""
        u = rms_norm(x, p[prefix + "norm2.w"], eps)
        if kind == "dense":
            return x + swiglu(u, p[prefix + "ff_w13.w"],
                              p[prefix + "ff_w2.w"], precision), None
        idx, w = route(u, p, prefix, cfg, precision)
        y = experts_part(u, idx, w, p, prefix, cfg, precision)
        if fault != "shared_expert_left_out":
            y = y + swiglu(u, p[prefix + "sh_w13.w"],
                           p[prefix + "sh_w2.w"], precision)
        return x + y, idx

    def head(x, top):
        return mm("td,dv->tv", rms_norm(x, top["glm_out_norm.w"], eps),
                  top["glm_head.w"])

    return {"keys": jax.jit(keys, static_argnames=("prefix",)),
            "index_keys": jax.jit(index_keys,
                                  static_argnames=("own", "prefix")),
            "queries": jax.jit(queries, static_argnames=("prefix",)),
            "select": jax.jit(select, static_argnames=("own",)),
            "attend": jax.jit(attend, static_argnames=("prefix",)),
            "ffn": jax.jit(ffn, static_argnames=("prefix", "kind")),
            "head": jax.jit(head)}


MODEL_KEYS = ("d_model", "n_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "index_n_heads", "index_head_dim", "index_rope_dim",
              "index_topk", "indexer_types", "d_dense", "d_expert",
              "n_experts", "top_k", "n_shared_experts", "norm_topk",
              "routed_scaling", "rope_theta", "norm_eps", "n_layers",
              "n_dense_layers", "experts_held", "first_held", "vocab",
              "init_gain", "silent_ids", "emb_scale", "router_gain",
              "bias_scale", "weight_dtype")


def model_cfg(c):
    return {k: c[k] for k in MODEL_KEYS if k in c}


def _hashable(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if not isinstance(v, dict)))


def _pad_rows(a, rows):
    return a if a.shape[0] == rows else jnp.pad(
        a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def forward(cfg, seed, tokens, want, precision="highest", fault=None,
            block=128):
    """One sequence `tokens` [T] through the model. `want`: the
    positions to report (ascending). Returns {"logits" [n, V] float32,
    "selected" [layers, n, index_topk] int32 (the positions a query
    attends in each layer: its own selection in a layer that owns an
    indexer, that layer's in the layers that share it; ascending, -1
    where it has fewer), "chosen" [expert layers, n, top_k] int32
    (ascending)}. Rows are padded to whole blocks and a
    block's keys cut to a multiple of KEY_BUCKET, so that sequences of
    any length share a few compiled shapes; a padded row is behind
    every real one and the causal mask keeps it out."""
    if precision not in PRECISIONS or fault not in FAULTS:
        raise ValueError(f"precision {precision!r} / fault {fault!r}")
    cfg = model_cfg(cfg)
    fn = _fns(_hashable(cfg), precision, fault)
    tokens = np.asarray(tokens, np.int64)
    want = np.asarray(want, np.int64)
    n_blocks = -(-len(tokens) // block)
    t_eff = n_blocks * block
    t_keys = -(-t_eff // KEY_BUCKET) * KEY_BUCKET
    tokens = np.pad(tokens, (0, t_eff - len(tokens)))
    pos_all = jnp.arange(t_eff, dtype=jnp.int32)
    top = make_top(seed, cfg)
    x = top["glm_emb"].astype(jnp.float32)[jnp.asarray(tokens)]
    spans = [(n * block, (n + 1) * block) for n in range(n_blocks)]
    topk = cfg["index_topk"]
    masks, selected, chosen = {}, [], []

    def n_keys(b):
        return min(t_keys, -(-b // KEY_BUCKET) * KEY_BUCKET)

    for i, (ffn_kind, indexer) in enumerate(layer_kinds(cfg)):
        p = make_layer(seed, cfg, i)
        prefix = f"g{i}_"
        own = f"g{indexer_owner(cfg, i)}_"
        kv = [fn["keys"](x[a:b], pos_all[a:b], p, prefix=prefix)
              for a, b in spans]
        k_all = _pad_rows(jnp.concatenate([k for k, _ in kv]), t_keys)
        v_all = _pad_rows(jnp.concatenate([v for _, v in kv]), t_keys)
        del kv
        selects = indexer == "full" or fault == "shared_selects_itself"
        if selects:
            if own != prefix:       # the fault: the owner's weights
                p = {**p, **make_layer(seed, cfg, indexer_owner(cfg, i))}
            ki_all = _pad_rows(jnp.concatenate([
                fn["index_keys"](x[a:b], pos_all[a:b], p, own=own,
                                 prefix=prefix) for a, b in spans]),
                t_keys)
        outs, used = [], []
        for n, (a, b) in enumerate(spans):
            nk = n_keys(b)
            q, cq, u = fn["queries"](x[a:b], pos_all[a:b], p,
                                     prefix=prefix)
            if selects:
                mask = fn["select"](u, cq, pos_all[a:b], ki_all[:nk], p,
                                    own=own)
                if indexer == "full":
                    masks[n] = mask
            else:
                mask = masks[n]
            outs.append(fn["attend"](x[a:b], q, k_all[:nk], v_all[:nk],
                                     mask, p, prefix=prefix))
            for t in want[(want >= a) & (want < b)]:
                row = np.flatnonzero(np.asarray(mask[int(t) - a]))
                used.append(np.pad(row, (0, max(0, topk - len(row))),
                                   constant_values=-1)[:topk])
        selected.append(np.stack(used).astype(np.int32))
        del k_all, v_all
        idxs = []
        for n, o in enumerate(outs):
            outs[n], idx = fn["ffn"](o, p, prefix=prefix, kind=ffn_kind)
            idxs.append(idx)
        x = jnp.concatenate(outs)
        del outs
        if ffn_kind == "moe":
            chosen.append(np.sort(np.asarray(
                jnp.concatenate(idxs))[want], -1).astype(np.int32))
        del p
    logits = fn["head"](x[jnp.asarray(want)], top)
    return {"logits": np.asarray(logits, np.float32),
            "selected": np.stack(selected) if selected else None,
            "chosen": np.stack(chosen) if chosen else None}


def moe_layer_parts(cfg, seed, x, i, experts=None, precision="highest"):
    """The feed-forward half of expert layer i on rows x [T, D] (what
    follows the attention): (held experts' part, shared expert's part,
    chosen). `experts` = (first, count) overrides the held range, for
    the test that adds the ranks' shares up."""
    cfg = model_cfg(cfg)
    if experts is not None:
        cfg = {**cfg, "first_held": experts[0], "experts_held": experts[1]}
    p = make_layer(seed, cfg, i)
    prefix = f"g{i}_"
    u = rms_norm(jnp.asarray(x, jnp.float32), p[prefix + "norm2.w"],
                 cfg["norm_eps"])
    idx, w = route(u, p, prefix, cfg, precision)
    return (experts_part(u, idx, w, p, prefix, cfg, precision),
            swiglu(u, p[prefix + "sh_w13.w"], p[prefix + "sh_w2.w"],
                   precision), idx)
