"""LFM2-MoE: Liquid's hybrid decoder-only language model
(`model_type` `lfm2_moe`; published configuration
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json).
No reference counterpart: Fluid 1.x has no such model; the builder
follows `models/transformer.py build_program`.

Every layer is `h = x + Mixer(RMSNorm(x)); y = h + FF(RMSNorm(h))`, no
biases anywhere, and one more RMSNorm before the output head, which is
the embedding table again (tied). `layer_types` says which mixer a
layer has:

* "conv": the gated short convolution. `[b, c, z] = split3(u W_in)`,
  `v = b * z`, a depthwise causal convolution of v over time with
  `conv_taps` taps, `out = (c * conv(v)) W_out` (layers.short_conv).
* "attention": grouped-query attention, `n_heads` query heads over
  `n_kv_heads` key-value heads; q and k get an RMSNorm over the head
  dimension and then rotary positions; causal softmax attention
  through the `attention` op, which routes long sequences to the
  Pallas flash kernel.

The first `n_dense_layers` layers have a gated feed-forward
`W2(silu(W1 x) * W3 x)` of width `d_dense`; every other layer a routed
expert layer that drops nothing (layers.moe_dropless): a sigmoid router
over all `n_experts`, `top_k` a token by score plus a per-expert bias,
chosen scores normalised. `experts_held = (first, count)` tells each
expert layer which experts it holds, as one rank of an expert-parallel
job does; it then returns its own experts' part of the result.

Parameter names are explicit (`l{i}_*`), so a reference's weights can
be written over the startup program's by name. Each expert layer leaves
`layer{i}_moe_chosen`, `layer{i}_moe_load` and
`layer{i}_moe_pairs_here` to be fetched; unfetched they cost nothing.
Device scopes: `lfm2.conv`, `lfm2.attn`, `lfm2.moe.route`,
`lfm2.moe.experts`, `lfm2.moe.combine`.
"""
from __future__ import annotations

from .. import layers
from ..core.program import device_scope
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr

# one period of the published layer_types after the leading dense
# layers, which are "conv"
PERIOD = ("attention", "conv", "conv", "conv")


def default_layer_types(n_layers, n_dense_layers):
    """["conv"] * n_dense_layers, then PERIOD repeated."""
    return ["conv"] * n_dense_layers + [
        PERIOD[i % len(PERIOD)] for i in range(n_layers - n_dense_layers)]


def _linear(x, size, name, fan_in):
    return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                     param_attr=ParamAttr(
                         name=name, initializer=NormalInitializer(
                             0.0, fan_in ** -0.5)))


def conv_mixer(x, d_model, taps, name):
    with device_scope("lfm2.conv"):
        bcz = _linear(x, 3 * d_model, f"{name}_conv_in.w", d_model)
        y = layers.short_conv(bcz, taps=taps,
                              param_attr=f"{name}_conv.k")
        return _linear(y, d_model, f"{name}_conv_out.w", d_model)


def attention_mixer(x, d_model, n_heads, n_kv_heads, rope_theta, eps,
                    name):
    head = d_model // n_heads
    with device_scope("lfm2.attn"):
        qkv = _linear(x, (n_heads + 2 * n_kv_heads) * head,
                      f"{name}_attn_qkv.w", d_model)
        q, k, v = layers.split(
            qkv, [n_heads * head, n_kv_heads * head, n_kv_heads * head],
            dim=2)
        q = layers.reshape(q, [0, 0, n_heads, head])
        k = layers.reshape(k, [0, 0, n_kv_heads, head])
        v = layers.reshape(v, [0, 0, n_kv_heads, head])
        q = layers.rms_norm(q, eps, param_attr=f"{name}_attn_qnorm.w")
        k = layers.rms_norm(k, eps, param_attr=f"{name}_attn_knorm.w")
        q = layers.rotary_embedding(q, theta=rope_theta)
        k = layers.rotary_embedding(k, theta=rope_theta)
        ctx = layers.attention(q, k, v, causal=True, scale=head ** -0.5,
                               layout="bthd")
        ctx = layers.reshape(ctx, [0, 0, d_model])
        return _linear(ctx, d_model, f"{name}_attn_out.w", d_model)


def dense_ff(x, d_model, d_inner, name):
    h = layers.swiglu(_linear(x, 2 * d_inner, f"{name}_ff_w13.w",
                              d_model))
    return _linear(h, d_model, f"{name}_ff_w2.w", d_inner)


def lfm2_moe(ids, label, vocab, d_model=2048, n_heads=32, n_kv_heads=8,
             n_layers=40, n_dense_layers=2, d_dense=11776, d_expert=1536,
             n_experts=64, top_k=4, experts_held=None, conv_taps=3,
             rope_theta=1e6, norm_eps=1e-5, norm_topk=True,
             routed_scaling=1.0, layer_types=None):
    """ids, label: [B, T] int64 (label the next token). Returns
    (avg_cost, logits)."""
    kinds = layer_types or default_layer_types(n_layers, n_dense_layers)
    if len(kinds) != n_layers:
        raise ValueError(f"{len(kinds)} layer types for {n_layers} layers")
    table = ParamAttr(name="tok_emb", initializer=NormalInitializer(
        0.0, d_model ** -0.5))
    x = layers.embedding(ids, size=[vocab, d_model], param_attr=table)
    for i, kind in enumerate(kinds):
        name = f"l{i}"
        u = layers.rms_norm(x, norm_eps, param_attr=f"{name}_norm1.w")
        if kind == "conv":
            mixed = conv_mixer(u, d_model, conv_taps, name)
        elif kind == "attention":
            mixed = attention_mixer(u, d_model, n_heads, n_kv_heads,
                                    rope_theta, norm_eps, name)
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        x = layers.elementwise_add(x, mixed)
        u = layers.rms_norm(x, norm_eps, param_attr=f"{name}_norm2.w")
        if i < n_dense_layers:
            ff = dense_ff(u, d_model, d_dense, name)
        else:
            ff = layers.moe_dropless(
                u, n_experts, d_expert, top_k, experts_held=experts_held,
                norm_topk=norm_topk, scaling=routed_scaling,
                name=f"layer{i}_moe", scope="lfm2.moe")[0]
        x = layers.elementwise_add(x, ff)
    x = layers.rms_norm(x, norm_eps, param_attr="out_norm.w")
    emb = x.block.program.global_block.var("tok_emb")
    logits = layers.matmul(x, emb, transpose_y=True)
    cost = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(label, [2]))
    return layers.mean(cost), logits


def build_program(seq_len=8192, vocab=65536,
                  learning_rate=1e-4, beta1=0.9, beta2=0.997,
                  epsilon=1e-9, with_optimizer=True, **model):
    """(main, startup, avg_cost) of the training program: next-token
    cross-entropy over the vocabulary, no auxiliary loss, Adam at a
    constant rate. `model` goes to `lfm2_moe`. main._moe_layers lists
    the indices of the expert layers (their `layer{i}_moe_*` variables
    can be fetched)."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("ids", shape=[seq_len], dtype="int64")
        label = layers.data("label", shape=[seq_len], dtype="int64")
        avg_cost, _ = lfm2_moe(ids, label, vocab, **model)
        if with_optimizer:
            fluid.optimizer.Adam(
                learning_rate=learning_rate, beta1=beta1, beta2=beta2,
                epsilon=epsilon).minimize(avg_cost)
    n_layers = model.get("n_layers", 40)
    main._moe_layers = list(range(model.get("n_dense_layers", 2),
                                  n_layers))
    return main, startup, avg_cost
