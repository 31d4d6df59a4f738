"""Olmo-Hybrid-7B (`model_type` `olmo_hybrid`; published configuration
https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json) on
the serve engine: a decoder-only hybrid whose layers are, by a pattern
string, gated delta-rule linear attention (`L`, Gated DeltaNet) or full
attention (`F`, over a paged key-value cache), each followed by a
SwiGLU MLP. No reference counterpart: Fluid 1.x has no such model. The
serve programs around the layers are
`decode_engine.build_decoder_only_bundle`'s, shared with
models/nemotron_h.py and models/glm_moe_dsa.py.

Every layer is Olmo 2's post-norm pair: `h = x + RMSNorm(mixer(x))`,
`out = h + RMSNorm(MLP(h))`, `MLP(h) = W_down(silu(W_gate h) * W_up
h)`; after the last layer one more RMSNorm and an untied head.

* `L` (layers.causal_conv_tail / gated_delta / gated_group_rms_norm):
  `[q | k | v | z | b | a] = x W_in`; q, k and v through a causal
  depthwise convolution of `conv_kernel` taps without bias and SiLU,
  whose last `kernel - 1` inputs are per-LANE state (`conv_tail_<i>`);
  the delta rule from and into the lane's matrix state `gdn_state_<i>`
  [H, Dk, Dv] float32 (ops/delta_ops.py), a prefill chunk by the
  chunked form from the lane's stored state (zeros where the chunk
  starts at position 0), a tick by one step of every live lane; the
  output normed per head with one shared weight and THEN gated by
  silu(z) (`norm_before_gate`); out `W_o`.
* `F` (layers.rope_at / paged_decode_attention / paged_prefill_
  attention): q, k and v; QK-norm, an RMS norm over the whole q and
  the whole k projection; rotary positions at the rows' cache
  positions; keys and values in pools `[NB*BS, Hkv*Dh]` behind the
  lane's block table (a tick of the cell's size reads them one lane at
  a time over its live pages: `paged_ops.paged_attention_by_lane`).

Parameter names are explicit (`o{i}_*`, `olmoh_emb`,
`olmoh_out_norm.w`, `olmoh_head.w`). Device scopes: `olmoh.gdn`,
`olmoh.attn`, `olmoh.mlp` in a tick; everything a prefill chunk runs is
under `olmoh.prefill_chunk`, its linear-attention mixers under
`olmoh.prefill_chunk/olmoh.gdn_chunk`.
"""
from __future__ import annotations

from .. import layers
from ..core.program import device_scope
from ..param_attr import ParamAttr
from .decode_engine import POOL_MARK, build_decoder_only_bundle

DEFAULT_CHUNKS = (256, 1024, 2048)
CHUNK_SCOPE = "olmoh.prefill_chunk"
GDN_CHUNK_SCOPE = f"{CHUNK_SCOPE}/olmoh.gdn_chunk"
TICK_SCOPES = {"L": "olmoh.gdn", "F": "olmoh.attn", "mlp": "olmoh.mlp"}


def _linear(x, size, name):
    return layers.fc(x, size, bias_attr=False,
                     param_attr=ParamAttr(name=name))


def conv_width(m):
    """Numbers a position the convolution sees: q, k and v."""
    return m["lin_heads"] * (2 * m["lin_key_dim"] + m["lin_value_dim"])


def _gdn_mixer(x, name, sv, li, m, chunk, gate, pos):
    p, heads = m["state_prefix"], m["lin_heads"]
    hk, hv = heads * m["lin_key_dim"], heads * m["lin_value_dim"]
    where = dict(chunk=chunk, gate=gate, pos=pos)
    qkv, z, beta, a = layers.split(
        _linear(x, conv_width(m) + hv + 2 * heads, f"{name}_in_proj.w"),
        [conv_width(m), hv, heads, heads], dim=-1)
    qkv = layers.causal_conv_tail(qkv, sv[f"{p}conv_tail_{li}"],
                                  m["conv_kernel"], f"{name}_conv",
                                  bias=False, **where)
    q, k, v = layers.split(qkv, [hk, hk, hv], dim=-1)
    y = layers.gated_delta(q, k, v, beta, a, sv[f"{p}gdn_state_{li}"],
                           name, block=m["delta_block"],
                           allow_neg_eigval=m["allow_neg_eigval"], **where)
    y = layers.gated_group_rms_norm(y, z, heads, m["norm_eps"],
                                    f"{name}_gdn_norm.w",
                                    norm_before_gate=True)
    return _linear(y, m["d_model"], f"{name}_out_proj.w")


def _attention(x, name, sv, li, m, chunk, gate, pos, cell, tab):
    p, bs = m["state_prefix"], m["block_size"]
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pool_k = sv[f"{p}k{li}{POOL_MARK}"]
    pool_v = sv[f"{p}v{li}{POOL_MARK}"]
    q, k, v = layers.split(_linear(x, (h + 2 * kv) * dh, f"{name}_qkv.w"),
                           [h * dh, kv * dh, kv * dh], dim=-1)
    q, k = (layers.rope_at(
        layers.rms_norm(t, m["norm_eps"], param_attr=f"{name}_{w}_norm.w"),
        pos, heads, m["rope_theta"])
        for t, w, heads in ((q, "q", h), (k, "k", kv)))
    for pool, new in ((pool_k, k), (pool_v, v)):
        layers.masked_pool_write(pool, new, cell, gate=gate, leading_dims=1,
                                 exclusive_via="block_table")
    if chunk is not None:
        ctx = layers.paged_prefill_attention(
            q, pool_k, pool_v, tab, pos, bs, h, kv, scale=dh ** -0.5)
    else:
        rows = q.shape[0]
        ctx = layers.reshape(layers.paged_decode_attention(
            layers.reshape(q, [rows, 1, h * dh]), pool_k, pool_v, tab,
            pos, bs, h, scale=dh ** -0.5, n_kv_heads=kv), [rows, h * dh])
    return _linear(ctx, m["d_model"], f"{name}_o.w")


def _mlp(x, name, m):
    h = layers.swiglu(_linear(x, 2 * m["d_ff"], f"{name}_gate_up.w"))
    return _linear(h, m["d_model"], f"{name}_down.w")


def olmo_stack(sv, x, pos, cell, gate, tab, chunk, m):
    """The layers of `m["layers"]` on rows x [N, D] (see
    decode_engine.build_decoder_only_bundle for the arguments). Returns
    (x, {}, {})."""
    eps = m["norm_eps"]
    for li, kind in enumerate(m["layers"]):
        name = f"o{li}"
        if chunk is None:
            scope, mlp_scope = TICK_SCOPES[kind], TICK_SCOPES["mlp"]
        else:
            scope = GDN_CHUNK_SCOPE if kind == "L" else CHUNK_SCOPE
            mlp_scope = CHUNK_SCOPE
        with device_scope(scope):
            if kind == "L":
                y = _gdn_mixer(x, name, sv, li, m, chunk, gate, pos)
            else:
                y = _attention(x, name, sv, li, m, chunk, gate, pos, cell,
                               tab)
            x = layers.elementwise_add(x, layers.rms_norm(
                y, eps, param_attr=f"{name}_attn_post_norm.w"))
        with device_scope(mlp_scope):
            x = layers.elementwise_add(x, layers.rms_norm(
                _mlp(x, name, m), eps, param_attr=f"{name}_ff_post_norm.w"))
    return x, {}, {}


def _layer_specs(m, rows, cells):
    """What each layer keeps in the slot state: a linear-attention
    layer its lanes' delta-rule state and convolution tail, a full
    layer its key and value pools."""
    p, dt = m["state_prefix"], m["dtype"]
    out = []
    for li, kind in enumerate(m["layers"]):
        if kind == "L":
            out.append({
                f"{p}gdn_state_{li}": (
                    (rows, m["lin_heads"], m["lin_key_dim"],
                     m["lin_value_dim"]), m["state_dtype"]),
                f"{p}conv_tail_{li}": (
                    (rows, m["conv_kernel"] - 1, conv_width(m)), dt)})
        else:
            width = m["n_kv_heads"] * m["head_dim"]
            out.append({f"{p}{w}{li}{POOL_MARK}": ((cells, width), dt)
                        for w in "kv"})
    return out


def build_olmo_hybrid_serve_bundle(vocab, d_model, layers_pattern,
                                   lin_heads, lin_key_dim, lin_value_dim,
                                   conv_kernel, n_heads, n_kv_heads,
                                   head_dim, d_ff, rope_theta=500000.0,
                                   allow_neg_eigval=True, norm_eps=1e-6,
                                   delta_block=64, dtype="bfloat16",
                                   state_dtype="float32", n_slots=8,
                                   block_size=64, n_blocks=64,
                                   context=None, max_new_tokens=64,
                                   chunk_sizes=DEFAULT_CHUNKS,
                                   max_chunks=8, end_id=1,
                                   probe_logits=False,
                                   state_prefix="@olmoh/"):
    """The decoder-only serve bundle (DecoderOnlyStepBundle) of an
    olmo_hybrid stack whose layers `layers_pattern` spells (`L`, `F`):
    `n_slots` lanes and the dustbin row, each with the state of every
    `L` layer (`bundle.lane_state`: names, shapes, bytes a lane) and a
    block table of `context / block_size` pages over `n_blocks` blocks
    for the `F` layers' keys and values. `delta_block`: positions a
    block of the chunked delta rule. `allow_neg_eigval`: beta in (0, 2)
    (the published `linear_allow_neg_eigval`). The other arguments as
    models/nemotron_h.py has them."""
    pattern = str(layers_pattern)
    if not pattern or set(pattern) - {"L", "F"}:
        raise ValueError(f"layers_pattern {pattern!r}: a string of L, F")
    if n_heads % n_kv_heads or head_dim % 2:
        raise ValueError(
            f"{n_heads} query heads over {n_kv_heads}, heads of "
            f"{head_dim}: the one has to divide, the other be even")
    context = context or block_size * 8
    m = dict(vocab=vocab, d_model=d_model, layers=pattern,
             lin_heads=lin_heads, lin_key_dim=lin_key_dim,
             lin_value_dim=lin_value_dim, conv_kernel=conv_kernel,
             n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
             d_ff=d_ff, rope_theta=rope_theta,
             allow_neg_eigval=allow_neg_eigval, norm_eps=norm_eps,
             delta_block=delta_block, dtype=dtype,
             state_dtype=state_dtype, block_size=block_size,
             state_prefix=state_prefix)
    specs = _layer_specs(m, n_slots + 1, n_blocks * block_size)
    bundle = build_decoder_only_bundle(
        lambda sv, *rows: olmo_stack(sv, *rows, m), specs,
        state_prefix=state_prefix, vocab=vocab, d_model=d_model,
        dtype=dtype, norm_eps=norm_eps,
        top_names=("olmoh_emb", "olmoh_out_norm.w", "olmoh_head.w"),
        moe_layers=[], first_held=0, experts_held=1, top_k=1,
        n_slots=n_slots, block_size=block_size, n_blocks=n_blocks,
        context=context, max_new_tokens=max_new_tokens,
        chunk_sizes=chunk_sizes, max_chunks=max_chunks, end_id=end_id,
        probe_logits=probe_logits, chunk_scope=CHUNK_SCOPE,
        probe_top_logit=True,
        lane_state=[name for li, kind in enumerate(pattern)
                    if kind == "L" for name in specs[li]])
    bundle.model = m
    return bundle
