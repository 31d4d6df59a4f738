"""Nemotron-3-Super (`model_type` `nemotron_h`; published configuration
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json)
on the serve engine: a decoder-only hybrid whose layers are, by a
pattern string, state-space mixers (`M`, Mamba-2), attention (`*`,
grouped queries over a paged key-value cache) or expert layers (`E`,
routed experts in a latent width beside a shared expert). No reference
counterpart: Fluid 1.x has no such model. The layers are here; the serve
programs around them are `decode_engine.build_decoder_only_bundle`'s,
shared with models/glm_moe_dsa.py.

Every layer is `h = h + mixer(RMSNorm(h))` with ONE mixer and nothing
else; after the last layer one more RMSNorm and an untied head.

* `M` (layers.causal_conv_tail / mamba2_scan / gated_group_rms_norm):
  `[z | xBC | dt] = u W_in`; the convolution and the recurrence read and
  leave per-LANE state: a scan state `[H, P, N]` float32 and the last
  `kernel - 1` inputs of the convolution, `ssm_state_<i>` and
  `conv_tail_<i>` in the slot state, indexed by lane and not by block
  table. A prefill chunk runs the chunked form from the lane's stored
  state (zeros where the chunk starts at position 0); a tick is one
  step of every live lane.
* `*` (layers.paged_decode_attention with `n_kv_heads` /
  paged_prefill_attention): no rotary embedding (the state-space layers
  carry position); keys and values in pools `[NB*BS, Hkv*Dh]` behind
  the lane's block table.
* `E` (layers.moe_dropless with `activation="relu2"` and an
  `expert_input`): the router reads the hidden state, the experts its
  latent projection `u W_down`; the routed part goes back up through
  `W_up`; the shared expert works on the full width.

Parameter names are explicit (`n{i}_*`, `nem_emb`, `nem_out_norm.w`,
`nem_head.w`). Device scopes: `nemotronh.ssm`, `nemotronh.attn`,
`nemotronh.moe` in a tick; everything a prefill chunk runs is under
`nemotronh.prefill_chunk`, its state-space mixers under
`nemotronh.prefill_chunk/nemotronh.ssm_scan`.
"""
from __future__ import annotations

from .. import layers
from ..core.program import device_scope
from ..param_attr import ParamAttr
from .decode_engine import POOL_MARK, build_decoder_only_bundle

DEFAULT_CHUNKS = (128, 512, 2048)
CHUNK_SCOPE = "nemotronh.prefill_chunk"
TICK_SCOPES = {"M": "nemotronh.ssm", "*": "nemotronh.attn",
               "E": "nemotronh.moe"}


def _linear(x, size, name):
    return layers.fc(x, size, bias_attr=False,
                     param_attr=ParamAttr(name=name))


def conv_width(m):
    """Numbers a position the convolution sees: x, B and C."""
    return m["ssm_heads"] * m["ssm_head_dim"] \
        + 2 * m["ssm_groups"] * m["ssm_state"]


def _ssm_mixer(u, name, sv, li, m, chunk, gate, pos):
    p = m["state_prefix"]
    d_inner = m["ssm_heads"] * m["ssm_head_dim"]
    where = dict(chunk=chunk, gate=gate, pos=pos)
    z, xbc, dt = layers.split(
        _linear(u, d_inner + conv_width(m) + m["ssm_heads"],
                f"{name}_in_proj.w"),
        [d_inner, conv_width(m), m["ssm_heads"]], dim=-1)
    xbc = layers.causal_conv_tail(xbc, sv[f"{p}conv_tail_{li}"],
                                  m["conv_kernel"], f"{name}_conv",
                                  **where)
    y = layers.mamba2_scan(xbc, dt, sv[f"{p}ssm_state_{li}"],
                           m["ssm_groups"], name, block=m["scan_block"],
                           **where)
    y = layers.gated_group_rms_norm(y, z, m["ssm_groups"], m["norm_eps"],
                                    f"{name}_ssm_norm.w")
    return _linear(y, m["d_model"], f"{name}_out_proj.w")


def _attention(u, name, sv, li, m, chunk, gate, pos, cell, tab):
    p, bs = m["state_prefix"], m["block_size"]
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pool_k = sv[f"{p}k{li}{POOL_MARK}"]
    pool_v = sv[f"{p}v{li}{POOL_MARK}"]
    q = _linear(u, h * dh, f"{name}_q.w")
    for pool, w in ((pool_k, "k"), (pool_v, "v")):
        layers.masked_pool_write(pool, _linear(u, kv * dh, f"{name}_{w}.w"),
                                 cell, gate=gate, leading_dims=1,
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


def _experts(u, name, m, scope, chunk):
    routed, idx, _load, _pairs = layers.moe_dropless(
        u, m["n_experts"], m["d_expert"], m["top_k"],
        experts_held=(m["first_held"], m["experts_held"]),
        norm_topk=m["norm_topk"], scaling=m["routed_scaling"],
        name=f"{name}_moe", scope=scope + ".moe" if chunk else scope,
        activation="relu2",
        expert_input=_linear(u, m["d_latent"], f"{name}_lat_down.w"))
    shared = _linear(
        layers.relu2(_linear(u, m["d_shared"], f"{name}_sh_w1.w")),
        m["d_model"], f"{name}_sh_w2.w")
    return layers.elementwise_add(
        _linear(routed, m["d_model"], f"{name}_lat_up.w"), shared), idx


def nemotron_stack(sv, x, pos, cell, gate, tab, chunk, m):
    """The layers of `m["layers"]` on rows x [N, D] (see
    decode_engine.build_decoder_only_bundle for the arguments). Returns
    (x, {}, {expert layer: chosen [N, top_k]})."""
    chosen = {}
    for li, kind in enumerate(m["layers"]):
        name = f"n{li}"
        if chunk is None:
            scope = TICK_SCOPES[kind]
        elif kind == "M":
            scope = f"{CHUNK_SCOPE}/nemotronh.ssm_scan"
        else:
            scope = CHUNK_SCOPE
        with device_scope(scope):
            u = layers.rms_norm(x, m["norm_eps"],
                                param_attr=f"{name}_norm.w")
            if kind == "M":
                y = _ssm_mixer(u, name, sv, li, m, chunk, gate, pos)
            elif kind == "*":
                y = _attention(u, name, sv, li, m, chunk, gate, pos, cell,
                               tab)
            else:
                y, chosen[li] = _experts(u, name, m, scope, chunk)
            x = layers.elementwise_add(x, y)
    return x, {}, chosen


def _layer_specs(m, rows, cells):
    """What each layer keeps in the slot state: a state-space layer its
    lanes' scan state and convolution tail, an attention layer its key
    and value pools, an expert layer nothing of its own."""
    p, dt = m["state_prefix"], m["dtype"]
    out = []
    for li, kind in enumerate(m["layers"]):
        if kind == "M":
            out.append({
                f"{p}ssm_state_{li}": (
                    (rows, m["ssm_heads"], m["ssm_head_dim"],
                     m["ssm_state"]), m["state_dtype"]),
                f"{p}conv_tail_{li}": (
                    (rows, m["conv_kernel"] - 1, conv_width(m)), dt)})
        elif kind == "*":
            width = m["n_kv_heads"] * m["head_dim"]
            out.append({f"{p}{w}{li}{POOL_MARK}": ((cells, width), dt)
                        for w in "kv"})
        else:
            out.append({})
    return out


def build_nemotron_h_serve_bundle(vocab, d_model, layers_pattern,
                                  ssm_heads, ssm_head_dim, ssm_groups,
                                  ssm_state, conv_kernel, n_heads,
                                  n_kv_heads, head_dim, n_experts, top_k,
                                  d_expert, d_latent, d_shared,
                                  experts_held=None, first_held=0,
                                  norm_topk=True, routed_scaling=1.0,
                                  norm_eps=1e-5, scan_block=128,
                                  dtype="bfloat16",
                                  state_dtype="float32", n_slots=8,
                                  block_size=64, n_blocks=64, context=None,
                                  max_new_tokens=64,
                                  chunk_sizes=DEFAULT_CHUNKS, max_chunks=8,
                                  end_id=1, probe_logits=False,
                                  state_prefix="@nem/"):
    """The decoder-only serve bundle (DecoderOnlyStepBundle) of a
    nemotron_h stack whose layers `layers_pattern` spells (`M`, `*`,
    `E`): `n_slots` lanes and the dustbin row, each with the state of
    every `M` layer (`bundle.lane_state`: names, shapes, bytes a lane)
    and a block table of `context / block_size` pages over `n_blocks`
    blocks for the `*` layers' keys and values. `scan_block`: positions
    a block of the chunked scan (the published `chunk_size`). The
    other arguments as
    models/glm_moe_dsa.py has them."""
    pattern = str(layers_pattern)
    if not pattern or set(pattern) - set(TICK_SCOPES):
        raise ValueError(f"layers_pattern {pattern!r}: a string of "
                         f"{', '.join(TICK_SCOPES)}")
    if ssm_heads % ssm_groups or n_heads % n_kv_heads:
        raise ValueError(
            f"{ssm_heads} state-space heads in {ssm_groups} groups, "
            f"{n_heads} query heads over {n_kv_heads}: each has to "
            f"divide")
    context = context or block_size * 8
    m = dict(vocab=vocab, d_model=d_model, layers=pattern,
             ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim,
             ssm_groups=ssm_groups, ssm_state=ssm_state,
             conv_kernel=conv_kernel, n_heads=n_heads,
             n_kv_heads=n_kv_heads, head_dim=head_dim,
             n_experts=n_experts, top_k=top_k, d_expert=d_expert,
             d_latent=d_latent, d_shared=d_shared,
             experts_held=experts_held or n_experts,
             first_held=first_held, norm_topk=norm_topk,
             routed_scaling=routed_scaling, norm_eps=norm_eps,
             scan_block=scan_block, dtype=dtype,
             state_dtype=state_dtype, block_size=block_size,
             state_prefix=state_prefix)
    specs = _layer_specs(m, n_slots + 1, n_blocks * block_size)
    bundle = build_decoder_only_bundle(
        lambda sv, *rows: nemotron_stack(sv, *rows, m), specs,
        state_prefix=state_prefix, vocab=vocab, d_model=d_model,
        dtype=dtype, norm_eps=norm_eps,
        top_names=("nem_emb", "nem_out_norm.w", "nem_head.w"),
        moe_layers=[li for li, kind in enumerate(pattern) if kind == "E"],
        first_held=first_held, experts_held=m["experts_held"],
        top_k=top_k, n_slots=n_slots, block_size=block_size,
        n_blocks=n_blocks, context=context,
        max_new_tokens=max_new_tokens, chunk_sizes=chunk_sizes,
        max_chunks=max_chunks, end_id=end_id, probe_logits=probe_logits,
        chunk_scope=CHUNK_SCOPE, probe_top_logit=True,
        lane_state=[name for li, kind in enumerate(pattern)
                    if kind == "M" for name in specs[li]])
    bundle.model = m
    return bundle
