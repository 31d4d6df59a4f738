"""GLM-5.2 (`model_type` `glm_moe_dsa`; published configuration
https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json) on the
serve engine: a decoder-only stack with latent attention (MLA) over a
paged latent cache, a learned sparse-attention indexer whose selection
the layers that follow share (DSA with IndexShare), and routed experts
beside a shared expert. No reference counterpart: Fluid 1.x has no such
model; the layers are here, and the serve programs around them (slot
state, tick, prefill chunks, admission, the While of ticks) are
`decode_engine.build_decoder_only_bundle`'s, which every decoder-only
builder shares.

Every layer is `h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h))`, no
biases, one more RMSNorm before an untied head.

* Attention (layers.mla_project / sparse_latent_attention /
  mla_output): the cache holds `[RMSNorm(c_KV) | rotated k_r]` a
  position a layer, `kv_lora_rank + qk_rope_head_dim` numbers and no
  heads. The key up-projection is absorbed into the query and the value
  up-projection applied after the weighted sum, so a head's score is
  one product with a cache row, in a decode tick and in a prefill chunk
  alike.
* Indexer (layers.dsa_indexer_project / dsa_indexer_scores /
  dsa_select), in the layers whose `indexer_types` entry is "full": a
  second pool of `index_head_dim` numbers a position behind the same
  block table; every cached position of the lane is scored and the
  `index_topk` largest are the query's selection; a "shared" layer uses
  the selection of the nearest "full" layer below it.
* Feed-forward: the first `n_dense_layers` a gated SiLU of `d_dense`;
  the others `layers.moe_dropless` (sigmoid router over all
  `n_experts`, `top_k` a token, `experts_held` = (first, count) as one
  rank of an expert-parallel job holds them) plus the shared expert.

Parameter names are explicit (`g{i}_*`, `glm_emb`, `glm_out_norm.w`,
`glm_head.w`), so a reference's weights can be written into the scope
by name. Device scopes: `glm.mla_proj`, `glm.indexer`, `glm.select`,
`glm.sparse_attn`, `glm.moe` (the expert layer's `.route`, `.experts`,
`.combine` and the shared expert) in a tick; everything a prefill chunk
runs is under `glm.prefill_chunk`.
"""
from __future__ import annotations

from .. import layers
from ..core.program import device_scope
from ..param_attr import ParamAttr
from .decode_engine import (POOL_MARK, DecoderOnlyStepBundle,
                            build_decoder_only_bundle)

DEFAULT_CHUNKS = (64, 256, 1024)
PREFILL = DecoderOnlyStepBundle.PREFILL


def _linear(x, size, name):
    return layers.fc(x, size, bias_attr=False,
                     param_attr=ParamAttr(name=name))


def _gated(x, width, d_model, w13, w2):
    return _linear(layers.swiglu(_linear(x, 2 * width, w13)), d_model, w2)


def row_width(m):
    """Numbers a row of a latent pool holds: the latent and the rotated
    key, rounded up to whole tiles of 128 lanes. At 576 the compiler
    wants the pool stored the other way round and copies every pool
    whole into and out of a dispatch (PERF.md, PR 32); the 64 numbers
    of padding are zeros that no score sees."""
    return -(-(m["kv_lora_rank"] + m["qk_rope_head_dim"]) // 128) * 128


def glm_stack(x, pos, cell, gate, tab, pools, m, chunk=False):
    """The layers on rows x [N, D] at cache positions pos [N], whose
    pool rows are cell [N] (written where gate [N] is 1) under the
    block-table rows tab [G, NP] (N = G * n). `pools`: layer ->
    (latent pool, indexer-key pool or None). `chunk`: the rows are one
    lane's (a prefill chunk), and the selection is kept as a threshold
    on the indexer's scores. Returns (x, {layer: the selection [N, K]
    it attended} (a tick's), {expert layer: chosen [N, top_k]})."""
    d, bs = m["d_model"], m["block_size"]
    rkv, dn, dr = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                   m["qk_rope_head_dim"])
    scale = (dn + dr) ** -0.5
    eps, theta = m["norm_eps"], m["rope_theta"]
    sel, sel_cells, selected, chosen = None, None, {}, {}
    width = row_width(m)

    def write(pool, rows):
        layers.masked_pool_write(pool, rows, cell, gate=gate,
                                 leading_dims=1,
                                 exclusive_via="block_table")

    for li, kind in enumerate(m["indexer_types"]):
        name = f"g{li}"
        lat_pool, idx_pool = pools[li]
        u = layers.rms_norm(x, eps, param_attr=f"{name}_norm1.w")
        q_lat, c_q, latent, kv_b = layers.mla_project(
            u, pos, m["n_heads"], m["q_lora_rank"], rkv, dn, dr,
            m["v_head_dim"], rope_theta=theta, epsilon=eps,
            row_width=width, name=name)
        write(lat_pool, latent)
        if kind == "full":
            q_i, k_i, w = layers.dsa_indexer_project(
                u, c_q, pos, m["index_n_heads"], m["index_head_dim"],
                m["index_rope_dim"], rope_theta=theta, name=name)
            write(idx_pool, k_i)
            scores = layers.dsa_indexer_scores(q_i, w, idx_pool, tab,
                                               pos, bs)
            if chunk:
                # many queries of one lane: the selection as a
                # threshold, and attention reads the context once
                sel = (scores, layers.dsa_select(
                    scores, m["index_topk"], mode="threshold"))
            else:
                sel = layers.dsa_select(scores, m["index_topk"])
                # the selected positions' pool rows, once for the
                # layers that share the selection
                sel_cells = layers.paged_cell_index(
                    tab, layers.reshape(sel, [-1]), bs)
        if not chunk:
            selected[li] = sel      # what this layer attends
        ctx = layers.sparse_latent_attention(q_lat, lat_pool, tab, sel,
                                             bs, rkv, scale=scale,
                                             k=m["index_topk"],
                                             cells=sel_cells)
        x = layers.elementwise_add(
            x, _linear(layers.mla_output(ctx, kv_b, dn), d,
                       f"{name}_o.w"))
        u = layers.rms_norm(x, eps, param_attr=f"{name}_norm2.w")
        if li < m["n_dense_layers"]:
            ff = _gated(u, m["d_dense"], d, f"{name}_ff_w13.w",
                        f"{name}_ff_w2.w")
        else:
            scope = "glm.prefill_chunk" if chunk else "glm.moe"
            with device_scope(scope):
                routed, idx, _load, _pairs = layers.moe_dropless(
                    u, m["n_experts"], m["d_expert"], m["top_k"],
                    experts_held=(m["first_held"], m["experts_held"]),
                    norm_topk=m["norm_topk"],
                    scaling=m["routed_scaling"], name=f"{name}_moe",
                    scope=scope + ".moe" if chunk else scope)
                ff = layers.elementwise_add(routed, _gated(
                    u, m["d_expert"] * m["n_shared_experts"], d,
                    f"{name}_sh_w13.w", f"{name}_sh_w2.w"))
            chosen[li] = idx
        x = layers.elementwise_add(x, ff)
    return x, selected, chosen


def _layer_specs(prefix, rows, cells, m, context):
    """What each layer keeps in the slot state: its latent pool, its
    indexer-key pool where it owns an indexer, and what the lane's last
    tick attended in it (its own selection, or the one it shares)."""
    dt = m["dtype"]
    out = []
    for li, kind in enumerate(m["indexer_types"]):
        layer = {f"{prefix}lat{li}{POOL_MARK}": ((cells, row_width(m)), dt)}
        if kind == "full":
            layer[f"{prefix}idx{li}{POOL_MARK}"] = (
                (cells, m["index_head_dim"]), dt)
        layer[f"{prefix}sel_last{li}"] = (
            (rows, min(m["index_topk"], context)), "int32")
        out.append(layer)
    return out


def build_glm_serve_bundle(vocab, d_model, n_heads, q_lora_rank,
                           kv_lora_rank, qk_nope_head_dim,
                           qk_rope_head_dim, v_head_dim, index_n_heads,
                           index_head_dim, index_rope_dim, index_topk,
                           indexer_types, d_dense, d_expert, n_experts,
                           top_k, n_layers=None, n_dense_layers=1,
                           n_shared_experts=1, experts_held=None,
                           first_held=0, norm_topk=True,
                           routed_scaling=1.0, rope_theta=10000.0,
                           norm_eps=1e-5, dtype="bfloat16", n_slots=8,
                           block_size=64, n_blocks=64, context=None,
                           max_new_tokens=64, chunk_sizes=DEFAULT_CHUNKS,
                           max_chunks=8, end_id=1, probe_logits=False,
                           state_prefix="@glm/"):
    """The decoder-only serve bundle (DecoderOnlyStepBundle) of a
    glm_moe_dsa stack: `n_slots` lanes and the dustbin row over one
    block table of `context / block_size` pages a lane, `n_blocks`
    blocks of `block_size` positions in every pool. `chunk_sizes`: the
    prefill program's chunk lengths (a chunk is padded to the smallest
    that holds it); `max_chunks`: chunks of each size, and admissions,
    a dispatch;
    `probe_logits` keeps every tick's logits of every lane in the
    state (a test's probe: rows x tokens x vocabulary floats)."""
    indexer_types = list(indexer_types)
    n_layers = len(indexer_types) if n_layers is None else n_layers
    if len(indexer_types) != n_layers or indexer_types[0] != "full":
        raise ValueError(
            f"indexer_types {indexer_types} for {n_layers} layers; the "
            f"first layer has to own an indexer")
    context = context or block_size * 8
    m = dict(vocab=vocab, d_model=d_model, n_heads=n_heads,
             q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
             qk_nope_head_dim=qk_nope_head_dim,
             qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
             index_n_heads=index_n_heads, index_head_dim=index_head_dim,
             index_rope_dim=index_rope_dim, index_topk=index_topk,
             indexer_types=indexer_types, d_dense=d_dense,
             d_expert=d_expert, n_experts=n_experts, top_k=top_k,
             n_dense_layers=n_dense_layers,
             n_shared_experts=n_shared_experts,
             experts_held=experts_held or n_experts,
             first_held=first_held, norm_topk=norm_topk,
             routed_scaling=routed_scaling, rope_theta=rope_theta,
             norm_eps=norm_eps, dtype=dtype, block_size=block_size)
    p = state_prefix

    def stack(sv, x, pos, cell, gate, tab, chunk):
        pools = {li: (sv[f"{p}lat{li}{POOL_MARK}"],
                      sv.get(f"{p}idx{li}{POOL_MARK}"))
                 for li in range(n_layers)}
        return glm_stack(x, pos, cell, gate, tab, pools, m,
                         chunk=chunk is not None)

    bundle = build_decoder_only_bundle(
        stack, _layer_specs(p, n_slots + 1, n_blocks * block_size, m,
                            context),
        state_prefix=p, vocab=vocab, d_model=d_model, dtype=dtype,
        norm_eps=norm_eps,
        top_names=("glm_emb", "glm_out_norm.w", "glm_head.w"),
        moe_layers=[li for li in range(n_layers) if li >= n_dense_layers],
        first_held=first_held, experts_held=m["experts_held"],
        top_k=top_k, n_slots=n_slots, block_size=block_size,
        n_blocks=n_blocks, context=context,
        max_new_tokens=max_new_tokens, chunk_sizes=chunk_sizes,
        max_chunks=max_chunks, end_id=end_id, probe_logits=probe_logits,
        chunk_scope="glm.prefill_chunk",
        selected_probes={li: f"{p}sel_last{li}" for li in range(n_layers)},
        selection_size=min(index_topk, context))
    bundle.model = m
    return bundle
