"""GLM-5.2 (`model_type` `glm_moe_dsa`; published configuration
https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json) on the
serve engine: a decoder-only stack with latent attention (MLA) over a
paged latent cache, a learned sparse-attention indexer whose selection
the layers that follow share (DSA with IndexShare), and routed experts
beside a shared expert. No reference counterpart: Fluid 1.x has no such
model; the bundle has the serve-program shape of
`decode_engine.build_decode_step_program` and shares its While, its
emit tail and its slot state with it.

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

import numpy as np

from .. import layers
from ..analysis import absint
from ..core.program import device_scope
from ..observability import devtel
from ..param_attr import ParamAttr
from .decode_engine import (POOL_MARK, CacheConfig, DecoderOnlyStepBundle,
                            build_serve_program, emit_lane_tokens,
                            lane_onehots, tel_add)

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


def _state_specs(prefix, rows, maxT, m, cache, context, probe_logits):
    dt = m["dtype"]
    cells = cache.n_blocks * cache.block_size
    specs = {
        f"{prefix}tok_buf": ((rows, maxT), "int64"),
        f"{prefix}step": ((rows,), "int64"),
        f"{prefix}finished": ((rows,), "int64"),
        f"{prefix}active": ((rows,), "int64"),
        # cache position of position 0 of a lane's token row, and how
        # many tokens the lane's request asked for
        f"{prefix}base": ((rows,), "int64"),
        f"{prefix}limit": ((rows,), "int64"),
        f"{prefix}block_tab": ((rows, context // cache.block_size),
                               "int32"),
        # what the live lanes of the ticks sent to the experts held
        # here: pairs, held experts with a pair, pairs an expert
        f"{prefix}moe_pairs": ((1,), "int64"),
        f"{prefix}moe_hit": ((1,), "int64"),
    }
    if probe_logits:
        specs[f"{prefix}logits_hist"] = ((rows, maxT, m["vocab"]),
                                         "float32")
    specs.update(devtel.counter_specs(prefix, True, chunked=True))
    for li, kind in enumerate(m["indexer_types"]):
        specs[f"{prefix}lat{li}{POOL_MARK}"] = ((cells, row_width(m)), dt)
        if kind == "full":
            specs[f"{prefix}idx{li}{POOL_MARK}"] = (
                (cells, m["index_head_dim"]), dt)
        # what the lane's last tick attended in this layer (its own
        # selection, or the one it shares)
        specs[f"{prefix}sel_last{li}"] = (
            (rows, min(m["index_topk"], context)), "int32")
        if li >= m["n_dense_layers"]:
            specs[f"{prefix}moe_load{li}"] = ((m["experts_held"],),
                                              "int64")
            specs[f"{prefix}chosen_hist{li}"] = (
                (rows, maxT, m["top_k"]), "int32")
    return specs


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
    import paddle_tpu as fluid

    indexer_types = list(indexer_types)
    chunk_sizes = tuple(sorted(set(int(c) for c in chunk_sizes)))
    n_layers = len(indexer_types) if n_layers is None else n_layers
    if len(indexer_types) != n_layers or indexer_types[0] != "full":
        raise ValueError(
            f"indexer_types {indexer_types} for {n_layers} layers; the "
            f"first layer has to own an indexer")
    context = context or block_size * 8
    if context % block_size:
        raise ValueError(f"block_size={block_size} must divide "
                         f"context={context}")
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
    cache = CacheConfig(layout="paged", block_size=block_size,
                        n_blocks=n_blocks, n_prompt_entries=1)
    rows, maxT = n_slots + 1, max_new_tokens + 1
    p = state_prefix
    specs = _state_specs(p, rows, maxT, m, cache, context, probe_logits)
    moe_layers = [li for li in range(n_layers) if li >= n_dense_layers]

    def mark(sv):
        absint.mark_pool_index_source(sv[f"{p}block_tab"], "block_table",
                                      bound=n_blocks)
        absint.mark_pool_index_source(sv[f"{p}active"], "lane_active")
        return sv

    def pools(sv):
        return {li: (sv[f"{p}lat{li}{POOL_MARK}"],
                     sv.get(f"{p}idx{li}{POOL_MARK}"))
                for li in range(n_layers)}

    def embed(toks):
        return layers.embedding(toks, size=[vocab, d_model], dtype=dtype,
                                param_attr=ParamAttr(name="glm_emb"))

    def add_to(var, delta):
        layers.assign(layers.elementwise_add(var, delta), output=var)

    def tick_body(sv):
        tok_buf, stepv = sv[f"{p}tok_buf"], sv[f"{p}step"]
        fin, act = sv[f"{p}finished"], sv[f"{p}active"]
        tel_add(sv, p, "tel_ticks",
                layers.fill_constant([1], "int64", 1.0))
        tel_add(sv, p, "tel_occupancy",
                layers.reduce_sum(act, keep_dim=True))
        positions = layers.cast(layers.range(0, maxT, 1), "int64")
        t_mask = layers.cast(
            layers.equal(positions, layers.reshape(stepv, [rows, 1])),
            "int64")
        cur_tok = layers.reduce_sum(
            layers.elementwise_mul(tok_buf, t_mask), dim=1,
            keep_dim=True)                                  # [R,1]
        pos = layers.elementwise_add(sv[f"{p}base"], stepv)
        tab = sv[f"{p}block_tab"]
        cell = layers.paged_cell_index(tab, pos, block_size)
        # idle, dustbin and prefilling lanes (act = 0) write nothing
        gate = layers.cast(act, "float32")
        x, selected, chosen = glm_stack(embed(cur_tok), pos, cell, gate,
                                        tab, pools(sv), m)
        logits = layers.lm_head(
            layers.rms_norm(x, norm_eps, param_attr="glm_out_norm.w"),
            vocab, "glm_head.w")
        tok = layers.cast(layers.argmax(logits, axis=-1), "int64")
        if probe_logits:
            layers.lane_probe_write(sv[f"{p}logits_hist"], logits, act,
                                    step=stepv)
        for li, sel in selected.items():
            layers.lane_probe_write(sv[f"{p}sel_last{li}"], sel, act)
        for li, idx in chosen.items():
            layers.lane_probe_write(sv[f"{p}chosen_hist{li}"], idx, act,
                                    step=stepv)
            pairs, hit, load = layers.moe_tick_stats(
                idx, act, first_held, m["experts_held"])
            add_to(sv[f"{p}moe_pairs"], pairs)
            add_to(sv[f"{p}moe_hit"], hit)
            add_to(sv[f"{p}moe_load{li}"], load)
        emit_lane_tokens(tok, tok_buf, stepv, fin, act, rows, maxT,
                         end_id, room_limit=sv[f"{p}limit"])

    def chunk_loop(sv, C, chunk_toks, chunk_lane, chunk_pos, chunk_len,
                   n_chunks):
        """The fed chunks of at most C tokens, one after another."""
        j = layers.fill_constant([1], "int64", 0)
        offs = layers.cast(layers.range(0, C, 1), "int64")
        cond = layers.less_than(j, n_chunks)
        loop = layers.While(cond)
        with loop.block(), device_scope("glm.prefill_chunk"):
            toks = layers.reshape(layers.gather(chunk_toks, j), [C, 1])
            n = layers.gather(chunk_len, j)
            pos = layers.elementwise_add(
                offs, layers.gather(chunk_pos, j))
            # rows past the chunk's length are padding: they write
            # nothing, and what they compute is dropped
            gate = layers.cast(layers.less_than(offs, n), "float32")
            tab = layers.gather(sv[f"{p}block_tab"],
                                layers.gather(chunk_lane, j))  # [1,NP]
            cell = layers.paged_cell_index(tab, pos, block_size)
            glm_stack(embed(toks), pos, cell, gate, tab, pools(sv), m,
                      chunk=True)
            tel_add(sv, p, "tel_chunks",
                    layers.fill_constant([1], "int64", 1.0))
            layers.increment(j, 1)
            layers.less_than(j, n_chunks, cond=cond)

    def prefill_body(sv):
        A = max_chunks

        def fed(name, shape):
            return layers.data(name, shape=shape, dtype="int64",
                               append_batch_size=False)

        # the largest chunks first: a lane's prompt is cut into whole
        # chunks of the largest size and one smaller rest, which has
        # to find them cached
        for C in sorted(chunk_sizes, reverse=True):
            chunk_loop(sv, C, fed(f"chunk_toks_{C}", [A, C]),
                       fed(f"chunk_lane_{C}", [A]),
                       fed(f"chunk_pos_{C}", [A]),
                       fed(f"chunk_len_{C}", [A]),
                       fed(f"n_chunks_{C}", [1]))
        slots, a_tok = fed("admit_slots", [A]), fed("admit_tok", [A])
        a_base, a_limit = fed("admit_base", [A]), fed("admit_limit", [A])
        # admission: the lanes whose prompt is cached now but for its
        # last token, which is position 0 of their token row
        oh, _, any_i, _, keep_i = lane_onehots(slots, A, rows)
        oh_i = layers.cast(oh, "int64")

        def scattered(v):       # [A] -> [rows]; the dustbin's is junk
            return layers.reduce_sum(layers.elementwise_mul(
                oh_i, layers.reshape(v, [A, 1])), dim=0)

        start_col = layers.assign(
            (np.arange(maxT) == 0).astype("int64"))
        keep_col = layers.reshape(keep_i, [rows, 1])
        tok_buf = sv[f"{p}tok_buf"]
        layers.assign(layers.elementwise_add(
            layers.elementwise_mul(tok_buf, keep_col),
            layers.elementwise_mul(
                layers.reshape(scattered(a_tok), [rows, 1]), start_col)),
            output=tok_buf)
        for name, new in (("step", None), ("finished", None),
                          ("base", a_base), ("limit", a_limit)):
            var = sv[f"{p}{name}"]
            kept = layers.elementwise_mul(var, keep_i)
            layers.assign(kept if new is None else
                          layers.elementwise_add(kept, scattered(new)),
                          output=var)
        valid = layers.assign(
            (np.arange(rows) < n_slots).astype("int64"))
        admitted = layers.elementwise_mul(any_i, valid)
        act = sv[f"{p}active"]
        layers.assign(layers.elementwise_add(
            layers.elementwise_mul(act, keep_i), admitted), output=act)
        tel_add(sv, p, "tel_admit_miss",
                layers.reduce_sum(admitted, keep_dim=True))

    serves = {0: build_serve_program(specs, p, lambda sv: None, tick_body,
                                     mark=mark)}
    serves[PREFILL] = build_serve_program(specs, p, prefill_body,
                                          tick_body, mark=mark)
    state = {k: f"{p}{k}" for k in
             ("tok_buf", "step", "finished", "active", "base", "limit",
              "block_tab", "moe_pairs", "moe_hit")}
    state.update(devtel.state_entries(p, True, chunked=True))
    state.update({f"moe_load{li}": f"{p}moe_load{li}"
                  for li in moe_layers})
    probes = {"selected": {li: f"{p}sel_last{li}"
                           for li in range(n_layers)},
              "chosen": {li: f"{p}chosen_hist{li}" for li in moe_layers}}
    if probe_logits:
        probes["logits"] = f"{p}logits_hist"
    bundle = DecoderOnlyStepBundle(
        serves, fluid.Program(), state, specs, n_slots, maxT, context,
        end_id, cache, chunk_sizes, max_chunks, probes=probes,
        selection_size=min(index_topk, context))
    bundle.model = m
    return bundle
