"""Layer wrappers for the round-2 op-gap ops.

Parity: reference python/paddle/fluid/layers/nn.py (pool3d,
conv3d_transpose, bilinear_tensor_product, rank_loss, random_crop,
add_position_encoding), layers/control_flow.py (lod_rank_table,
max_sequence_len, lod_tensor_to_array, array_to_lod_tensor,
shrink_memory, reorder_lod_tensor_by_rank, Print, is_empty),
layers/nn.py dynamic_lstmp.
"""
from __future__ import annotations

import numpy as np

from ..layer_helper import LayerHelper
from .sequence import SEQ_LEN_SUFFIX, seq_len_of

__all__ = ["pool3d", "conv3d_transpose", "bilinear_tensor_product",
           "rank_loss", "random_crop", "add_position_encoding",
           "dynamic_lstmp", "lod_rank_table", "max_sequence_len",
           "lod_tensor_to_array", "array_to_lod_tensor",
           "shrink_memory", "reorder_lod_tensor_by_rank", "Print",
           "is_empty", "spp", "unpool", "conv_shift", "data_norm",
           "modified_huber_loss", "squared_l2_distance",
           "teacher_student_sigmoid_loss", "max_pool2d_with_index",
           "max_pool3d_with_index"]


def _triple(v):
    return list(v) if isinstance(v, (list, tuple)) else [v] * 3


def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [v] * 2


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, exclusive=True, name=None):
    helper = LayerHelper("pool3d", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "pool3d", {"X": input}, {"Out": out},
        {"pooling_type": pool_type, "ksize": _triple(pool_size),
         "strides": _triple(pool_stride),
         "paddings": _triple(pool_padding),
         "global_pooling": global_pooling, "ceil_mode": ceil_mode,
         "exclusive": exclusive})
    return out


def max_pool2d_with_index(input, pool_size, pool_stride=1,
                          pool_padding=0, global_pooling=False,
                          name=None):
    helper = LayerHelper("max_pool2d_with_index", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    mask = helper.create_variable_for_type_inference("int32", True)
    helper.append_op(
        "max_pool2d_with_index", {"X": input},
        {"Out": out, "Mask": mask},
        {"ksize": _pair(pool_size), "strides": _pair(pool_stride),
         "paddings": _pair(pool_padding),
         "global_pooling": global_pooling})
    return out, mask


def max_pool3d_with_index(input, pool_size, pool_stride=1,
                          pool_padding=0, global_pooling=False,
                          name=None):
    helper = LayerHelper("max_pool3d_with_index", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    mask = helper.create_variable_for_type_inference("int32", True)
    helper.append_op(
        "max_pool3d_with_index", {"X": input},
        {"Out": out, "Mask": mask},
        {"ksize": _triple(pool_size), "strides": _triple(pool_stride),
         "paddings": _triple(pool_padding),
         "global_pooling": global_pooling})
    return out, mask


def unpool(input, indices, pool_size, pool_stride=2, pool_padding=0,
           name=None):
    helper = LayerHelper("unpool", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "unpool", {"X": input, "Indices": indices}, {"Out": out},
        {"ksize": _pair(pool_size), "strides": _pair(pool_stride),
         "paddings": _pair(pool_padding), "unpooling_type": "max"})
    return out


def spp(input, pyramid_height, pool_type="max", name=None):
    helper = LayerHelper("spp", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("spp", {"X": input}, {"Out": out},
                     {"pyramid_height": pyramid_height,
                      "pooling_type": pool_type})
    return out


def conv3d_transpose(input, num_filters, output_size=None,
                     filter_size=None, padding=0, stride=1, dilation=1,
                     groups=1, param_attr=None, bias_attr=None,
                     use_cudnn=True, act=None, name=None):
    helper = LayerHelper("conv3d_transpose", input=input,
                         param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    in_c = input.shape[1]
    fs = _triple(filter_size)
    w = helper.create_parameter(
        helper.param_attr, [in_c, num_filters // groups] + fs,
        input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "conv3d_transpose", {"Input": input, "Filter": w},
        {"Output": out},
        {"strides": _triple(stride), "paddings": _triple(padding),
         "dilations": _triple(dilation), "groups": groups})
    out = helper.append_bias_op(out, dim_start=1, dim_end=2)
    return helper.append_activation(out)


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    helper = LayerHelper("bilinear_tensor_product", input=x,
                         param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    dx, dy = x.shape[1], y.shape[1]
    w = helper.create_parameter(helper.param_attr, [size, dx, dy],
                                x.dtype)
    out = helper.create_variable_for_type_inference(x.dtype)
    ins = {"X": x, "Y": y, "Weight": w}
    if helper.bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr, [1, size],
                                    x.dtype, is_bias=True)
        if b is not None:
            ins["Bias"] = b
    helper.append_op("bilinear_tensor_product", ins, {"Out": out}, {})
    return helper.append_activation(out)


def rank_loss(label, left, right, name=None):
    helper = LayerHelper("rank_loss", input=label, name=name)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op("rank_loss",
                     {"Label": label, "Left": left, "Right": right},
                     {"Out": out}, {})
    return out


def modified_huber_loss(input, label, name=None):
    helper = LayerHelper("modified_huber_loss", input=input, name=name)
    inter = helper.create_variable_for_type_inference(input.dtype, True)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("modified_huber_loss",
                     {"X": input, "Y": label},
                     {"IntermediateVal": inter, "Out": out}, {})
    return out


def squared_l2_distance(x, y, name=None):
    helper = LayerHelper("squared_l2_distance", input=x, name=name)
    sub = helper.create_variable_for_type_inference(x.dtype, True)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("squared_l2_distance", {"X": x, "Y": y},
                     {"sub_result": sub, "Out": out}, {})
    return out


def teacher_student_sigmoid_loss(input, label,
                                 soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    helper = LayerHelper("teacher_student_sigmoid_loss", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("teacher_student_sigmoid_loss",
                     {"X": input, "Label": label}, {"Y": out},
                     {"soft_max_up_bound": soft_max_up_bound,
                      "soft_max_lower_bound": soft_max_lower_bound})
    return out


def conv_shift(x, y, name=None):
    helper = LayerHelper("conv_shift", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("conv_shift", {"X": x, "Y": y}, {"Out": out}, {})
    return out


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):
    helper = LayerHelper("add_position_encoding", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("add_position_encoding", {"X": input},
                     {"Out": out}, {"alpha": alpha, "beta": beta})
    return out


def data_norm(input, act=None, epsilon=1e-5, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False):
    """reference layers/nn.py data_norm: normalization by running batch
    statistics, no trainable scale/shift."""
    from ..initializer import ConstantInitializer
    from ..param_attr import ParamAttr

    helper = LayerHelper("data_norm", input=input,
                         param_attr=param_attr, name=name)
    c = input.shape[1]
    attr = ParamAttr._to_attr(param_attr) or ParamAttr()
    bsize = helper.create_parameter(
        ParamAttr(name=attr.name and attr.name + ".batch_size",
                  initializer=ConstantInitializer(1e4)),
        [c], input.dtype)
    bsum = helper.create_parameter(
        ParamAttr(name=attr.name and attr.name + ".batch_sum",
                  initializer=ConstantInitializer(0.0)),
        [c], input.dtype)
    bsq = helper.create_parameter(
        ParamAttr(name=attr.name and attr.name + ".batch_square_sum",
                  initializer=ConstantInitializer(1e4)),
        [c], input.dtype)
    for p in (bsize, bsum, bsq):
        p.stop_gradient = True
        p.trainable = False
    y = helper.create_variable_for_type_inference(input.dtype)
    means = helper.create_variable_for_type_inference(input.dtype, True)
    scales = helper.create_variable_for_type_inference(input.dtype,
                                                       True)
    helper.append_op(
        "data_norm",
        {"X": input, "BatchSize": bsize, "BatchSum": bsum,
         "BatchSquareSum": bsq},
        {"Y": y, "Means": means, "Scales": scales,
         "BatchSizeOut": bsize, "BatchSumOut": bsum,
         "BatchSquareSumOut": bsq},
        {"epsilon": epsilon})
    return helper.append_activation(y)


def random_crop(x, shape, seed=None):
    helper = LayerHelper("random_crop", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("random_crop", {"X": x}, {"Out": out},
                     {"shape": list(shape),
                      "startup_seed": seed if seed is not None else 0})
    return out


def dynamic_lstmp(input, size, proj_size, param_attr=None,
                  bias_attr=None, use_peepholes=True, is_reverse=False,
                  gate_activation="sigmoid", cell_activation="tanh",
                  candidate_activation="tanh", proj_activation="tanh",
                  dtype="float32", name=None):
    """reference layers/nn.py dynamic_lstmp (lstmp_op.cc): input
    pre-projected [B,T,4H]; recurrence on the P-dim projection."""
    helper = LayerHelper("dynamic_lstmp", input=input,
                         param_attr=param_attr, bias_attr=bias_attr,
                         name=name)
    hidden = size // 4
    w = helper.create_parameter(helper.param_attr,
                                [proj_size, 4 * hidden], dtype)
    w_proj = helper.create_parameter(helper.param_attr,
                                     [hidden, proj_size], dtype)
    bias_size = 7 * hidden if use_peepholes else 4 * hidden
    b = helper.create_parameter(helper.bias_attr, [1, bias_size],
                                dtype, is_bias=True)
    proj = helper.create_variable_for_type_inference(dtype)
    cell = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "lstmp",
        {"Input": input, "Weight": w, "ProjWeight": w_proj, "Bias": b,
         "SeqLen": seq_len_of(input)},
        {"Projection": proj, "Cell": cell},
        {"use_peepholes": use_peepholes, "is_reverse": is_reverse,
         "gate_activation": gate_activation,
         "cell_activation": cell_activation,
         "candidate_activation": candidate_activation,
         "proj_activation": proj_activation})
    block = proj.block
    for o in (proj, cell):
        lname = o.name + SEQ_LEN_SUFFIX
        helper.append_op("assign", {"X": input.name + SEQ_LEN_SUFFIX},
                         {"Out": lname}, {})
        block.create_var(name=lname, shape=(-1,), dtype="int32",
                         stop_gradient=True)
    return proj, cell


# --- LoD machinery (reference layers/control_flow.py) --------------------
def lod_rank_table(x, level=0):
    helper = LayerHelper("lod_rank_table", input=x)
    table = helper.create_variable_for_type_inference("int32", True)
    helper.append_op("lod_rank_table",
                     {"X": x, "SeqLen": seq_len_of(x)},
                     {"Out": table}, {"level": level})
    return table


def max_sequence_len(rank_table):
    helper = LayerHelper("max_sequence_len", input=rank_table)
    out = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("max_sequence_len", {"RankTable": rank_table},
                     {"Out": out}, {})
    return out


def lod_tensor_to_array(x, table):
    helper = LayerHelper("lod_tensor_to_array", input=x)
    arr = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op("lod_tensor_to_array",
                     {"X": x, "RankTable": table}, {"Out": arr}, {})
    return arr


def array_to_lod_tensor(x, table):
    helper = LayerHelper("array_to_lod_tensor", input=x)
    out = helper.create_variable_for_type_inference(None, True)
    helper.append_op("array_to_lod_tensor",
                     {"X": x, "RankTable": table}, {"Out": out}, {})
    return out


def shrink_memory(x, i, table):
    helper = LayerHelper("shrink_memory", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    cnt = helper.create_variable_for_type_inference("int32", True)
    helper.append_op("shrink_rnn_memory",
                     {"X": x, "I": i, "RankTable": table},
                     {"Out": out, "ActiveCount": cnt}, {})
    return out


def reorder_lod_tensor_by_rank(x, rank_table):
    helper = LayerHelper("reorder_lod_tensor_by_rank", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("reorder_lod_tensor_by_rank",
                     {"X": x, "RankTable": rank_table},
                     {"Out": out}, {})
    return out


def Print(input, first_n=-1, message=None, summarize=-1,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=True,
          print_phase="both"):
    """reference layers/control_flow.py Print (print_op.cc)."""
    helper = LayerHelper("print", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("print", {"X": input}, {"Out": out},
                     {"first_n": first_n, "message": message or "",
                      "summarize": summarize,
                      "print_phase": print_phase})
    return out


def is_empty(x, cond=None):
    helper = LayerHelper("is_empty", input=x)
    out = cond or helper.create_variable_for_type_inference("bool",
                                                            True)
    helper.append_op("is_empty", {"X": x}, {"Out": out}, {})
    return out


# --- op-gap batch 2 wrappers (reference layers/nn.py selu, l1 helpers,
# space_to_depth, sequence_mask...; resize_* live in nn.py already) ---
def selu(x, scale=None, alpha=None, name=None):
    helper = LayerHelper("selu", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {}
    if scale is not None:
        attrs["scale"] = scale
    if alpha is not None:
        attrs["alpha"] = alpha
    helper.append_op("selu", {"X": x}, {"Out": out}, attrs)
    return out


def space_to_depth(x, blocksize, name=None):
    helper = LayerHelper("space_to_depth", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("space_to_depth", {"X": x}, {"Out": out},
                     {"blocksize": blocksize})
    return out


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    if maxlen is None or int(maxlen) < 1:
        # fail at the CALL SITE: maxlen=max(x) is data-dependent shape,
        # which XLA cannot compile (reference sequence_mask_op.h:69
        # allows it; the TPU design makes maxlen mandatory)
        raise ValueError(
            "sequence_mask requires a static maxlen > 0 on TPU "
            "(maxlen=None would make the output shape data-dependent)")
    helper = LayerHelper("sequence_mask", input=x, name=name)
    out = helper.create_variable_for_type_inference(dtype, True)
    helper.append_op("sequence_mask", {"X": x}, {"Y": out},
                     {"maxlen": int(maxlen), "out_dtype": dtype})
    return out


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper("pad_constant_like", input=x, name=name)
    out = helper.create_variable_for_type_inference(y.dtype)
    helper.append_op("pad_constant_like", {"X": x, "Y": y},
                     {"Out": out}, {"pad_value": float(pad_value)})
    return out


def l1_norm(x, name=None):
    helper = LayerHelper("l1_norm", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("l1_norm", {"X": x}, {"Out": out}, {})
    return out


def hash(input, hash_size, num_hash=1, name=None):
    helper = LayerHelper("hash", input=input, name=name)
    out = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("hash", {"X": input}, {"Out": out},
                     {"mod_by": hash_size, "num_hash": num_hash})
    return out


def fsp_matrix(x, y):
    helper = LayerHelper("fsp", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("fsp", {"X": x, "Y": y}, {"Out": out}, {})
    return out


__all__.extend(["selu", "space_to_depth", "sequence_mask",
                "pad_constant_like", "l1_norm", "hash", "fsp_matrix"])


def masked_pool_write(pool, new, index, gate=None, leading_dims=1,
                      exclusive_via=None, name=None):
    """Write rows into a SHARED decode KV pool by disjoint one-hot
    scatter, IN PLACE (the op's Out is the pool var itself, so the
    pool rides the executor's read-modify-write state path). The one
    blessed write surface for `@POOL`-marked persistables
    (models/decode_engine.py paged layout; ops/paged_ops.py kernel):
    checker PTA110 rejects any other writer, because an aliased
    scatter into a shared pool silently corrupts ANOTHER request's KV
    — the nastiest failure class of paged serving.

    ``exclusive_via`` is mandatory and names the lane-exclusivity
    proof: "block_table" (per-lane blocks from the host free-list —
    requires ``gate`` so idle/dustbin/paused lanes write nothing),
    "host_indices" (host-deduplicated admission targets), or
    "cow_dst" (freshly allocated exclusive blocks a COW copy
    diverges into — the radix/beam branching path).
    """
    if exclusive_via not in ("block_table", "host_indices",
                             "cow_dst"):
        raise ValueError(
            f"masked_pool_write needs exclusive_via='block_table', "
            f"'host_indices' or 'cow_dst' (got {exclusive_via!r}): "
            f"shared-pool writes must declare why row indices "
            f"cannot alias (checker PTA110)")
    if exclusive_via == "block_table" and gate is None:
        raise ValueError(
            "masked_pool_write(exclusive_via='block_table') needs a "
            "gate: ungated lane writes through a block table let "
            "idle/dustbin lanes scribble over other requests' KV "
            "(checker PTA110)")
    helper = LayerHelper("masked_pool_write", input=pool, name=name)
    inputs = {"Pool": pool, "New": new, "Index": index}
    if gate is not None:
        inputs["Gate"] = gate
    helper.append_op("masked_pool_write", inputs, {"Out": pool},
                     {"leading_dims": int(leading_dims),
                      "exclusive_via": exclusive_via})
    return pool


__all__.append("masked_pool_write")


def paged_decode_attention(q, pool_k, pool_v, block_tab, pos, block_size,
                           n_heads, scale=1.0, name=None, n_kv_heads=None,
                           reads="cells"):
    """Context rows ``[R, q, H*Dh]`` of the decode tick's queries ``q``
    over each lane's own cache positions, read from the SHARED
    ``[NB*BS, H*Dh]`` pools through the lane's row of ``block_tab``
    (ops/paged_ops.py; query j of a lane attends positions
    <= pos + j). The read surface of the `@POOL` self-attention pools
    as ``masked_pool_write`` is their write surface: the ownership
    prover (PTA190) must be able to chain ``block_tab`` to a marked
    host table with a bound, because the kernel neither clamps nor
    fills. Reference counterpart: none (the reference's decode caches
    are dense per-request tensors,
    tests/unittests/dist_transformer.py:1498). `n_kv_heads` fewer than
    `n_heads`: grouped queries over pools ``[NB*BS, Hkv*Dh]``.
    `reads="prompt_table"` names the cross-attention read (the prompt
    table's rows behind ``prompt_ref`` as a table of one block a
    lane) in the routing record; it chooses nothing."""
    helper = LayerHelper("paged_decode_attention", input=q, name=name)
    out = helper.create_variable_for_type_inference(q.dtype, True)
    attrs = {"block_size": int(block_size), "n_heads": int(n_heads),
             "scale": float(scale)}
    if n_kv_heads and n_kv_heads != n_heads:
        attrs["n_kv_heads"] = int(n_kv_heads)
    if reads != "cells":
        attrs["reads"] = reads
    helper.append_op(
        "paged_decode_attention",
        {"Q": q, "PoolK": pool_k, "PoolV": pool_v, "Table": block_tab,
         "Pos": pos}, {"Out": out}, attrs)
    return out


def paged_prefill_attention(q, pool_k, pool_v, block_tab, pos, block_size,
                            n_heads, n_kv_heads=None, scale=1.0,
                            name=None):
    """Context rows ``[N, H*Dh]`` of a prefill chunk's queries ``q``
    over the lane's paged prefix and the chunk itself under the causal
    mask (ops/paged_ops.py): row i sees the positions <= pos[i] of its
    group's row of ``block_tab`` ``[G, NP]``. The pools hold the chunk's
    own keys and values already."""
    helper = LayerHelper("paged_prefill_attention", input=q, name=name)
    out = helper.create_variable_for_type_inference(q.dtype, True)
    helper.append_op(
        "paged_prefill_attention",
        {"Q": q, "PoolK": pool_k, "PoolV": pool_v, "Table": block_tab,
         "Pos": pos}, {"Out": out},
        {"block_size": int(block_size), "n_heads": int(n_heads),
         "n_kv_heads": int(n_kv_heads or n_heads), "scale": float(scale)})
    return out


__all__.extend(["paged_decode_attention", "paged_prefill_attention"])


def filtered_softmax(logits, temperature=1.0, top_k=0, top_p=1.0,
                     name=None):
    """Temperature/top-k/top-p filtered, renormalized probabilities
    over the last axis of `logits` (ops/spec_ops.py). temperature=0 is
    the greedy degenerate case: a one-hot at argmax — which is what
    lets greedy speculative acceptance ride the same rejection-rule
    kernel (layers.spec_accept) token-exactly."""
    helper = LayerHelper("filtered_softmax", input=logits, name=name)
    out = helper.create_variable_for_type_inference("float32", True)
    helper.append_op("filtered_softmax", {"X": logits}, {"Out": out},
                     {"temperature": float(temperature),
                      "top_k": int(top_k), "top_p": float(top_p)})
    return out


def sample_categorical(probs, seed, pos, noise_tag=0, base_seed=0,
                       name=None):
    """One token per lane from [R, V] probabilities
    (ops/spec_ops.py). Noise is a pure function of (base_seed,
    noise_tag, seed[r], pos[r]) — NOT the executor step key — so the
    same (request seed, position) draws the same token in every serve
    specialization: admission order, burst boundaries, and paged
    recompute-preemption replay cannot move sampled tokens (the
    serving layer's byte-exact contract; ops/spec_ops.py module
    docstring has the full rationale)."""
    helper = LayerHelper("sample_categorical", input=probs, name=name)
    out = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("sample_categorical",
                     {"Probs": probs, "Seed": seed, "Pos": pos},
                     {"Out": out},
                     {"noise_tag": int(noise_tag),
                      "base_seed": int(base_seed)})
    return out


def span_scatter(buf, vals, start, count, name=None):
    """Per-row span write: buf[r, start[r]:start[r]+count[r]] =
    vals[r, :count[r]], IN PLACE (Out is the buf var, so the buffer
    rides the executor's read-modify-write state path) — the
    accepted-prefix token write of the speculative decode step
    (ops/spec_ops.py)."""
    helper = LayerHelper("span_scatter", input=buf, name=name)
    helper.append_op("span_scatter",
                     {"X": buf, "Vals": vals, "Start": start,
                      "Count": count},
                     {"Out": buf}, {})
    return buf


def spec_accept(proposals, draft_probs, target_probs, seed, pos, k,
                end_id, max_len, greedy=True, base_seed=0, noise_tag=0,
                name=None):
    """Draft-and-verify acceptance for one batched speculative step
    (ops/spec_ops.py spec_accept: Leviathan-style rejection sampling;
    greedy=True makes it token-exact greedy). Returns (advance,
    tokens, accepted, fin): per-lane emitted count (clipped at the
    first end_id and at buffer room), the [R, k+1] emitted tokens,
    the accepted-proposal count, and the EOS latch. Checker PTA120
    verifies the declared shapes agree with k (the counter-advance
    <= k+1 bound is only provable when they do)."""
    helper = LayerHelper("spec_accept", input=proposals, name=name)
    advance = helper.create_variable_for_type_inference("int64", True)
    tokens = helper.create_variable_for_type_inference("int64", True)
    accepted = helper.create_variable_for_type_inference("int64", True)
    fin = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("spec_accept",
                     {"Proposals": proposals, "DraftProbs": draft_probs,
                      "TargetProbs": target_probs, "Seed": seed,
                      "Pos": pos},
                     {"Advance": advance, "Tokens": tokens,
                      "Accepted": accepted, "Fin": fin},
                     {"k": int(k), "end_id": int(end_id),
                      "max_len": int(max_len), "greedy": bool(greedy),
                      "base_seed": int(base_seed),
                      "noise_tag": int(noise_tag)})
    return advance, tokens, accepted, fin


__all__.extend(["filtered_softmax", "sample_categorical",
                "span_scatter", "spec_accept"])


# ---------------------------------------------------------------------------
# the paged side of latent attention with a learned selection
# (ops/paged_ops.py): rows come in G groups, each one lane with its row
# of the block table
# ---------------------------------------------------------------------------
def paged_cell_index(block_tab, pos, block_size, name=None):
    """Pool rows [N] int32 of positions pos [N] through block_tab [G,
    NP] (N = G * n, group-major)."""
    helper = LayerHelper("paged_cell_index", input=pos, name=name)
    out = helper.create_variable_for_type_inference("int32", True)
    helper.append_op("paged_cell_index",
                     {"Table": block_tab, "Pos": pos}, {"Out": out},
                     {"block_size": int(block_size)})
    return out


def dsa_indexer_scores(q_i, w, pool, block_tab, pos, block_size,
                       name=None):
    """Indexer scores [N, NP*BS] float32 of every cached position of
    each row's lane (-inf past the row's own position), read from the
    indexer-key pool through block_tab [G, NP]."""
    helper = LayerHelper("dsa_indexer_scores", input=q_i, name=name)
    out = helper.create_variable_for_type_inference("float32", True)
    helper.append_op(
        "dsa_indexer_scores",
        {"QI": q_i, "W": w, "Pool": pool, "Table": block_tab,
         "Pos": pos}, {"Out": out}, {"block_size": int(block_size)})
    return out


def dsa_select(scores, k, mode="indices", name=None):
    """Each row's k largest scores: mode "indices" their positions [N,
    k] int32 (-1 where it has fewer live positions), mode "threshold"
    the k-th largest score [N] float32 (ops/paged_ops.py)."""
    helper = LayerHelper("dsa_select", input=scores, name=name)
    out = helper.create_variable_for_type_inference(
        "int32" if mode == "indices" else "float32", True)
    helper.append_op("dsa_select", {"Scores": scores}, {"Out": out},
                     {"k": int(k), "mode": mode})
    return out


def sparse_latent_attention(q_lat, pool, block_tab, sel, block_size,
                            latent_dim, scale=1.0, k=0, cells=None,
                            name=None):
    """[N, H, latent_dim] float32: attention of the absorbed queries
    q_lat [N, H, rkv+dr] over the rows of the latent pool that the
    selection names, addressed through block_tab [G, NP]. `sel`: [N,
    K] positions of the row's lane (-1 for none), or the pair (scores
    [N, T], threshold [N]) of `dsa_select(mode="threshold")` with the
    selection's size `k`. The read surface of the latent pools."""
    helper = LayerHelper("sparse_latent_attention", input=q_lat,
                         name=name)
    out = helper.create_variable_for_type_inference("float32", True)
    chosen = {"Scores": sel[0], "Thr": sel[1]} \
        if isinstance(sel, tuple) else {"Sel": sel}
    if cells is not None:       # paged_cell_index of sel, made once
        chosen["Cells"] = cells
    helper.append_op(
        "sparse_latent_attention",
        {"Q": q_lat, "Pool": pool, "Table": block_tab, **chosen},
        {"Out": out},
        {"block_size": int(block_size), "latent_dim": int(latent_dim),
         "scale": float(scale), "k": int(k)})
    return out


__all__.extend(["paged_cell_index", "dsa_indexer_scores", "dsa_select",
                "sparse_latent_attention"])


def lane_probe_write(hist, new, gate, step=None, name=None):
    """hist [R, K] (or [R, T, K] with step [R]) takes new [R, K] in the
    rows (at the steps) of the lanes whose gate is 1, in place."""
    helper = LayerHelper("lane_probe_write", input=hist, name=name)
    inputs = {"Hist": hist, "New": new, "Gate": gate}
    if step is not None:
        inputs["Step"] = step
    helper.append_op("lane_probe_write", inputs, {"Out": hist}, {})
    return hist


__all__.append("lane_probe_write")


def pack_row(xs, name, dtype="int64"):
    """The integer variables `xs` laid end to end, each flattened, as
    the one flat variable `name` (op pack_row): a serve program's fetch."""
    helper = LayerHelper("pack_row", input=xs[0], name=name)
    size = sum(int(np.prod(x.shape)) for x in xs)
    out = helper.main_program.current_block().create_var(
        name=name, shape=(size,), dtype=dtype, stop_gradient=True)
    helper.append_op("pack_row", {"X": list(xs)}, {"Out": out},
                     {"dtype": dtype})
    return out


__all__.append("pack_row")

