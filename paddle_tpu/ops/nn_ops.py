"""Neural-net ops: conv/pool/norm/softmax/dropout/embedding/losses/metrics.

Parity targets: reference paddle/fluid/operators/conv_op.cc (+cuDNN
conv_cudnn_op.cu.cc), pool_op.cc, batch_norm_op.cc/.cu, layer_norm_op.cu,
group_norm_op.cc, softmax_op.cc, softmax_with_cross_entropy_op.cu,
cross_entropy_op.cc, dropout_op.cc, lookup_table_op.cc, lrn_op.cc,
metrics/accuracy_op.cc, auc_op.cc. TPU-first notes:

* conv2d lowers to lax.conv_general_dilated -- XLA tiles it onto the MXU
  (the cuDNN algo-search cache of the reference is obsolete here).
* batch_norm keeps the reference's mutable running-stat semantics by
  emitting MeanOut/VarianceOut as functional state (the executor threads
  them back into the scope).
* dropout SAVES its mask as an output (like the reference) so the grad op
  is deterministic -- the generic vjp grad would re-toss the coin.
* lookup_table's sparse SelectedRows grad path becomes a dense
  scatter-add here; a row-sharded embedding (pserver parity) lives in
  parallel/embedding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.program import Operator, grad_var_name
from ..core.registry import (OpContext, register_op, get_op_info,
                             EMPTY_VAR)


# --------------------------------------------------------------------------
# conv / pool
# --------------------------------------------------------------------------
def _pair(v):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v, v]


@register_op("conv2d")
def conv2d(ctx):
    x = ctx.input("Input")
    w = ctx.input("Filter")  # [out_c, in_c/groups, kh, kw]
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _pair(ctx.attr("paddings", [0, 0]))
    dilations = _pair(ctx.attr("dilations", [1, 1]))
    groups = ctx.attr("groups", 1)
    out = jax.lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return {"Output": out}


@register_op("depthwise_conv2d")
def depthwise_conv2d(ctx):
    return conv2d(ctx)


@register_op("conv2d_transpose")
def conv2d_transpose(ctx):
    x = ctx.input("Input")
    w = ctx.input("Filter")  # [in_c, out_c/groups, kh, kw]
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _pair(ctx.attr("paddings", [0, 0]))
    dilations = _pair(ctx.attr("dilations", [1, 1]))
    groups = ctx.attr("groups", 1)
    out = _conv_transpose_nd(x, w, strides, pads, dilations, groups,
                             spatial=2)
    return {"Output": out}


def _conv_transpose_nd(x, w, strides, pads, dilations, groups, spatial):
    """Transpose conv as an input-dilated forward conv (the textbook
    identity), matching conv_transpose_op.cc's output formula
    out = (in-1)*s - 2p + d*(k-1) + 1.

    fluid filter layout is [C_in, C_out/g, *k]; the equivalent forward
    kernel is the spatially-flipped, per-group channel-swapped
    [C_out, C_in/g, *k]."""
    ksp = w.shape[2:2 + spatial]
    c_in = x.shape[1]
    c_out_per_g = w.shape[1]
    sp_axes = tuple(range(2, 2 + spatial))
    w_f = jnp.flip(w, axis=sp_axes)
    # [C_in, C_out/g, *k] -> [g, C_in/g, C_out/g, *k] -> swap ->
    # [C_out, C_in/g, *k]
    w_k = w_f.reshape((groups, c_in // groups, c_out_per_g) + ksp)
    w_k = jnp.swapaxes(w_k, 1, 2).reshape(
        (groups * c_out_per_g, c_in // groups) + ksp)
    tpads = [(dilations[i] * (ksp[i] - 1) - pads[i],) * 2
             for i in range(spatial)]
    dn = (("NCHW", "OIHW", "NCHW") if spatial == 2
          else ("NCDHW", "OIDHW", "NCDHW"))
    return jax.lax.conv_general_dilated(
        x, w_k, window_strides=(1,) * spatial, padding=tpads,
        lhs_dilation=tuple(strides), rhs_dilation=tuple(dilations),
        dimension_numbers=dn, feature_group_count=groups)


def _deform_bilinear(img, y, x):
    """Bilinear sample with zero padding outside the image.

    img: [B, G, Cg, H, W]; y/x: [B, G, N] float sample coords in image
    space. Returns [B, G, N, Cg]. One flat gather per corner — the
    whole thing stays a dense static-shape XLA program (no
    data-dependent shapes), so it fuses and vectorizes on TPU.
    """
    B, G, Cg, H, W = img.shape
    flat = img.reshape(B, G, Cg, H * W)
    y0, x0 = jnp.floor(y), jnp.floor(x)
    out = jnp.zeros(y.shape + (Cg,), img.dtype)
    for dy in (0.0, 1.0):
        for dx in (0.0, 1.0):
            yi, xi = y0 + dy, x0 + dx
            w = (1.0 - jnp.abs(y - yi)) * (1.0 - jnp.abs(x - xi))
            valid = ((yi >= 0) & (yi <= H - 1) &
                     (xi >= 0) & (xi <= W - 1))
            idx = (jnp.clip(yi, 0, H - 1) * W +
                   jnp.clip(xi, 0, W - 1)).astype(jnp.int32)
            # flat [B,G,Cg,HW], idx [B,G,N] -> [B,G,Cg,N]
            g = jnp.take_along_axis(flat, idx[:, :, None, :], axis=3)
            g = jnp.moveaxis(g, 2, 3)  # [B,G,N,Cg]
            out = out + jnp.where(valid, w, 0.0)[..., None] * g
    return out


def _deformable_conv_infer_shape(op, block):
    """Output = [B(Input), F(Filter), Ho, Wo(Offset)]. A custom shape
    fn (not the generic eval_shape probe): a -1-batch Input combined
    with a concrete-batch Offset makes the probe's substitute batches
    disagree inside the kernel."""
    x = block._find_var_recursive(op.inputs["Input"][0])
    w = block._find_var_recursive(op.inputs["Filter"][0])
    off = block._find_var_recursive(op.inputs["Offset"][0])
    out = block._find_var_recursive(op.outputs["Output"][0])
    if None in (x, w, off, out) or not (x.shape and w.shape
                                        and off.shape):
        return
    out.shape = (x.shape[0], w.shape[0], off.shape[2], off.shape[3])
    out.dtype = x.dtype


@register_op("deformable_conv", infer_shape=_deformable_conv_infer_shape)
def deformable_conv(ctx):
    """Deformable convolution v1/v2 (Dai et al. '17 / Zhu et al. '19).
    No counterpart op exists in this reference tree (beyond-reference
    capability; the layer name is part of later fluid API surfaces).

    TPU design: instead of the CUDA deformable-im2col kernel, sample
    all B*G*K*Ho*Wo tap positions with one vectorized bilinear gather
    (`_deform_bilinear`), then contract taps x in-channels against the
    filter with a single einsum — the contraction is the FLOPs and XLA
    tiles it onto the MXU. Offset layout matches torchvision/paddle:
    [B, 2*dg*kh*kw, Ho, Wo] with (dy, dx) pairs per tap; optional Mask
    [B, dg*kh*kw, Ho, Wo] gives the modulated (v2) form. Grads come
    from the generic vjp maker (bilinear weights are differentiable in
    the offsets)."""
    x = ctx.input("Input")
    offset = ctx.input("Offset")
    w = ctx.input("Filter")  # [F, C/groups, kh, kw]
    mask = ctx.input("Mask") if ctx.has_input("Mask") else None
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _pair(ctx.attr("paddings", [0, 0]))
    dilations = _pair(ctx.attr("dilations", [1, 1]))
    groups = ctx.attr("groups", 1)
    dg = ctx.attr("deformable_groups", 1)

    B, C, H, W = x.shape
    F, _, kh, kw = w.shape
    K = kh * kw
    Ho = (H + 2 * pads[0] - (dilations[0] * (kh - 1) + 1)) // strides[0] + 1
    Wo = (W + 2 * pads[1] - (dilations[1] * (kw - 1) + 1)) // strides[1] + 1

    # base tap coords (unpadded image space): [K, Ho, Wo]
    ho = jnp.arange(Ho) * strides[0] - pads[0]
    wo = jnp.arange(Wo) * strides[1] - pads[1]
    ki = jnp.arange(kh) * dilations[0]
    kj = jnp.arange(kw) * dilations[1]
    base_y = (ho[None, :] + ki[:, None]).reshape(kh, 1, Ho, 1)
    base_x = (wo[None, :] + kj[:, None]).reshape(1, kw, 1, Wo)
    base_y = jnp.broadcast_to(base_y, (kh, kw, Ho, Wo)).reshape(K, Ho, Wo)
    base_x = jnp.broadcast_to(base_x, (kh, kw, Ho, Wo)).reshape(K, Ho, Wo)

    # offsets: [B, 2*dg*K, Ho, Wo] -> dy/dx [B, dg, K, Ho, Wo]
    off = offset.reshape(B, dg, K, 2, Ho, Wo)
    y = base_y[None, None] + off[:, :, :, 0]
    xx = base_x[None, None] + off[:, :, :, 1]

    img = x.reshape(B, dg, C // dg, H, W)
    samp = _deform_bilinear(img, y.reshape(B, dg, K * Ho * Wo),
                            xx.reshape(B, dg, K * Ho * Wo))
    samp = samp.reshape(B, dg, K, Ho, Wo, C // dg)
    if mask is not None:
        m = mask.reshape(B, dg, K, Ho, Wo)
        samp = samp * m[..., None]
    # [B, dg, K, Ho, Wo, C/dg] -> [B, K, Ho, Wo, C] (dg-major channels)
    samp = jnp.moveaxis(samp, 1, 4).reshape(B, K, Ho, Wo, C)

    # grouped contraction: out[b,g,f,ho,wo] = sum_{c,k} samp * w
    samp_g = samp.reshape(B, K, Ho, Wo, groups, C // groups)
    w_g = w.reshape(groups, F // groups, C // groups, K)
    out = jnp.einsum("bkhwgc,gfck->bghwf", samp_g, w_g,
                     preferred_element_type=samp_g.dtype)
    out = jnp.moveaxis(out, 4, 2).reshape(B, F, Ho, Wo)
    return {"Output": out}


@register_op("switch_moe")
def switch_moe(ctx):
    """Switch/GShard mixture-of-experts FFN block (beyond-reference
    capability; see parallel/moe.py for the routing math and the
    expert-parallel dataflow). Inside a `with expert_parallel(mesh):`
    scope and when token/expert counts divide the ep axis, lowers to
    the shard_map all_to_all form; otherwise runs the identical dense
    math on one device — ep=N and ep=1 are numerically interchangeable
    in the no-drop capacity regime (per-shard FIFO capacity can drop
    different tokens when over-subscribed)."""
    from ..parallel import moe as moe_mod

    x = ctx.input("X")            # [..., D]
    wg = ctx.input("GateW")       # [D, E]
    w1 = ctx.input("W1")          # [E, D, F]
    w2 = ctx.input("W2")          # [E, F, D]
    top_k = int(ctx.attr("top_k", 1))
    cf = float(ctx.attr("capacity_factor", 2.0))
    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)
    t, E = xt.shape[0], w1.shape[0]
    if moe_mod.ep_applicable(t, E):
        mesh, axis = moe_mod.active_expert_parallel()
        out, aux, drop = moe_mod.moe_apply(
            xt, wg, w1, w2, mesh, axis=axis,
            capacity_factor=cf, top_k=top_k)
    else:
        cap = max(1, int(cf * top_k * t / E))
        out, aux, drop = moe_mod.moe_dense(xt, wg, w1, w2, cap, top_k)
    # DropFrac: fraction of tokens with zero dispatch slots — the
    # first thing to monitor in real MoE training. Extra outputs are
    # free when unfetched (XLA dead-codes them); stop_gradient keeps
    # the monitoring path out of AD.
    return {"Out": out.reshape(shape),
            "AuxLoss": aux.reshape(1).astype(jnp.float32),
            "DropFrac": jax.lax.stop_gradient(drop).reshape(1).astype(
                jnp.float32)}


@register_op("conv3d")
def conv3d(ctx):
    x = ctx.input("Input")
    w = ctx.input("Filter")
    strides = ctx.attr("strides", [1, 1, 1])
    pads = ctx.attr("paddings", [0, 0, 0])
    dilations = ctx.attr("dilations", [1, 1, 1])
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=list(strides),
        padding=[(p, p) for p in pads],
        rhs_dilation=list(dilations),
        feature_group_count=ctx.attr("groups", 1),
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    return {"Output": out}


def _pool2d_impl(ctx):
    x = ctx.input("X")
    ptype = ctx.attr("pooling_type", "max")
    ksize = _pair(ctx.attr("ksize", [2, 2]))
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _pair(ctx.attr("paddings", [0, 0]))
    if ctx.attr("global_pooling", False):
        ksize = [x.shape[2], x.shape[3]]
        pads = [0, 0]
        strides = [1, 1]
    window = (1, 1, ksize[0], ksize[1])
    strides_ = (1, 1, strides[0], strides[1])
    padding = ((0, 0), (0, 0), (pads[0], pads[0]), (pads[1], pads[1]))
    if ptype == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides_,
                                    padding)
    else:
        s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides_,
                                  padding)
        if ctx.attr("exclusive", True) and (pads[0] or pads[1]):
            ones = jnp.ones_like(x)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                        strides_, padding)
            out = s / cnt
        else:
            out = s / (ksize[0] * ksize[1])
    return out


@register_op("pool2d")
def pool2d(ctx):
    return _pool2d_impl(ctx)


@register_op("adaptive_pool2d")
def adaptive_pool2d(ctx):
    x = ctx.input("X")
    out_hw = ctx.attr("pooling_size", [1, 1])
    ptype = ctx.attr("pooling_type", "avg")
    n, c, h, w = x.shape
    oh, ow = out_hw
    x5 = x.reshape(n, c, oh, h // oh, ow, w // ow)
    if ptype == "avg":
        return x5.mean(axis=(3, 5))
    return x5.max(axis=(3, 5))


# --------------------------------------------------------------------------
# normalization
# --------------------------------------------------------------------------
def _bn_grad_maker(op, no_grad_set=frozenset()):
    """batch_norm grad: differentiate only w.r.t. X/Scale/Bias using saved
    batch statistics; running stats are state, not differentiable."""
    grad_type = "batch_norm_grad"
    from ..core.registry import is_registered, register_op as _reg

    if not is_registered(grad_type):
        _reg(grad_type, differentiable=False)(_bn_grad_kernel)
    inputs = {
        "X": op.inputs["X"], "Scale": op.inputs["Scale"],
        "Bias": op.inputs["Bias"],
        "SavedMean": op.outputs.get("SavedMean", []),
        "SavedVariance": op.outputs.get("SavedVariance", []),
        "Y@GRAD": [grad_var_name(n) for n in op.outputs["Y"]],
    }
    outputs = {}
    for slot in ("X", "Scale", "Bias"):
        names = op.inputs[slot]
        if all(n in no_grad_set for n in names):
            continue
        outputs[slot + "@GRAD"] = [grad_var_name(n) for n in names]
    attrs = dict(op.attrs)
    return [Operator(op.block, grad_type, inputs, outputs, attrs)]


def _bn_grad_kernel(ctx):
    x = ctx.input("X")
    scale = ctx.input("Scale")
    mean = ctx.input("SavedMean")
    inv_std = ctx.input("SavedVariance")  # we save inv-std like cuDNN
    dy = ctx.input("Y@GRAD")
    eps = ctx.attr("epsilon", 1e-5)
    layout = ctx.attr("data_layout", "NCHW")
    axes = (0, 2, 3) if (layout == "NCHW" and x.ndim == 4) else \
        tuple(i for i in range(x.ndim) if i != x.ndim - 1)
    shape = [1] * x.ndim
    caxis = 1 if (layout == "NCHW" and x.ndim == 4) else x.ndim - 1
    shape[caxis] = x.shape[caxis]
    m = float(np.prod([x.shape[a] for a in axes]))
    mean_b = mean.reshape(shape)
    inv_b = inv_std.reshape(shape)
    xhat = (x - mean_b) * inv_b
    dbias = jnp.sum(dy, axis=axes)
    dscale = jnp.sum(dy * xhat, axis=axes)
    if ctx.attr("is_test", False) or ctx.attr(
            "use_global_stats", False):
        dx = dy * scale.reshape(shape) * inv_b
    else:
        dx = (scale.reshape(shape) * inv_b / m) * (
            m * dy - dbias.reshape(shape)
            - xhat * dscale.reshape(shape))
    out = {"X@GRAD": dx, "Scale@GRAD": dscale, "Bias@GRAD": dbias}
    return {k: v for k, v in out.items() if k in
            {s for s in ctx.op.outputs}}


@register_op("batch_norm", grad_maker=_bn_grad_maker)
def batch_norm(ctx):
    x = ctx.input("X")
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    mean_in, var_in = ctx.input("Mean"), ctx.input("Variance")
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    is_test = ctx.attr("is_test", False) or ctx.attr(
        "use_global_stats", False)
    layout = ctx.attr("data_layout", "NCHW")
    if layout == "NCHW" and x.ndim == 4:
        axes, caxis = (0, 2, 3), 1
    else:
        axes, caxis = tuple(i for i in range(x.ndim - 1)), x.ndim - 1
    shape = [1] * x.ndim
    shape[caxis] = x.shape[caxis]
    if is_test:
        mean, var = mean_in, var_in
        y = (x - mean.reshape(shape)) * jax.lax.rsqrt(
            var.reshape(shape) + eps) * scale.reshape(shape) \
            + bias.reshape(shape)
        return {"Y": y, "MeanOut": mean_in, "VarianceOut": var_in,
                "SavedMean": mean_in,
                "SavedVariance": jax.lax.rsqrt(var_in + eps)}
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x), axis=axes) - jnp.square(mean)
    inv_std = jax.lax.rsqrt(var + eps)
    y = (x - mean.reshape(shape)) * inv_std.reshape(shape) \
        * scale.reshape(shape) + bias.reshape(shape)
    mean_out = mean_in * momentum + mean * (1 - momentum)
    var_out = var_in * momentum + var * (1 - momentum)
    return {"Y": y, "MeanOut": mean_out, "VarianceOut": var_out,
            "SavedMean": mean, "SavedVariance": inv_std}


@register_op("layer_norm")
def layer_norm(ctx):
    """Statistics always run in fp32 regardless of input dtype (the
    pallas kernel already did; the jnp fallback now matches). NOTE the
    op stays on the AMP BLACK list: keeping LN bf16-in/bf16-out to
    elide the convert chain was tried and measured SLOWER on v5e
    (200.6 vs 184 ms/step transformer-base) -- XLA folds the converts
    into neighboring fusions for free, while bf16 IO degrades the
    pallas LN tiles. See PERF.md dead ends."""
    x = ctx.input("X")
    eps = ctx.attr("epsilon", 1e-5)
    begin = ctx.attr("begin_norm_axis", 1)
    lead = int(np.prod(x.shape[:begin]))
    x2 = x.reshape(lead, -1)
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    x2f = x2.astype(jnp.float32)
    mean = jnp.mean(x2f, axis=1, keepdims=True)
    var = jnp.var(x2f, axis=1, keepdims=True)
    from .pallas import layer_norm as pallas_ln, note_route

    if scale is not None and bias is not None:
        s1, b1 = scale.reshape(-1), bias.reshape(-1)
        # pallas kernel when usable, else its oracle (_ln_ref) -- ONE
        # fp32 recipe shared with the kernel's custom_vjp backward
        y = (pallas_ln.layer_norm(x2, s1, b1, eps)
             if note_route("layer_norm", x2.shape,
                           pallas_ln.usable(lead, x2.shape[1]))
             else pallas_ln._ln_ref(x2, s1, b1, eps))
        return {"Y": y.reshape(x.shape), "Mean": mean.reshape(lead),
                "Variance": var.reshape(lead)}
    y = (x2f - mean) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.reshape(1, -1).astype(jnp.float32)
    if bias is not None:
        y = y + bias.reshape(1, -1).astype(jnp.float32)
    return {"Y": y.astype(x.dtype).reshape(x.shape),
            "Mean": mean.reshape(lead),
            "Variance": var.reshape(lead)}


@register_op("group_norm")
def group_norm(ctx):
    x = ctx.input("X")  # NCHW
    g = ctx.attr("groups")
    eps = ctx.attr("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape(n, g, -1)
    mean = jnp.mean(xg, axis=2, keepdims=True)
    var = jnp.var(xg, axis=2, keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    shape = [1, c] + [1] * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return {"Y": y, "Mean": mean.reshape(n, g), "Variance": var.reshape(n, g)}


@register_op("instance_norm")
def instance_norm(ctx):
    x = ctx.input("X")
    eps = ctx.attr("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    scale, bias = ctx.input("Scale"), ctx.input("Bias")
    shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return {"Y": y, "SavedMean": mean.reshape(x.shape[0], x.shape[1]),
            "SavedVariance": var.reshape(x.shape[0], x.shape[1])}


@register_op("lrn")
def lrn(ctx):
    x = ctx.input("X")  # NCHW
    n_size = ctx.attr("n", 5)
    alpha = ctx.attr("alpha", 1e-4)
    beta = ctx.attr("beta", 0.75)
    k = ctx.attr("k", 1.0)
    sq = jnp.square(x)
    half = n_size // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n_size))
    mid = (k + alpha * acc) ** beta
    return {"Out": x / mid, "MidOut": mid}


@register_op("l2_normalize")
def l2_normalize(ctx):
    x = ctx.input("X")
    axis = ctx.attr("axis", -1)
    eps = ctx.attr("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {"Out": x / norm, "Norm": norm}


@register_op("norm")
def norm_op(ctx):
    x = ctx.input("X")
    axis = ctx.attr("axis", -1)
    eps = ctx.attr("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {"Out": x / norm, "Norm": norm}


# --------------------------------------------------------------------------
# softmax & losses
# --------------------------------------------------------------------------
@register_op("softmax")
def softmax(ctx):
    return jax.nn.softmax(ctx.input("X"), axis=ctx.attr("axis", -1))


@register_op("log_softmax")
def log_softmax(ctx):
    return jax.nn.log_softmax(ctx.input("X"), axis=ctx.attr("axis", -1))


def _swce_grad_maker(op, no_grad_set=frozenset()):
    """Fused grad recomputed from saved Logits (reference
    softmax_with_cross_entropy_op.cu backward keeps the softmax tensor;
    recomputing it from logits trades cheap VPU FLOPs for the [N,V]
    probability buffer -- with a 32k vocab that buffer dominates HBM, so
    this is the TPU-right choice and lets XLA dead-code the unfetched
    Softmax output entirely)."""
    from ..core.registry import is_registered, register_op as _reg

    if not is_registered("softmax_with_cross_entropy_grad"):
        _reg("softmax_with_cross_entropy_grad", differentiable=False)(
            _swce_grad_kernel)
    inputs = {
        "Logits": op.inputs["Logits"],
        "Label": op.inputs["Label"],
        "Loss@GRAD": [grad_var_name(n) for n in op.outputs["Loss"]],
    }
    outputs = {"Logits@GRAD": [grad_var_name(n)
                               for n in op.inputs["Logits"]]}
    return [Operator(op.block, "softmax_with_cross_entropy_grad", inputs,
                     outputs, dict(op.attrs))]


def _swce_grad_kernel(ctx):
    """grad = (softmax - target) * dloss, emitted directly in the
    logits' storage dtype: the fp32 probabilities exist only inside
    the fused exp(l - lse) expression, never as an [N, V] HBM buffer;
    the hard-label one-hot subtraction is a fused iota==label compare
    select, not a materialized one-hot."""
    logits = ctx.input("Logits")
    label = ctx.input("Label")
    dloss = ctx.input("Loss@GRAD")
    if dloss is None:
        dloss = jnp.ones(logits.shape[:-1] + (1,), jnp.float32)
    dloss = dloss.astype(jnp.float32)
    eps = ctx.attr("label_smooth_eps", 0.0)
    vocab = logits.shape[-1]
    if not ctx.attr("soft_label", False):
        from .pallas import xent as pallas_xent

        routed = pallas_xent.maybe_route(logits, label)
        if routed is not None:
            l2, lab1 = routed
            dx = pallas_xent.xent_backward(
                l2, lab1, dloss.reshape(-1), eps=eps,
                ignore_index=ctx.attr("ignore_index", -100))
            return {"Logits@GRAD": dx.reshape(logits.shape)}
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1, keepdims=True)
    p_scaled = jnp.exp(lf - lse) * dloss  # fused, lands in grad
    if ctx.attr("soft_label", False):
        target = label.astype(jnp.float32)
        if eps:
            target = target * (1.0 - eps) + eps / vocab
        grad = p_scaled - target * dloss
        return {"Logits@GRAD": grad.astype(logits.dtype)}
    lab = label.astype(jnp.int32)
    if lab.ndim == logits.ndim:
        lab = lab[..., 0]
    ignore = ctx.attr("ignore_index", -100)
    valid = (lab != ignore)[..., None]
    dloss = jnp.where(valid, dloss, 0.0)
    p_scaled = jnp.where(valid, p_scaled, 0.0)
    if eps:
        grad = p_scaled - (eps / vocab) * dloss
        hit = (1.0 - eps) * dloss
    else:
        grad = p_scaled
        hit = dloss
    # one-hot as a fused iota==label compare: elementwise over [N,V],
    # no scatter temp, no materialized one-hot -- the whole expression
    # collapses into the single bf16 output pass
    iota = jnp.arange(vocab, dtype=jnp.int32)
    onehot = (iota == lab[..., None])
    grad = grad - jnp.where(onehot, hit, 0.0)
    return {"Logits@GRAD": grad.astype(logits.dtype)}


@register_op("softmax_with_cross_entropy", grad_maker=_swce_grad_maker)
def softmax_with_cross_entropy(ctx):
    """Reduction-form xent: loss = lse(logits) - logits[label].

    With a 32k vocab the [N, V] tensors dominate HBM traffic, so the
    kernel never materializes an fp32 log-softmax: logits stay in
    their storage dtype (bf16 under AMP -- this op is on the amp KEEP
    list and manages its own precision), the logsumexp reduction
    accumulates in fp32 on the fly, and the label logit is a gather.
    The Softmax output is only computed when a consumer fetches it
    (XLA dead-codes it otherwise)."""
    logits = ctx.input("Logits")
    label = ctx.input("Label")
    eps = ctx.attr("label_smooth_eps", 0.0)
    if not ctx.attr("soft_label", False):
        from .pallas import xent as pallas_xent

        routed = pallas_xent.maybe_route(logits, label)
        if routed is not None:
            l2, lab1 = routed
            loss_flat, lse_flat = pallas_xent.xent_forward(
                l2, lab1, eps=eps,
                ignore_index=ctx.attr("ignore_index", -100))
            loss = loss_flat.reshape(logits.shape[:-1] + (1,))
            # Softmax output stays a jnp expression off the pallas lse:
            # XLA dead-codes it when (as in every model here) nothing
            # consumes the Softmax slot
            sm = jnp.exp(logits.astype(jnp.float32)
                         - lse_flat.reshape(
                             logits.shape[:-1] + (1,)))
            return {"Loss": loss, "Softmax": sm}
    lf = logits.astype(jnp.float32)  # fuses into the reductions below
    lse = jax.scipy.special.logsumexp(lf, axis=-1, keepdims=True)
    if ctx.attr("soft_label", False):
        # sum(label * (lse - logits)) = lse - sum(label * logits)
        loss = lse - jnp.sum(label.astype(jnp.float32) * lf, axis=-1,
                             keepdims=True)
        if eps:
            uniform = lse - jnp.mean(lf, axis=-1, keepdims=True)
            loss = (1.0 - eps) * loss + eps * uniform
    else:
        lab = label.astype(jnp.int32)
        if lab.ndim == logits.ndim:
            lab = lab[..., 0]
        ignore = ctx.attr("ignore_index", -100)
        valid = lab != ignore
        safe = jnp.where(valid, lab, 0)
        picked = jnp.take_along_axis(lf, safe[..., None], axis=-1)
        loss = lse - picked
        if eps:
            # smoothed target (1-eps)*onehot + eps/V without the [N,V]
            # one-hot: mean_j(lse - logits_j) = lse - mean(logits)
            uniform = lse - jnp.mean(lf, axis=-1, keepdims=True)
            loss = (1.0 - eps) * loss + eps * uniform
        loss = jnp.where(valid[..., None], loss, 0.0)
    sm = jnp.exp(lf - lse)
    return {"Loss": loss, "Softmax": sm}


@register_op("cross_entropy", stop_gradient_slots=("Label",))
def cross_entropy(ctx):
    x = ctx.input("X")  # probabilities
    label = ctx.input("Label")
    if ctx.attr("soft_label", False):
        return -jnp.sum(label * jnp.log(x + 1e-20), axis=-1, keepdims=True)
    lab = label.astype(jnp.int32)
    if lab.ndim == x.ndim:
        lab = lab[..., 0]
    p = jnp.take_along_axis(x, lab[..., None], axis=-1)
    return -jnp.log(p + 1e-20)


@register_op("sigmoid_cross_entropy_with_logits",
             stop_gradient_slots=("Label",))
def sigmoid_ce_logits(ctx):
    x = ctx.input("X")
    label = ctx.input("Label")
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = ctx.attr("ignore_index", -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    if ctx.attr("normalize", False):
        n = jnp.maximum(jnp.sum(label != ignore).astype(x.dtype), 1.0)
        loss = loss / n
    return loss


@register_op("square_error_cost")
def square_error_cost(ctx):
    d = ctx.input("X") - ctx.input("Y")
    return d * d


@register_op("huber_loss")
def huber_loss(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    delta = ctx.attr("delta", 1.0)
    r = y - x
    a = jnp.abs(r)
    quad = 0.5 * r * r
    lin = delta * (a - 0.5 * delta)
    loss = jnp.where(a <= delta, quad, lin)
    return {"Out": loss, "Residual": r}


@register_op("log_loss")
def log_loss(ctx):
    p = ctx.input("Predicted")
    y = ctx.input("Labels")
    eps = ctx.attr("epsilon", 1e-4)
    return -y * jnp.log(p + eps) - (1 - y) * jnp.log(1 - p + eps)


@register_op("smooth_l1_loss")
def smooth_l1_loss(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    sigma = ctx.attr("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    iw = ctx.input("InsideWeight")
    if iw is not None:
        d = d * iw
    a = jnp.abs(d)
    loss = jnp.where(a < 1.0 / s2, 0.5 * d * d * s2, a - 0.5 / s2)
    ow = ctx.input("OutsideWeight")
    if ow is not None:
        loss = loss * ow
    red = loss.reshape(loss.shape[0], -1).sum(axis=1, keepdims=True)
    return {"Out": red, "Diff": d}


@register_op("hinge_loss")
def hinge_loss(ctx):
    logits = ctx.input("Logits")
    labels = ctx.input("Labels")
    return jnp.maximum(0.0, 1.0 - (2.0 * labels - 1.0) * logits)


@register_op("margin_rank_loss")
def margin_rank_loss(ctx):
    x1, x2 = ctx.input("X1"), ctx.input("X2")
    label = ctx.input("Label")
    margin = ctx.attr("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": out, "Activated": (out > 0).astype(x1.dtype)}


@register_op("bpr_loss", stop_gradient_slots=("Label",))
def bpr_loss(ctx):
    x = ctx.input("X")
    label = ctx.input("Label").astype(jnp.int32)
    if label.ndim == x.ndim:
        label = label[..., 0]
    pos = jnp.take_along_axis(x, label[..., None], axis=-1)
    diff = x - pos
    loss = jnp.log1p(jnp.exp(diff))
    n = x.shape[-1]
    mask = 1.0 - jax.nn.one_hot(label, n, dtype=x.dtype)
    return jnp.sum(loss * mask, axis=-1, keepdims=True) / (n - 1)


@register_op("kldiv_loss", stop_gradient_slots=("Target",))
def kldiv_loss(ctx):
    x = ctx.input("X")  # log-probabilities
    t = ctx.input("Target")
    loss = t * (jnp.log(jnp.maximum(t, 1e-20)) - x)
    red = ctx.attr("reduction", "mean")
    if red == "mean":
        return jnp.mean(loss).reshape(1)
    if red == "sum":
        return jnp.sum(loss).reshape(1)
    if red == "batchmean":
        return (jnp.sum(loss) / x.shape[0]).reshape(1)
    return loss


# --------------------------------------------------------------------------
# dropout (mask saved for deterministic grad, reference dropout_op.cc)
# --------------------------------------------------------------------------
def _dropout_grad_maker(op, no_grad_set=frozenset()):
    from ..core.registry import is_registered, register_op as _reg

    if not is_registered("dropout_grad"):
        _reg("dropout_grad", differentiable=False)(_dropout_grad_kernel)
    inputs = {"Mask": op.outputs["Mask"],
              "Out@GRAD": [grad_var_name(n) for n in op.outputs["Out"]]}
    outputs = {"X@GRAD": [grad_var_name(n) for n in op.inputs["X"]]}
    return [Operator(op.block, "dropout_grad", inputs, outputs,
                     dict(op.attrs))]


def _dropout_grad_kernel(ctx):
    dy = ctx.input("Out@GRAD")
    mask = ctx.input("Mask")
    p = ctx.attr("dropout_prob", 0.5)
    impl = ctx.attr("dropout_implementation", "downgrade_in_infer")
    if ctx.attr("is_test", False):
        if impl == "upscale_in_train":
            return {"X@GRAD": dy}
        return {"X@GRAD": dy * (1.0 - p)}
    if impl == "upscale_in_train":
        scale = 1.0 / max(1.0 - p, 1e-8)
        return {"X@GRAD": dy * mask * scale}
    return {"X@GRAD": dy * mask}


@register_op("dropout", grad_maker=_dropout_grad_maker, needs_rng=True)
def dropout(ctx):
    x = ctx.input("X")
    p = ctx.attr("dropout_prob", 0.5)
    is_test = ctx.attr("is_test", False)
    impl = ctx.attr("dropout_implementation", "downgrade_in_infer")
    if is_test:
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
        return {"Out": out, "Mask": jnp.ones_like(x)}
    seed = ctx.attr("seed", 0)
    key = jax.random.PRNGKey(seed) if seed else ctx.rng()
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape).astype(x.dtype)
    if impl == "upscale_in_train":
        out = x * keep / max(1.0 - p, 1e-8)
    else:
        out = x * keep
    return {"Out": out, "Mask": keep}


# --------------------------------------------------------------------------
# embedding (reference lookup_table_op.cc; SelectedRows grad -> scatter-add)
# --------------------------------------------------------------------------
def _lookup_grad_maker(op, no_grad_set=frozenset()):
    from ..core.registry import is_registered, register_op as _reg

    if not is_registered("lookup_table_grad"):
        _reg("lookup_table_grad", differentiable=False)(
            _lookup_grad_kernel)
    inputs = {"W": op.inputs["W"], "Ids": op.inputs["Ids"],
              "Out@GRAD": [grad_var_name(n) for n in op.outputs["Out"]]}
    w = op.inputs["W"][0]
    if w in no_grad_set:
        return []
    outputs = {"W@GRAD": [grad_var_name(w)]}
    return [Operator(op.block, "lookup_table_grad", inputs, outputs,
                     dict(op.attrs))]


def _lookup_grad_kernel(ctx):
    w = ctx.input("W")
    ids = ctx.input("Ids").astype(jnp.int32)
    dy = ctx.input("Out@GRAD")
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    flat_ids = ids.reshape(-1)
    flat_dy = dy.reshape(-1, w.shape[-1])
    padding_idx = ctx.attr("padding_idx", -1)
    if padding_idx is not None and padding_idx >= 0:
        keep = (flat_ids != padding_idx).astype(flat_dy.dtype)
        flat_dy = flat_dy * keep[:, None]
    dw = jnp.zeros_like(w).at[flat_ids].add(flat_dy)
    return {"W@GRAD": dw}


@register_op("lookup_table", grad_maker=_lookup_grad_maker,
             stop_gradient_slots=("Ids",))
def lookup_table(ctx):
    w = ctx.input("W")
    ids = ctx.input("Ids").astype(jnp.int32)
    squeeze_last = ids.ndim >= 2 and ids.shape[-1] == 1
    if squeeze_last:
        ids = ids[..., 0]
    out = jnp.take(w, ids, axis=0)
    padding_idx = ctx.attr("padding_idx", -1)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx).astype(w.dtype)[..., None]
        out = out * mask
    return out


@register_op("lookup_table_v2", grad_maker=_lookup_grad_maker,
             stop_gradient_slots=("Ids",))
def lookup_table_v2(ctx):
    return lookup_table(ctx)


@register_op("embedding_grad_dense_to_sparse", differentiable=False)
def embedding_grad_dense_to_sparse(ctx):
    # capability surface for SelectedRows-style sparse grads: returns the
    # unique rows + their grads (reference selected_rows.h:32 analogue)
    return ctx.input("X")


# --------------------------------------------------------------------------
# metrics (reference metrics/accuracy_op.cc, auc_op.cc)
# --------------------------------------------------------------------------
@register_op("accuracy", differentiable=False)
def accuracy(ctx):
    indices = ctx.input("Indices")
    label = ctx.input("Label").astype(indices.dtype)
    if label.ndim == 1:
        label = label[:, None]
    correct = jnp.any(indices == label, axis=-1)
    total = correct.shape[0]
    num_correct = jnp.sum(correct.astype(jnp.float32))
    acc = (num_correct / total).reshape(1)
    return {"Accuracy": acc,
            "Correct": num_correct.astype(jnp.int32).reshape(1),
            "Total": jnp.array([total], dtype=jnp.int32)}


@register_op("auc", differentiable=False)
def auc(ctx):
    """Streaming AUC via histogram buckets (reference auc_op.cc)."""
    preds = ctx.input("Predict")
    label = ctx.input("Label").reshape(-1)
    stat_pos = ctx.input("StatPos")
    stat_neg = ctx.input("StatNeg")
    num_thresholds = ctx.attr("num_thresholds", 4095)
    pos_prob = preds[:, -1] if preds.ndim == 2 else preds.reshape(-1)
    bucket = jnp.clip((pos_prob * num_thresholds).astype(jnp.int32), 0,
                      num_thresholds)
    is_pos = (label > 0).astype(stat_pos.dtype)
    new_pos = stat_pos.at[bucket].add(is_pos)
    new_neg = stat_neg.at[bucket].add(1 - is_pos)
    # compute AUC from histograms (trapezoid over thresholds)
    tot_pos = jnp.cumsum(new_pos[::-1])[::-1]
    tot_neg = jnp.cumsum(new_neg[::-1])[::-1]
    tp = tot_pos
    fp = tot_neg
    p_total = jnp.maximum(tp[0], 1)
    n_total = jnp.maximum(fp[0], 1)
    tpr = tp / p_total
    fpr = fp / n_total
    auc_val = -jnp.trapezoid(tpr, fpr)
    return {"AUC": auc_val.reshape(1).astype(jnp.float32),
            "StatPosOut": new_pos, "StatNegOut": new_neg}


@register_op("mean_iou", differentiable=False)
def mean_iou(ctx):
    pred = ctx.input("Predictions").reshape(-1).astype(jnp.int32)
    label = ctx.input("Labels").reshape(-1).astype(jnp.int32)
    n = ctx.attr("num_classes")
    inter = jnp.zeros(n).at[jnp.where(pred == label, pred, n - 1)].add(
        (pred == label).astype(jnp.float32))
    pred_cnt = jnp.zeros(n).at[pred].add(1.0)
    lab_cnt = jnp.zeros(n).at[label].add(1.0)
    union = pred_cnt + lab_cnt - inter
    iou = jnp.where(union > 0, inter / jnp.maximum(union, 1e-9), 0.0)
    valid = (union > 0).astype(jnp.float32)
    miou = jnp.sum(iou) / jnp.maximum(jnp.sum(valid), 1.0)
    return {"OutMeanIou": miou.reshape(1), "OutWrong": union,
            "OutCorrect": inter}


# --------------------------------------------------------------------------
# fused scaled-dot-product attention -- the framework-level attention op.
# Routes to the Pallas flash-attention kernel on TPU for supported shapes
# (ops/pallas/attention.py); falls back to the jnp composition (which XLA
# still fuses well). The reference has no fused attention op -- attention
# exists only as a layer composition (reference nets.py
# scaled_dot_product_attention) -- so this op is a TPU-first upgrade.
# --------------------------------------------------------------------------
@register_op("ffn_block")
def ffn_block_op(ctx):
    """Whole-layer fused position-wise MLP: ONE op for
    relu(x @ W1 + b1) @ W2 + b2 (the MLP half of PERF.md's
    whole-layer-fusion lever; kernel in ops/pallas/ffn_block.py).
    Grads flow through the kernel's custom_vjp (hidden recomputed,
    never stored to HBM)."""
    x = ctx.input("X")
    w1, b1 = ctx.input("W1"), ctx.input("B1")
    w2, b2 = ctx.input("W2"), ctx.input("B2")
    from .pallas import ffn_block as FB, note_route

    if note_route("ffn_block", x.shape, FB.usable(x, w1)):
        return {"Out": FB.ffn_block(x, w1, b1, w2, b2)}
    return {"Out": FB.ffn_block_reference(x, w1, b1, w2, b2)}


@register_op("attention_block")
def attention_block_op(ctx):
    """Whole-layer fused self-attention sub-layer: ONE op for
    x @ Wqkv -> split-heads SDPA -> merge -> @ Wo (the PERF.md
    whole-layer-fusion lever; kernel in ops/pallas/attention_block.py).
    Replaces the 7-op sequence multi_head_attention otherwise emits;
    grads come from the generic vjp, which flows through the kernel's
    custom_vjp (saved-P backward, zero exps)."""
    x = ctx.input("X")
    wqkv = ctx.input("WQKV")
    wo = ctx.input("WO")
    n_heads = int(ctx.attr("n_heads"))
    scale = ctx.attr("scale", None)
    if scale is None:
        scale = (x.shape[-1] // n_heads) ** -0.5
    causal = ctx.attr("causal", False)
    from .pallas import attention_block as AB, note_route

    if note_route("attention_block", x.shape,
                  AB.usable(x, wqkv, n_heads)):
        out = AB.attention_block(x, wqkv, wo, n_heads, float(scale),
                                 bool(causal))
    else:
        out = AB.attention_block_reference(x, wqkv, wo, n_heads,
                                           float(scale), bool(causal))
    return {"Out": out}


@register_op("attention", needs_rng=True)
def attention(ctx):
    """layout attr: 'bhtd' (default) or 'bthd'. The bthd form takes
    q/k/v straight from the head-split reshape WITHOUT a physical
    [B,T,H,D]->[B,H,T,D] transpose -- dot_general batches over h in
    place, which removed ~30ms/step of transpose+copy HLOs from
    transformer-base (profiled on v5e; the transposes and their jvp
    duals were ~15% of device time). The pallas flash kernel keeps its
    bhtd contract, so routes through transposes only when it is
    actually selected (long sequences)."""
    q = ctx.input("Q")
    k = ctx.input("K")
    v = ctx.input("V")
    scale = ctx.attr("scale", None)
    causal = ctx.attr("causal", False)
    layout = ctx.attr("layout", "bhtd")
    dropout_rate = ctx.attr("dropout_rate", 0.0)
    if ctx.attr("is_test", False):
        dropout_rate = 0.0
    if scale is None:
        scale = q.shape[-1] ** -0.5
    from . import pallas
    from .pallas import attention as pallas_attn
    from ..parallel import ring_attention as ra

    def to_bhtd(x):
        return jnp.swapaxes(x, 1, 2) if layout == "bthd" else x

    qh, kh, vh = to_bhtd(q), to_bhtd(k), to_bhtd(v)
    group = qh.shape[1] // kh.shape[1]
    if group > 1:
        # grouped-query attention: a key-value head is shared by
        # `group` query heads. The flash kernel reads it in place;
        # every other path sees the key-value heads repeated.
        if dropout_rate == 0.0 and pallas.note_route(
                "flash_attention", qh.shape,
                qh.shape[2] > 512 and pallas_attn.usable(qh, kh, vh)):
            return to_bhtd(pallas_attn.flash_attention(
                qh, kh, vh, scale=scale, causal=causal))
        k, v = (jnp.repeat(x, group, axis=2 if layout == "bthd" else 1)
                for x in (k, v))
        kh, vh = to_bhtd(k), to_bhtd(v)
    if ra.cp_applicable(qh, kh, vh, dropout_rate):
        return to_bhtd(ra.cp_attention(qh, kh, vh, scale, causal))
    if dropout_rate == 0.0:
        if pallas.note_route("sdpa_short", qh.shape,
                             pallas_attn.sdpa_usable(qh, kh, vh)):
            # short-T fused SDPA: scores never touch HBM and the
            # backward reuses the saved probabilities instead of
            # re-exping (the VPU exp rate is the floor at short T --
            # see the kernel's module comment). Worth the bthd
            # transposes at every size it accepts.
            return to_bhtd(pallas_attn.sdpa_short(
                qh, kh, vh, scale=scale, causal=causal))
        if pallas.note_route(
                "flash_attention", qh.shape,
                qh.shape[2] > 512 and pallas_attn.usable(qh, kh, vh)):
            # flash wins only at long T (its b*h-programs grid is
            # launch-overhead-bound below that -- measured slower than
            # the jnp composition at T<=512 on v5e, either layout)
            return to_bhtd(pallas_attn.flash_attention(
                qh, kh, vh, scale=scale, causal=causal))
        if layout == "bthd":
            return _attention_bthd(q, k, v, scale, causal)
        return pallas.reference_attention(q, k, v, scale, causal)
    # dropout between softmax and the V product forces the inline form
    return to_bhtd(_sdpa(qh, kh, vh, scale, causal, "bhtd",
                         dropout_rate=dropout_rate, rng=ctx.rng()))


def _attention_bthd(q, k, v, scale, causal):
    return _sdpa(q, k, v, scale, causal, "bthd")


def _sdpa(q, k, v, scale, causal, layout, dropout_rate=0.0, rng=None):
    """The one masked-softmax attention body behind both layouts and
    the dropout path (pallas.reference_attention stays a deliberately
    independent oracle for kernel tests). QK^T and PV accumulate in
    fp32 via preferred_element_type -- bf16 inputs stay in HBM, the
    MXU accumulator carries the precision, matching the flash kernel's
    numerics."""
    if layout == "bthd":
        qk, pv = "bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd"
    else:
        qk, pv = "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd"
    s = jnp.einsum(qk, q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool), tk - tq)
        s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, p.shape)
        p = p * keep / (1.0 - dropout_rate)
    out = jnp.einsum(pv, p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# fc: fused mul+add+act (reference operators/fc_op-era fc; produced by
# ir.fc_fuse_pass like ir/fc_fuse_pass.cc produces the fc op)
# --------------------------------------------------------------------------
@register_op("fc")
def fc(ctx):
    from .math_ops import _flatten2d

    x = ctx.input("Input")
    w = ctx.input("W")
    b = ctx.input("Bias")
    ncd = ctx.attr("in_num_col_dims", 1)
    x2 = _flatten2d(x, ncd)
    out = jnp.matmul(x2, jnp.reshape(w, (x2.shape[-1], -1)))
    if b is not None:
        out = out + jnp.reshape(b, (1, -1))
    # restore the leading dims the mul op would have kept (mul_op.cc
    # reshapes to x.shape[:ncd] + y.shape[ync:])
    out = jnp.reshape(out, x.shape[:ncd] + (out.shape[-1],))
    act = ctx.attr("activation_type", "")
    if act == "relu":
        out = jax.nn.relu(out)
    elif act == "tanh":
        out = jnp.tanh(out)
    elif act == "softmax":
        out = jax.nn.softmax(out, axis=-1)
    elif act:
        raise ValueError(f"fc: unsupported activation {act!r}")
    return {"Out": out}


@register_op("adaptive_pool3d")
def adaptive_pool3d(ctx):
    """reference operators/pool_op.cc adaptive path, 3-D: NCDHW input
    pooled to pooling_size output cells (divisible case, like
    adaptive_pool2d above)."""
    x = ctx.input("X")
    od, oh, ow = ctx.attr("pooling_size", [1, 1, 1])
    ptype = ctx.attr("pooling_type", "avg")
    n, c, d, h, w = x.shape
    x7 = x.reshape(n, c, od, d // od, oh, h // oh, ow, w // ow)
    if ptype == "avg":
        return x7.mean(axis=(3, 5, 7))
    return x7.max(axis=(3, 5, 7))
