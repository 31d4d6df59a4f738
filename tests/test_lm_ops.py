"""The decoder-only language-model ops (ops/lm_ops.py), the dropless
expert routing (parallel/moe.py) and grouped-query attention through
the `attention` op: OpTests against numpy, the Pallas paths in
interpret mode against their references, and what the executor
learned for them (integer outputs under the generic vjp maker, device
scopes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import amp, layers
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import attention as pallas_attn
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.parallel import moe

from op_test import OpTest


def _silu(x):
    return x / (1.0 + np.exp(-x))


class TestRmsNorm(OpTest):
    def setUp(self):
        super().setUp()
        self.op_type = "rms_norm"
        x = np.random.randn(2, 5, 16).astype("float32")
        g = np.random.rand(16).astype("float32") + 0.5
        self.inputs = {"X": x, "Scale": g}
        self.attrs = {"epsilon": 1e-5}
        self.outputs = {"Y": x / np.sqrt(
            (x ** 2).mean(-1, keepdims=True) + 1e-5) * g}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X", "Scale"], "Y")


class TestRotaryEmbedding(OpTest):
    def setUp(self):
        super().setUp()
        self.op_type = "rotary_embedding"
        x = np.random.randn(2, 6, 3, 8).astype("float32")
        theta = 100.0
        inv = 1.0 / theta ** (np.arange(0, 8, 2) / 8)
        ang = np.arange(6)[:, None] * inv
        ang = np.concatenate([ang, ang], -1)[None, :, None, :]
        rot = np.concatenate([-x[..., 4:], x[..., :4]], -1)
        self.inputs = {"X": x}
        self.attrs = {"theta": theta}
        self.outputs = {"Out": x * np.cos(ang) + rot * np.sin(ang)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X"], "Out")

    def test_position_zero_is_the_identity(self):
        out = self.outputs["Out"]
        assert np.allclose(out[:, 0], self.inputs["X"][:, 0])


class TestSwiglu(OpTest):
    def setUp(self):
        super().setUp()
        self.op_type = "swiglu"
        x = np.random.randn(3, 4, 12).astype("float32")
        self.inputs = {"X": x}
        self.outputs = {"Out": _silu(x[..., :6]) * x[..., 6:]}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X"], "Out")


class TestShortConv(OpTest):
    def setUp(self):
        super().setUp()
        self.op_type = "short_conv"
        d, taps, t = 4, 3, 7
        x = np.random.randn(2, t, 3 * d).astype("float32")
        w = np.random.randn(d, taps).astype("float32")
        b, c, z = x[..., :d], x[..., d:2 * d], x[..., 2 * d:]
        v = b * z
        conv = np.zeros_like(v)
        for pos in range(t):
            for j in range(taps):
                src = pos - (taps - 1) + j
                if src >= 0:
                    conv[:, pos] += w[:, j] * v[:, src]
        self.inputs = {"X": x, "Filter": w}
        self.outputs = {"Out": c * conv}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X", "Filter"], "Out")

    def test_no_position_sees_a_later_one(self):
        """Causal: changing position 5 leaves positions 0..4 alone."""
        from paddle_tpu.core.registry import OpContext, get_op_info

        class _Op:
            attrs, type = {}, "short_conv"
        x = self.inputs["X"].copy()

        def run(x):
            ctx = OpContext(_Op(), {"X": [jnp.asarray(x)], "Filter": [
                jnp.asarray(self.inputs["Filter"])]})
            return np.asarray(get_op_info("short_conv").kernel(ctx)["Out"])
        before = run(x)
        x[:, 5] += 1.0
        after = run(x)
        assert np.array_equal(before[:, :5], after[:, :5])
        assert not np.allclose(before[:, 5:], after[:, 5:])


# ---------------------------------------------------------------------
# dropless routing
# ---------------------------------------------------------------------
T, D, F, E, K = 64, 128, 128, 16, 4     # widths the kernels' tiles admit


@pytest.fixture
def moe_weights():
    rng = np.random.default_rng(3)
    return {"x": jnp.asarray(rng.standard_normal((T, D)), jnp.float32),
            "wg": jnp.asarray(rng.standard_normal((D, E)) * 0.4,
                              jnp.float32),
            "b": jnp.asarray(rng.standard_normal(E) * 0.1, jnp.float32),
            "w13": jnp.asarray(rng.standard_normal((E, D, 2 * F)) * 0.2,
                               jnp.float32),
            "w2": jnp.asarray(rng.standard_normal((E, F, D)) * 0.2,
                              jnp.float32)}


def _naive(p, lo, n, bias=None):
    """A loop over the held experts with a mask."""
    bias = p["b"] if bias is None else bias
    s = jax.nn.sigmoid(p["x"] @ p["wg"])
    _, idx = jax.lax.top_k(s + bias, K)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-6)
    out = jnp.zeros_like(p["x"])
    for e in range(lo, lo + n):
        h = p["x"] @ p["w13"][e]
        y = (jax.nn.silu(h[:, :F]) * h[:, F:]) @ p["w2"][e]
        out = out + jnp.where(idx == e, w, 0.0).sum(-1)[:, None] * y
    return out


def _share(p, lo, n, bias=None):
    return moe.moe_dropless(
        p["x"], p["wg"], p["b"] if bias is None else bias,
        p["w13"][lo:lo + n], p["w2"][lo:lo + n], lo, K)


@pytest.mark.parametrize("lo,n", [(0, 16), (4, 4), (12, 4), (0, 1)])
def test_share_is_the_held_experts_part(moe_weights, lo, n):
    out, idx, load, pairs = _share(moe_weights, lo, n)
    assert np.allclose(out, _naive(moe_weights, lo, n), atol=1e-5)
    assert idx.shape == (T, K) and idx.dtype == jnp.int32
    counts = np.bincount(np.asarray(idx).ravel(), minlength=E)
    assert np.array_equal(np.asarray(load), counts[lo:lo + n])
    assert int(pairs[0]) == counts[lo:lo + n].sum()


def test_shares_add_up_to_the_whole_layer(moe_weights):
    whole = _naive(moe_weights, 0, E)
    parts = sum(_share(moe_weights, lo, 4)[0] for lo in range(0, E, 4))
    assert np.allclose(parts, whole, atol=1e-5)
    assert int(sum(_share(moe_weights, lo, 4)[3][0]
                   for lo in range(0, E, 4))) == T * K    # nothing dropped


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("skew", [0.0, 5.0])
def test_nothing_is_dropped_whatever_the_routing(
        moe_weights, skew, interpret):
    """With a bias that sends every token's every choice to the held
    experts the layer still matches, values and gradients, on the
    reference products and on the megablox kernels (interpret mode)."""
    pallas_attn.force_interpret(interpret)
    try:
        p = moe_weights
        bias = p["b"].at[4:8].add(skew)
        pairs = int(_share(p, 4, 4, bias)[3][0])
        assert (pairs == T * K) if skew else (0 < pairs < T * K)

        def loss(f):
            return lambda x, wg, w13, w2: jnp.sum(jnp.square(f(
                {**p, "x": x, "wg": wg, "w13": w13, "w2": w2})))
        args = (p["x"], p["wg"], p["w13"], p["w2"])
        want = jax.value_and_grad(
            loss(lambda q: _naive(q, 4, 4, bias)), (0, 1, 2, 3))(*args)
        with pallas.record_routes() as routes:
            got = jax.value_and_grad(
                loss(lambda q: _share(q, 4, 4, bias)[0]),
                (0, 1, 2, 3))(*args)
        assert np.allclose(got[0], want[0], rtol=1e-5)
        for a, b in zip(got[1], want[1]):
            assert np.allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()))
        assert {r[2] for r in routes if r[0] == "grouped_matmul"} \
            == {interpret}
    finally:
        pallas_attn.force_interpret(False)


def test_bias_enters_the_choice_only(moe_weights):
    p = moe_weights
    idx0, w0 = moe.route_dropless(p["x"], p["wg"], jnp.zeros(E), K)
    idx1, w1 = moe.route_dropless(p["x"], p["wg"], p["b"], K)
    assert not np.array_equal(idx0, idx1)        # it changes choices
    same = np.asarray((np.sort(idx0) == np.sort(idx1)).all(-1))
    assert same.any()
    order0, order1 = np.argsort(idx0[same]), np.argsort(idx1[same])
    assert np.allclose(                           # and no weight
        np.take_along_axis(np.asarray(w0[same]), order0, -1),
        np.take_along_axis(np.asarray(w1[same]), order1, -1))
    assert np.allclose(np.asarray(w1).sum(-1), 1.0, atol=1e-4)
    g = jax.grad(lambda b: moe.route_dropless(
        p["x"], p["wg"], b, K)[1].sum())(p["b"])
    assert not np.any(np.asarray(g))


def test_grouped_matmul_reference_leaves_other_groups_zero():
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.standard_normal((12, 8)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((2, 8, 4)), jnp.float32)
    sizes = jnp.asarray([3, 4, 5], jnp.int32)
    out = np.asarray(gm.grouped_matmul(lhs, rhs, sizes))
    assert np.allclose(out[:3], lhs[:3] @ rhs[0], atol=1e-5)
    assert np.allclose(out[3:7], lhs[3:7] @ rhs[1], atol=1e-5)
    assert not out[7:].any()


# ---------------------------------------------------------------------
# grouped-query attention
# ---------------------------------------------------------------------
def _gqa_reference(q, k, v, scale, causal):
    g = q.shape[1] // k.shape[1]
    return pallas.reference_attention(q, jnp.repeat(k, g, 1),
                                      jnp.repeat(v, g, 1), scale, causal)


@pytest.mark.parametrize("h,hkv,tq,tk,causal", [
    (4, 2, 64, 64, True), (4, 4, 32, 64, True), (8, 2, 128, 128, False),
    (4, 1, 512, 512, True)])
def test_flash_kernels_read_shared_key_value_heads(h, hkv, tq, tk,
                                                   causal):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, h, tq, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, hkv, tk, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, hkv, tk, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    pallas_attn.force_interpret(True)
    try:
        assert pallas_attn.usable(q, k, v)
        got = jax.value_and_grad(lambda *a: jnp.sum(
            pallas_attn.flash_attention(*a, 0.125, causal) * w),
            (0, 1, 2))(q, k, v)
    finally:
        pallas_attn.force_interpret(False)
    want = jax.value_and_grad(lambda *a: jnp.sum(
        _gqa_reference(*a, 0.125, causal) * w), (0, 1, 2))(q, k, v)
    assert np.allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        assert a.shape == b.shape
        assert np.allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("layout", ["bthd", "bhtd"])
@pytest.mark.parametrize("t,interpret", [(16, False), (1024, True)])
def test_attention_op_takes_fewer_key_value_heads(layout, t, interpret):
    """Short sequences repeat the key-value heads for the jnp
    composition; long ones reach the flash kernel in place."""
    h, hkv, d = 4, 2, 64
    rng = np.random.default_rng(2)

    def arr(heads):
        shape = (1, t, heads, d) if layout == "bthd" else (1, heads, t, d)
        return rng.standard_normal(shape).astype("float32")
    q, k, v = arr(h), arr(hkv), arr(hkv)
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        qv, kv, vv = (layers.data(n, shape=list(a.shape[1:]))
                      for n, a in (("q", q), ("k", k), ("v", v)))
        out = layers.attention(qv, kv, vv, causal=True, scale=d ** -0.5,
                               layout=layout)
    pallas_attn.force_interpret(interpret)
    try:
        with pallas.record_routes() as routes:
            got, = fluid.Executor().run(
                prog, feed={"q": q, "k": k, "v": v}, fetch_list=[out])
    finally:
        pallas_attn.force_interpret(False)
    assert ("flash_attention", (1, h, t, d), True) in routes \
        if interpret else not any(r[2] for r in routes)

    def bhtd(a):
        return jnp.swapaxes(a, 1, 2) if layout == "bthd" else a
    want = bhtd(_gqa_reference(bhtd(q), bhtd(k), bhtd(v), d ** -0.5, True))
    assert np.allclose(got, want, atol=2e-5)


# ---------------------------------------------------------------------
# the program path
# ---------------------------------------------------------------------
def _moe_program(held=(4, 4)):
    prog, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, start):
        x = layers.data("x", shape=[8, D])
        x.stop_gradient = False
        with fluid.device_scope("unit.norm"):
            u = layers.rms_norm(x, param_attr="n.w")
        out, chosen, load, pairs = layers.moe_dropless(
            u, E, F, K, experts_held=held, name="m", scope="unit.moe")
        loss = layers.mean(out)
        fluid.backward.append_backward(loss)
    return prog, start, loss, (chosen, load, pairs)


def test_layer_trains_through_the_generic_vjp_maker():
    """Integer outputs (the chosen experts, the counts) take no
    cotangent; the bias is a buffer, no gradient reaches it."""
    prog, start, loss, extras = _moe_program()
    exe, scope = fluid.Executor(), fluid.core.scope.Scope()
    exe.run(start, scope=scope)
    x = np.random.default_rng(0).standard_normal((2, 8, D)).astype("f4")
    got = exe.run(prog, feed={"x": x}, scope=scope, fetch_list=[
        loss, *extras, "x@GRAD", "m_w13@GRAD", "m_gate.w@GRAD"])
    assert got[1].shape == (16, K) and got[1].dtype == np.int32
    assert got[2].shape == (4,) and got[2].sum() == got[3][0]
    assert all(np.isfinite(g).all() and np.abs(g).max() > 0
               for g in got[4:])
    names = {n for op in prog.global_block.ops
             for n in op.output_arg_names}
    assert "m_bias@GRAD" not in names
    assert not prog.global_block.var("m_bias").trainable


def test_device_scopes_reach_the_compiled_step():
    prog, start, loss, _ = _moe_program()
    tagged = {op.attrs.get("_device_scope") for op in prog.global_block.ops
              if op.type.startswith("rms_norm")}
    assert tagged == {"unit.norm"}          # forward and grad op alike
    exe, scope = fluid.Executor(), fluid.core.scope.Scope()
    exe.run(start, scope=scope)
    feed = {"x": np.zeros((2, 8, D), "float32")}
    with pytest.raises(RuntimeError, match="has not run"):
        exe.compiled_text(prog, feed, [loss], scope)
    exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
    text = exe.compiled_text(prog, feed, [loss], scope)
    for scope_name in ("unit.norm", "unit.moe.route", "unit.moe.experts",
                       "unit.moe.combine"):
        assert f"/{scope_name}/" in text, scope_name


def test_router_stays_float32_under_amp(moe_weights):
    """AMP runs the experts in bfloat16 and leaves the router alone:
    the experts chosen are the float32 router's, token for token."""
    assert "rms_norm" in amp.BLACK_LIST and "moe_dropless" in amp.KEEP_LIST
    prog, start, loss, (chosen, _, _) = _moe_program(held=(0, E))
    x = np.random.default_rng(5).standard_normal((2, 8, D)).astype("f4")
    picks = []
    for on in (False, True):
        exe, scope = fluid.Executor(), fluid.core.scope.Scope()
        with amp.amp_guard(on):
            exe.run(start, scope=scope)
            scope._set("m_gate.w", moe_weights["wg"])
            scope._set("m_bias", moe_weights["b"])
            picks.append(exe.run(prog, feed={"x": x}, scope=scope,
                                 fetch_list=[chosen])[0])
    assert np.array_equal(picks[0], picks[1])
