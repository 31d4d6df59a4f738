"""Olmo-Hybrid-7B (olmo_hybrid) on the serve engine at rehearsal sizes
on the CPU, float32 weights from the seed: the decoder-only bundle with
per-lane gated delta-rule state served by
PagedContinuousGenerationServer against the plain reference
(benchmark/chip/reference/olmo_hybrid.py) through prefill in chunks,
the lanes' state, the paged key-value cache and decoding; lanes reused
and lanes idle; the chunked delta rule against the stepped one and both
against the recurrence; beta past 1; the gated norm in both orders;
rotary positions at the rows' positions; no prefix reuse beside lane
state; the Nemotron bundle that shares the ops built as before."""
import json
import os

import numpy as np
import pytest

from benchmark.chip.reference import olmo_hybrid as R

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 11
# float32 everywhere, but the program sums in another order than the
# reference (the chunked form of the delta rule, attention a page at a
# time, the fused projections): a few tens of units of float32's last
# place on logits of magnitude one to ten over 80 positions (5.4e-5
# read). A state rounded to bfloat16 after every position moves the
# logits by more than twenty times this
# (test_a_bfloat16_state_fails_the_tolerance)
LOGIT_TOL = 1e-4
# the ops alone against a float64 recurrence, on values of magnitude one
OP_TOL = 2e-5


def sizes(**over):
    with open(os.path.join(HERE, "..", "benchmark", "chip", "configs",
                           "olmo-hybrid-7b-serve-pp4.json")) as f:
        config = json.load(f)
    return {**config["sizes"], **config["rehearsal"],
            "weight_dtype": "float32", **over}


def build(c, seed=SEED, **over):
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.models import olmo_hybrid as O

    from benchmark.chip.drivers.olmoh_serve import NOT_THE_BUILDERS

    model = {k: v for k, v in R.model_cfg(c).items()
             if k not in NOT_THE_BUILDERS}
    geometry = dict(n_slots=4, block_size=8, n_blocks=64, context=128,
                    max_new_tokens=16, chunk_sizes=(8, 32), max_chunks=4,
                    delta_block=c["delta_block"])
    geometry.update(over)
    with unique_name.guard():
        bundle = O.build_olmo_hybrid_serve_bundle(
            layers_pattern=c["layers"], dtype=c["weight_dtype"],
            probe_logits=True, **model, **geometry)
    scope, exe = Scope(), fluid.Executor(fluid.TPUPlace(0))
    for name, value in R.make_top(seed, c).items():
        scope._set(name, value)
    for i in range(len(c["layers"])):
        for name, value in R.make_layer(seed, c, i).items():
            scope._set(name, value)
    srv = PagedContinuousGenerationServer(
        bundle, executor=exe, scope=scope, record_probes=True,
        steps_per_tick=4, drain_steps=4)
    return srv, scope


def served(row):
    row = np.asarray(row)
    return row[1:1 + int((row[1:] >= 0).sum())]


def reference_of(c, prompt, row, fault=None):
    toks = served(row)
    want = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
    return R.forward(c, SEED, np.concatenate([prompt, toks]), want,
                     fault=fault)


# prompts whose rest (all but the last token) is cut into chunks of 32
# and 8 with true lengths 4, 5, 3 and 0 in the last one: chunk ends that
# are and are not multiples of the delta rule's block of 4
PROMPTS = (45, 5, 17, 33, 1, 70, 12)
NEWS = (6, 10, 16, 4, 8, 5, 7)


@pytest.fixture(scope="module")
def session():
    """One server of 4 lanes and 7 requests sent at once: lanes are
    admitted in different cycles and three requests take a lane that
    another has left."""
    c = sizes()
    srv, scope = build(c)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, c["vocab"], n) for n in PROMPTS]
    replies = [srv.submit(p, max_new_tokens=m)
               for p, m in zip(prompts, NEWS)]
    for r in replies:
        r.result(timeout=600)
    out = {"c": c, "prompts": prompts, "replies": replies, "srv": srv,
           "scope": scope, "stats": srv.pool_stats()}
    yield out
    srv.close()


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_served_logits_follow_the_reference(session, i):
    """Prefill in chunks, the lane's state and the paged cache, then
    decoding: every tick's logits against the reference's full forward
    pass over prompt and served tokens."""
    c, reply = session["c"], session["replies"][i]
    row = reply.result()
    ref = reference_of(c, session["prompts"][i], row)
    assert len(served(row)) == NEWS[i]
    assert np.abs(reply.probe["logits"] - ref["logits"]).max() < LOGIT_TOL
    assert list(served(row)) == list(ref["logits"].argmax(-1))


def test_a_bfloat16_state_fails_the_tolerance(session):
    """The tolerance is tight enough that the reference with its state
    rounded to bfloat16 after every position lies outside it."""
    c = session["c"]
    i = PROMPTS.index(70)
    row = session["replies"][i].result()
    exact = reference_of(c, session["prompts"][i], row)
    low = reference_of(c, session["prompts"][i], row, fault="state_bf16")
    assert np.abs(low["logits"] - exact["logits"]).max() > 20 * LOGIT_TOL


def test_the_bundle_says_what_a_lane_carries(session):
    c, bundle = session["c"], session["srv"].bundle
    state = bundle.lane_state
    linear = [i for i, k in enumerate(c["layers"]) if k == "L"]
    assert state["names"] == tuple(
        f"@olmoh/{kind}_{i}" for i in linear
        for kind in ("gdn_state", "conv_tail"))
    h, dk, dv = c["lin_heads"], c["lin_key_dim"], c["lin_value_dim"]
    assert state["shapes"][f"@olmoh/gdn_state_{linear[0]}"] == (h, dk, dv)
    tail = (c["conv_kernel"] - 1) * h * (2 * dk + dv) * 4   # float32
    assert state["bytes_per_lane"] == len(linear) * (h * dk * dv * 4
                                                     + tail)
    st = session["stats"]
    assert st["state_lanes"] == 4
    assert st["state_bytes"] == 5 * state["bytes_per_lane"]
    assert st["state_resets"] == len(PROMPTS)


def test_a_reused_lane_reads_as_a_fresh_one(session):
    """After the session every lane holds what its last request left.
    One more request takes the first lane: its logits are the
    reference's from zero state, and the other lanes' state is bit for
    bit what it was."""
    c, srv, scope = session["c"], session["srv"], session["scope"]
    names = srv.bundle.lane_state["names"]
    before = {n: np.asarray(scope._get(n)).copy() for n in names}
    assert all(np.abs(before[n][:4]).max() > 0 for n in names)
    prompt = np.random.default_rng(5).integers(3, c["vocab"], 21)
    reply = srv.submit(prompt, max_new_tokens=6)
    row = reply.result(timeout=600)
    ref = reference_of(c, prompt, row)
    assert np.abs(reply.probe["logits"] - ref["logits"]).max() < LOGIT_TOL
    for n in names:
        after = np.asarray(scope._get(n))
        assert (after[1:] == before[n][1:]).all(), n
        assert (after[0] != before[n][0]).any(), n


def test_a_turn_that_resends_its_history_is_prefilled_whole(session):
    """A second turn whose prompt is the first's prompt, the reply and
    a new message: nothing is found cached, the whole prompt is
    prefilled again, and the logits are the reference's."""
    c, srv = session["c"], session["srv"]
    st0 = srv.pool_stats()
    rng = np.random.default_rng(6)
    first = rng.integers(3, c["vocab"], 40)
    row = srv.submit(first, max_new_tokens=8).result(timeout=600)
    second = np.concatenate([first, served(row),
                             rng.integers(3, c["vocab"], 9)])
    reply = srv.submit(second, max_new_tokens=8, cache_tokens=len(second))
    ref = reference_of(c, second, reply.result(timeout=600))
    assert np.abs(reply.probe["logits"] - ref["logits"]).max() < LOGIT_TOL
    st = srv.pool_stats()
    assert st["prefix_reuse_skipped"] - st0["prefix_reuse_skipped"] == 2
    assert st["cached_prompt_tokens"] == 0 and st["radix_nodes"] == 0
    assert st["prefill_tokens"] - st0["prefill_tokens"] \
        == len(first) - 1 + len(second) - 1


# ---------------------------------------------------------------------
# the ops by themselves
# ---------------------------------------------------------------------
class Ctx:
    """What a kernel sees of its op: inputs by slot, attributes."""

    def __init__(self, attrs=None, **inputs):
        self.inputs, self.attrs = inputs, attrs or {}

    def input(self, slot):
        return self.inputs.get(slot)

    def attr(self, name, default=None):
        return self.attrs.get(name, default)


def _recurrence(q, k, v, b, a, dt_bias, a_log, s0, beta_scale=2.0):
    """The delta rule position by position in numpy, float64; q, k [T,
    H*Dk], v [T, H*Dv], b, a [T, H]; s0 [H, Dk, Dv]."""
    h, dk, dv = s0.shape
    s = np.asarray(s0, np.float64).copy()
    ys = []
    for t in range(len(q)):
        qt, kt = (np.asarray(x[t], np.float64).reshape(h, dk)
                  for x in (q, k))
        qt = qt / np.sqrt((qt * qt).sum(-1, keepdims=True) + 1e-6) \
            * dk ** -0.5
        kt = kt / np.sqrt((kt * kt).sum(-1, keepdims=True) + 1e-6)
        vt = np.asarray(v[t], np.float64).reshape(h, dv)
        beta = beta_scale / (1 + np.exp(-np.asarray(b[t], np.float64)))
        g = -np.exp(np.asarray(a_log, np.float64)) * np.log1p(
            np.exp(np.asarray(a[t], np.float64) + dt_bias))
        s = np.exp(g)[:, None, None] * s
        read = np.einsum("hk,hkv->hv", kt, s)
        s = s + kt[:, :, None] * (beta[:, None] * (vt - read))[:, None]
        ys.append(np.einsum("hk,hkv->hv", qt, s).reshape(-1))
    return np.stack(ys), s


def _rows(rng, t, h=3, dk=8, dv=12, beta_gain=2.0):
    """Random inputs of t rows; beta logits of deviation `beta_gain`,
    so that beta = 2 sigmoid(b) lies past 1 for about half of them."""
    return dict(
        q=rng.normal(size=(t, h * dk)).astype(np.float32),
        k=rng.normal(size=(t, h * dk)).astype(np.float32),
        v=rng.normal(size=(t, h * dv)).astype(np.float32),
        b=(rng.normal(size=(t, h)) * beta_gain).astype(np.float32),
        a=rng.normal(size=(t, h)).astype(np.float32))


def _chunk(rows, state, length, pos, block, lane=1):
    import jax.numpy as jnp
    from paddle_tpu.ops import delta_ops

    return delta_ops.gated_delta_chunk(Ctx(
        {"block": block, "allow_neg_eigval": True},
        Q=jnp.asarray(rows["q"]), K=jnp.asarray(rows["k"]),
        V=jnp.asarray(rows["v"]), Beta=rows["b"], A=rows["a"],
        DtBias=rows["dt_bias"], ALog=rows["a_log"],
        State=jnp.asarray(state), Lane=np.array([lane]),
        Len=np.array([length]), Pos=np.array([pos])))


def _leaves(rng, h=3):
    return {"dt_bias": (rng.normal(size=h) - 2.0).astype(np.float32),
            "a_log": np.log(rng.uniform(1, 16, h)).astype(np.float32)}


@pytest.mark.parametrize("length,block,pos", [
    (13, 4, 24), (16, 4, 24), (5, 16, 9), (9, 3, 40), (16, 8, 0),
    (11, 8, 0), (1, 4, 7)])
def test_a_chunk_from_a_state_with_padding_equals_the_recurrence(
        length, block, pos):
    """gated_delta_chunk on a chunk of 16 rows of which `length` are
    real, from a lane's stored state where the chunk starts mid-lane
    (pos > 0) and from zero where it starts at 0: the real rows' output
    and the state left behind are the recurrence's over the real rows
    (chunks that end in padding and cross the inner block's boundary);
    the other lanes' state is untouched."""
    rng = np.random.default_rng(length * 100 + block)
    rows = {**_rows(rng, 16), **_leaves(rng)}
    state = rng.normal(size=(3, 3, 8, 12)).astype(np.float32)
    out = _chunk(rows, state, length, pos, block)
    s0 = state[1] if pos else np.zeros_like(state[1])
    y, s = _recurrence(*(rows[k][:length] for k in "qkvba"),
                       rows["dt_bias"], rows["a_log"], s0)
    assert np.abs(np.asarray(out["Y"])[:length] - y).max() < OP_TOL
    new = np.asarray(out["StateOut"])
    assert np.abs(new[1] - s).max() < OP_TOL
    assert (new[[0, 2]] == state[[0, 2]]).all()


def test_a_chunk_equals_its_rows_stepped_one_at_a_time():
    """The chunked form over 11 real rows of a chunk of 12 and the tick
    op fed the same rows one after another leave the same state and
    give the same outputs."""
    import jax.numpy as jnp
    from paddle_tpu.ops import delta_ops

    rng = np.random.default_rng(3)
    rows = {**_rows(rng, 12), **_leaves(rng)}
    state = rng.normal(size=(2, 3, 8, 12)).astype(np.float32)
    out = _chunk(rows, state, 11, 5, 4, lane=0)
    st, ys = jnp.asarray(state), []
    for t in range(11):
        one = {k: np.repeat(rows[k][t:t + 1], 2, 0) for k in "qkvba"}
        o = delta_ops.gated_delta_step(Ctx(
            {"allow_neg_eigval": True}, Q=one["q"], K=one["k"], V=one["v"],
            Beta=one["b"], A=one["a"], DtBias=rows["dt_bias"],
            ALog=rows["a_log"], State=st, Gate=np.array([1.0, 0.0]),
            Pos=np.array([5 + t, 3])))
        st = o["StateOut"]
        ys.append(np.asarray(o["Y"])[0])
    assert np.abs(np.stack(ys) - np.asarray(out["Y"])[:11]).max() < OP_TOL
    assert np.abs(np.asarray(st)[0]
                  - np.asarray(out["StateOut"])[0]).max() < OP_TOL
    assert (np.asarray(st)[1] == state[1]).all()


def test_a_step_of_every_lane_equals_the_recurrence_and_gates():
    import jax.numpy as jnp
    from paddle_tpu.ops import delta_ops

    rng = np.random.default_rng(4)
    rows = {**_rows(rng, 5), **_leaves(rng)}
    state = rng.normal(size=(5, 3, 8, 12)).astype(np.float32)
    gate = np.array([1, 0, 1, 1, 0], np.float32)
    pos = np.array([3, 5, 0, 9, 0])
    out = delta_ops.gated_delta_step(Ctx(
        {"allow_neg_eigval": True}, Q=rows["q"], K=rows["k"], V=rows["v"],
        Beta=rows["b"], A=rows["a"], DtBias=rows["dt_bias"],
        ALog=rows["a_log"], State=jnp.asarray(state), Gate=gate, Pos=pos))
    new = np.asarray(out["StateOut"])
    for r in range(5):
        if not gate[r]:
            assert (new[r] == state[r]).all()
            continue
        s0 = state[r] if pos[r] else np.zeros_like(state[r])
        y, s = _recurrence(*(rows[k][r:r + 1] for k in "qkvba"),
                           rows["dt_bias"], rows["a_log"], s0)
        assert np.abs(np.asarray(out["Y"])[r] - y[0]).max() < OP_TOL
        assert np.abs(new[r] - s).max() < OP_TOL


def test_beta_past_one_is_what_the_rule_computes():
    """beta = 2 sigmoid(b) lies in (0, 2): with beta logits of
    deviation 3 most of the betas lie past 1, and the op follows the
    recurrence with them; a beta clipped to (0, 1) (not doubled)
    misses it by far more than the tolerance."""
    rng = np.random.default_rng(8)
    rows = {**_rows(rng, 16, beta_gain=3.0), **_leaves(rng)}
    assert (2 / (1 + np.exp(-rows["b"])) > 1).mean() > 0.4
    state = np.zeros((2, 3, 8, 12), np.float32)
    out = np.asarray(_chunk(rows, state, 16, 0, 4, lane=0)["Y"])
    args = [rows[k] for k in "qkvba"] + [rows["dt_bias"], rows["a_log"],
                                         state[0]]
    y, _ = _recurrence(*args)
    clipped, _ = _recurrence(*args, beta_scale=1.0)
    assert np.abs(out - y).max() < OP_TOL
    assert np.abs(out - clipped).max() > 1000 * OP_TOL


@pytest.mark.parametrize("before", [False, True])
def test_the_gated_norm_in_both_orders(before):
    """gated_group_rms_norm: Mamba-2's order (gate, then a norm a group
    with a weight of the whole width) and Gated DeltaNet's
    (`norm_before_gate`: a norm a group with one weight every group
    shares, then the gate)."""
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops

    rng = np.random.default_rng(int(before))
    groups, width = 3, 4
    x = rng.normal(size=(5, groups * width)).astype(np.float32)
    z = rng.normal(size=(5, groups * width)).astype(np.float32)
    w = rng.normal(size=width if before else groups * width) \
        .astype(np.float32)
    attrs = {"groups": groups, "epsilon": 1e-6}
    if before:
        attrs["norm_before_gate"] = True
    out = np.asarray(ssm_ops.gated_group_rms_norm(Ctx(
        attrs, X=jnp.asarray(x), Z=jnp.asarray(z),
        Scale=jnp.asarray(w)))["Out"])
    gate = z / (1 + np.exp(-z.astype(np.float64)))

    def norm(u):
        u = u.reshape(5, groups, width)
        return (u / np.sqrt((u * u).mean(-1, keepdims=True) + 1e-6)) \
            .reshape(5, -1)

    want = norm(x) * np.tile(w, groups) * gate if before \
        else norm(x * gate) * w
    assert np.abs(out - want).max() < 1e-5
    other = norm(x * gate) * np.tile(w, groups) if before \
        else norm(x) * w * gate
    assert np.abs(out - other).max() > 0.1


def test_rotary_positions_at_each_rows_position():
    """rope_at turns each row by its own position, head by head, as
    the rotary table of positions 0..T-1 turns row t."""
    import jax.numpy as jnp
    from paddle_tpu.ops import lm_ops
    from paddle_tpu.ops.lm_ops import rotary_tables

    rng = np.random.default_rng(2)
    heads, dim = 3, 8
    x = rng.normal(size=(4, heads * dim)).astype(np.float32)
    pos = np.array([0, 5, 17, 3])
    out = np.asarray(lm_ops.rope_at(Ctx(
        {"n_heads": heads, "theta": 500000.0}, X=jnp.asarray(x),
        Pos=pos))["Out"]).reshape(4, heads, dim)
    cos, sin = (np.asarray(t) for t in rotary_tables(18, dim, 500000.0))
    xh = x.reshape(4, heads, dim)
    rot = np.concatenate([-xh[..., dim // 2:], xh[..., :dim // 2]], -1)
    want = xh * cos[pos][:, None] + rot * sin[pos][:, None]
    assert np.abs(out - want).max() < 1e-5
    assert np.abs(out[0] - xh[0]).max() == 0


def test_the_convolution_without_a_bias():
    """causal_conv_tail with no Bias input is the convolution with a
    zero bias."""
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops

    rng = np.random.default_rng(1)
    x = rng.normal(size=(6, 5)).astype(np.float32)
    w = rng.normal(size=(5, 4)).astype(np.float32)
    tail = rng.normal(size=(2, 3, 5)).astype(np.float32)
    where = dict(Lane=np.array([1]), Len=np.array([6]), Pos=np.array([4]))
    none = ssm_ops.causal_conv_tail(Ctx(X=jnp.asarray(x),
                                        Tail=jnp.asarray(tail), Filter=w,
                                        **where))
    zero = ssm_ops.causal_conv_tail(Ctx(
        X=jnp.asarray(x), Tail=jnp.asarray(tail), Filter=w,
        Bias=np.zeros(5, np.float32), **where))
    for slot in ("Out", "TailOut"):
        assert (np.asarray(none[slot]) == np.asarray(zero[slot])).all()


def test_the_nemotron_bundle_keeps_its_bias_and_its_order():
    """The Nemotron stack shares causal_conv_tail and
    gated_group_rms_norm: its programs still feed every convolution a
    bias and norm after the gate, as before this model's forms."""
    from test_nemotron_h import build as nemotron_build
    from test_nemotron_h import sizes as nemotron_sizes

    srv, _scope = nemotron_build(nemotron_sizes())
    try:
        ops = [op for prog in srv.bundle.programs()
               for block in prog.blocks for op in block.ops]
    finally:
        srv.close()
    convs = [op for op in ops if op.type == "causal_conv_tail"]
    norms = [op for op in ops if op.type == "gated_group_rms_norm"]
    assert convs and norms
    assert all(op.inputs.get("Bias") for op in convs)
    assert not any(op.attrs.get("norm_before_gate") for op in norms)


def test_the_layer_pattern_is_checked():
    from paddle_tpu.models import olmo_hybrid as O

    from benchmark.chip.drivers.olmoh_serve import NOT_THE_BUILDERS

    c = sizes()
    model = {k: v for k, v in R.model_cfg(c).items()
             if k not in NOT_THE_BUILDERS}
    with pytest.raises(ValueError, match="layers_pattern"):
        O.build_olmo_hybrid_serve_bundle(layers_pattern="LMF", **model)
    with pytest.raises(ValueError, match="divide"):
        O.build_olmo_hybrid_serve_bundle(
            layers_pattern="LF", **{**model, "n_kv_heads": 3})


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_a_tick_reads_each_lane_over_its_live_pages(hq, hkv, monkeypatch):
    """paged_decode_attention where every lane's whole table would be
    more than WHOLE_TABLES_BYTES: one lane after another, each over the
    fewest eighths of its table that hold its positions, against
    attention by heads over the lane's gathered positions (float64);
    the same as the route that gathers every lane's whole table."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_ops, pallas

    lanes, pages, bs, dh = 5, 16, 8, 16
    rng = np.random.default_rng(hq + hkv)
    n_blocks = lanes * pages + 3
    tab = rng.permutation(n_blocks)[:lanes * pages].reshape(lanes, pages)
    pk, pv = (jnp.asarray(rng.normal(size=(n_blocks * bs, hkv * dh)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(lanes, 1, hq * dh)), jnp.float32)
    pos = np.array([0, 7, 17, 64, 127])
    attrs = {"block_size": bs, "n_heads": hq, "scale": dh ** -0.5}
    if hkv != hq:
        attrs["n_kv_heads"] = hkv
    ins = dict(Q=q, PoolK=pk, PoolV=pv, Table=jnp.asarray(tab), Pos=pos)
    whole = np.asarray(paged_ops.paged_decode_attention(Ctx(attrs, **ins)))
    monkeypatch.setattr(paged_ops, "WHOLE_TABLES_BYTES", 1 << 10)
    with pallas.record_routes() as routes:
        out = np.asarray(paged_ops.paged_decode_attention(
            Ctx(attrs, **ins)))
    # on the CPU no kernel takes it: the route is by_lane's
    assert routes == [("paged_decode_attention", q.shape, False)]
    assert (np.asarray(paged_ops.paged_attention_by_lane(
        q, pk, pv, jnp.asarray(tab), pos, block_size=bs, n_heads=hq,
        n_kv_heads=hkv, scale=dh ** -0.5)) == out).all()
    assert np.abs(out - whole).max() < 1e-5
    cells = (tab[:, :, None] * bs + np.arange(bs)).reshape(lanes, -1)
    for r in range(lanes):
        k = np.asarray(pk, np.float64)[cells[r]].reshape(-1, hkv, dh)
        v = np.asarray(pv, np.float64)[cells[r]].reshape(-1, hkv, dh)
        qr = np.asarray(q[r, 0], np.float64).reshape(hq, dh)
        for h in range(hq):
            g = h // (hq // hkv)
            s = k[:pos[r] + 1, g] @ qr[h] * dh ** -0.5
            w = np.exp(s - s.max())
            want = (w / w.sum()) @ v[:pos[r] + 1, g]
            assert np.abs(out[r, 0, h * dh:(h + 1) * dh] - want).max() \
                < 1e-5


# the ticks of the serve cells as paged_decode_attention sees them: Q,
# the pools, the table, block size, heads, key-value heads, what it reads
TICKS = {
    "olmoh": ((33, 1, 3840), (3584 * 64, 3840), "bfloat16", (33, 144),
              64, 30, 30, "cells"),
    "olmoh_grouped_heads": ((33, 1, 3840), (3584 * 64, 1920), "bfloat16",
                            (33, 144), 64, 30, 15, "cells"),
    "olmoh_several_queries": ((33, 4, 3840), (3584 * 64, 3840),
                              "bfloat16", (33, 144), 64, 30, 30, "cells"),
    "transformer_big_serve": ((33, 1, 1024), (1280 * 16, 1024), "float32",
                              (33, 16), 16, 16, 16, "cells"),
    "transformer_big_serve_prompts": ((33, 1, 1024), (129 * 256, 1024),
                                      "float32", (33, 1), 256, 16, 16,
                                      "prompt_table"),
    "nemotron": ((129, 1, 4096), (5120 * 64, 256), "bfloat16", (129, 40),
                 64, 32, 2, "cells"),
}
# the route each takes on the CPU and on a TPU (interpret mode stands in
# for it): the streamed kernel, the float32 kernel, one lane at a time,
# or one gather of every lane's table
ROUTES = {
    "olmoh": ("by_lane", "streamed"),
    "olmoh_grouped_heads": ("by_lane", "by_lane"),
    "olmoh_several_queries": ("by_lane", "by_lane"),
    "transformer_big_serve": ("gather", "kernel"),
    "transformer_big_serve_prompts": ("gather", "kernel"),
    "nemotron": ("gather", "gather"),
}


@pytest.mark.parametrize("interpret", [False, True], ids=["cpu", "tpu"])
@pytest.mark.parametrize("tick", list(TICKS))
def test_a_tick_takes_its_route_by_what_the_op_sees(tick, interpret):
    """The route of each serve cell's tick at its own shapes, traced and
    not run: past WHOLE_TABLES_BYTES one query a lane over bfloat16
    pools with a key-value head a query head streams on the TPU, and
    reads one lane at a time off it, for grouped heads and for several
    queries; the ticks under the threshold keep their routes. The
    routing record says whether a kernel was taken."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_ops, pallas
    from paddle_tpu.ops.pallas import attention as base
    from test_paged_decode import _eqns

    qs, ps, dtype, ts, bs, h, kv, reads = TICKS[tick]
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        (qs, dtype), (ps, dtype), (ps, dtype), (ts, jnp.int32),
        (qs[:1], jnp.int32))]
    attrs = {"block_size": bs, "n_heads": h, "n_kv_heads": kv,
             "scale": 0.125, "reads": reads}

    def op(q, pk, pv, tab, pos):
        return paged_ops.paged_decode_attention(Ctx(
            attrs, Q=q, PoolK=pk, PoolV=pv, Table=tab, Pos=pos))
    base.force_interpret(interpret)
    try:
        with pallas.record_routes() as routes:
            closed = jax.make_jaxpr(op)(*args)
    finally:
        base.force_interpret(False)
    eqns = list(_eqns(closed.jaxpr))
    kernels = {e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"}
    if kernels:
        route = {"paged_decode_attention_streamed": "streamed",
                 "paged_decode_attention": "kernel"}[kernels.pop()]
    else:
        route = "by_lane" if any(e.primitive.name == "scan"
                                 for e in eqns) else "gather"
    assert route == ROUTES[tick][interpret]
    assert routes == [(paged_ops.ROUTE_LABELS[reads], qs,
                       route in ("streamed", "kernel"))]


# the streamed kernel in interpret mode at a width it takes (4 heads of
# 32), blocks of 16 rows and chunks of two blocks, each lane a table of
# 8 blocks from a permutation: lane -> its position
STREAM = dict(bs=16, pages=8, heads=4, dh=32)
STREAM_LANES = {
    "position_0": 0,
    "last_row_of_a_block": 15,
    "first_row_of_a_block": 16,
    "last_row_of_a_chunk": 31,
    "first_row_of_the_next_chunk": 32,
    "the_tables_last_position": 127,
    "idle_on_block_0": 0,           # a cleared table row
}


@pytest.fixture(scope="module")
def streamed():
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_ops
    from paddle_tpu.ops.pallas import attention as base
    from paddle_tpu.ops.pallas import paged_attention as PA

    bs, pages, h, dh = (STREAM[k] for k in ("bs", "pages", "heads", "dh"))
    names = list(STREAM_LANES)
    rng = np.random.default_rng(17)
    n_blocks = len(names) * pages + 3
    tab = rng.permutation(n_blocks)[:len(names) * pages].reshape(
        len(names), pages).astype(np.int32)
    tab[names.index("idle_on_block_0")] = 0
    pos = np.array(list(STREAM_LANES.values()), np.int32)
    pk, pv = (jnp.asarray(rng.normal(size=(n_blocks * bs, h * dh)),
                          jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(len(names), 1, h * dh)), jnp.bfloat16)
    kw = dict(block_size=bs, n_heads=h, scale=dh ** -0.5)
    budget = PA._STREAM_BUFFERS
    PA._STREAM_BUFFERS = 4 * 2 * bs * h * dh * 2
    base.force_interpret(True)
    try:
        chunk_pages = PA.stream_chunk_pages(bs, h * dh, 2, pages)
        got = PA.paged_decode_attention_streamed(
            q, pk, pv, jnp.asarray(tab), jnp.asarray(pos), **kw)
    finally:
        base.force_interpret(False)
        PA._STREAM_BUFFERS = budget
    by_lane = paged_ops.paged_attention_by_lane(
        q, pk, pv, jnp.asarray(tab), jnp.asarray(pos), n_kv_heads=h, **kw)
    cells = (tab[:, :, None] * bs + np.arange(bs)).reshape(len(names), -1)
    k64, v64 = (np.asarray(p, np.float64) for p in (pk, pv))
    want = []
    for r, last in enumerate(pos):
        k = k64[cells[r, :last + 1]].reshape(-1, h, dh)
        v = v64[cells[r, :last + 1]].reshape(-1, h, dh)
        qr = np.asarray(q[r, 0], np.float64).reshape(h, dh)
        s = np.einsum("thd,hd->ht", k, qr) * dh ** -0.5
        w = np.exp(s - s.max(1, keepdims=True))
        w /= w.sum(1, keepdims=True)
        want.append(np.einsum("ht,thd->hd", w, v).reshape(-1))
    return {"chunk_pages": chunk_pages, "dtype": got.dtype,
            "got": np.asarray(got, np.float64)[:, 0],
            "by_lane": np.asarray(by_lane, np.float64)[:, 0],
            "want": np.array(want)}


@pytest.mark.parametrize("lane", list(STREAM_LANES))
def test_the_streamed_kernel_reads_a_lane_as_by_lane_does(streamed, lane):
    """Each lane's context rows from the kernel, its live blocks copied
    a chunk at a time, against the route it replaces and a float64
    oracle over the lane's positions: bfloat16 out, as q is, within a
    unit of bfloat16's last place at the lane's largest number of both
    (each rounds its output, and the kernel rounds the softmax's
    weights before they are normalised, by_lane after: by_lane itself
    reads up to 0.6 of that unit against the oracle)."""
    assert streamed["chunk_pages"] == 2 and streamed["dtype"] == "bfloat16"
    i = list(STREAM_LANES).index(lane)
    got, want = streamed["got"][i], streamed["want"][i]
    ulp = 2.0 ** (np.floor(np.log2(np.abs(want).max())) - 7)
    assert np.abs(got - streamed["by_lane"][i]).max() <= ulp
    assert np.abs(got - want).max() <= ulp


def test_the_streamed_chunk_is_whole_blocks_in_a_vmem_budget():
    """A chunk is as many blocks as four buffers of them fit in the
    kernel's budget: 256 rows at the Olmo cell's width of 3,840
    bfloat16 numbers, one block at least, the table at most."""
    from paddle_tpu.ops.pallas import paged_attention as PA

    assert PA.stream_chunk_pages(64, 3840, 2, 144) * 64 == 256
    assert PA.stream_chunk_pages(64, 1 << 16, 2, 144) == 1
    assert PA.stream_chunk_pages(16, 128, 2, 8) == 8


def test_an_olmo_tick_past_the_threshold_serves_alike_on_both_routes(
        monkeypatch):
    """A bfloat16 Olmo-Hybrid bundle at a width the kernel takes (four
    heads of 32), its tables made to count as too large to gather:
    served through the streamed kernel (interpret mode) and through
    by_lane (the CPU), the tokens are the same, and the serve programs
    record the route each took."""
    from paddle_tpu.ops import paged_ops
    from paddle_tpu.ops.pallas import attention as base

    monkeypatch.setattr(paged_ops, "WHOLE_TABLES_BYTES", 1 << 10)
    c = sizes(weight_dtype="bfloat16", n_heads=4, n_kv_heads=4,
              head_dim=32)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(3, c["vocab"], n) for n in (5, 20, 37)]
    rows, routes = [], []
    for interpret in (False, True):
        base.force_interpret(interpret)
        try:
            srv, _ = build(c, block_size=16, n_blocks=32)
            try:
                replies = [srv.submit(p, max_new_tokens=8)
                           for p in prompts]
                rows.append([np.asarray(r.result(timeout=600))
                             for r in replies])
                routes.append(srv.stats()["self_attention_routes"])
            finally:
                srv.close()
        finally:
            base.force_interpret(False)
    for a, b in zip(*rows):
        assert (a == b).all()
    assert {tuple(v) for v in routes[0].values()} == {("reference",)}
    assert {tuple(v) for v in routes[1].values()} == {("kernel",)}
