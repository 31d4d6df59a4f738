"""Nemotron-3-Super (nemotron_h) on the serve engine at rehearsal sizes
on the CPU, float32 weights from the seed: the decoder-only bundle with
per-lane state-space state served by PagedContinuousGenerationServer
against the plain reference (benchmark/chip/reference/nemotron_h.py)
through prefill in chunks, the lanes' state, the paged key-value cache
and decoding; lanes reused and lanes idle; the chunked scan against the
sequential recurrence; the expert ranks' shares; the experts that are
not gated; grouped queries over paged keys and values; no prefix reuse
beside lane state."""
import json
import os

import numpy as np
import pytest

from benchmark.chip.reference import nemotron_h as R

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 7
# float32 everywhere, but the program sums in another order than the
# reference (the chunked form of the scan, grouped products, attention
# a page at a time): a few units of float32's last place on logits of
# magnitude ten
LOGIT_TOL = 5e-5


def sizes(**over):
    with open(os.path.join(HERE, "..", "benchmark", "chip", "configs",
                           "nemotron-3-super-serve-ep4.json")) as f:
        config = json.load(f)
    return {**config["sizes"], **config["rehearsal"],
            "weight_dtype": "float32", **over}


def build(c, seed=SEED, **over):
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.models import nemotron_h as N

    from benchmark.chip.drivers.nemotron_serve import NOT_THE_BUILDERS

    model = {k: v for k, v in R.model_cfg(c).items()
             if k not in NOT_THE_BUILDERS}
    geometry = dict(n_slots=4, block_size=8, n_blocks=64, context=128,
                    max_new_tokens=16, chunk_sizes=(8, 32), max_chunks=4,
                    scan_block=c["scan_block"])
    geometry.update(over)
    server = {k: geometry.pop(k) for k in ("steps_per_tick", "drain_steps")
              if k in geometry}
    with unique_name.guard():
        bundle = N.build_nemotron_h_serve_bundle(
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
        **{"steps_per_tick": 4, "drain_steps": 4, **server})
    return srv, scope


def served(row):
    row = np.asarray(row)
    return row[1:1 + int((row[1:] >= 0).sum())]


def reference_of(c, prompt, row):
    toks = served(row)
    want = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
    return R.forward(c, SEED, np.concatenate([prompt, toks]), want)


# prompts whose rest (all but the last token) is cut into chunks of 32
# and 8 with true lengths 4, 5, 3 and 0 in the last one: boundaries that
# are and are not multiples of the scan's block of 4
PROMPTS = (45, 5, 17, 33, 1, 70, 12)
NEWS = (6, 10, 16, 4, 8, 5, 7)


@pytest.fixture(scope="module")
def session():
    """One server of 4 lanes and 7 requests sent at once: lanes are
    admitted in different cycles (chunks of several prompts share a
    dispatch's budget) and three requests take a lane that another has
    left."""
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
    """(a) Prefill in chunks, the lane's state and the paged cache,
    then decoding: every tick's logits against the reference's full
    forward pass over prompt and served tokens."""
    c, reply = session["c"], session["replies"][i]
    row = reply.result()
    ref = reference_of(c, session["prompts"][i], row)
    assert len(served(row)) == NEWS[i]
    got = reply.probe["logits"]
    assert np.abs(got - ref["logits"]).max() < LOGIT_TOL
    assert list(served(row)) == list(ref["logits"].argmax(-1))
    for j, li in enumerate(sorted(reply.probe["chosen"])):
        assert (np.sort(reply.probe["chosen"][li], -1)
                == ref["chosen"][j]).all()


def test_the_bundle_says_what_a_lane_carries(session):
    c, bundle = session["c"], session["srv"].bundle
    state = bundle.lane_state
    m_layers = [i for i, k in enumerate(c["layers"]) if k == "M"]
    assert state["names"] == tuple(
        f"@nem/{kind}_{i}" for i in m_layers
        for kind in ("ssm_state", "conv_tail"))
    scan = c["ssm_heads"] * c["ssm_head_dim"] * c["ssm_state"] * 4
    width = c["ssm_heads"] * c["ssm_head_dim"] \
        + 2 * c["ssm_groups"] * c["ssm_state"]
    tail = (c["conv_kernel"] - 1) * width * 4      # float32 weights
    assert state["bytes_per_lane"] == len(m_layers) * (scan + tail)
    st = session["stats"]
    assert st["state_lanes"] == 4
    assert st["state_bytes"] == 5 * state["bytes_per_lane"]
    assert st["state_resets"] == len(PROMPTS)


def test_a_reused_lane_starts_from_zero_and_an_idle_one_keeps_its_state(
        session):
    """(b) After the session every lane holds what its last request
    left. One more request takes the first lane: its logits are the
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


def test_a_bundle_with_lane_state_takes_no_prefix_hit(session):
    """(g) The same prompt twice, its whole length cacheable: nothing
    is found, nothing is left in the tree, and the admissions that took
    no hit are counted."""
    c, srv = session["c"], session["srv"]
    st0 = srv.pool_stats()
    prompt = np.random.default_rng(6).integers(3, c["vocab"], 40)
    rows = [np.asarray(srv.submit(prompt, max_new_tokens=4,
                                  cache_tokens=len(prompt))
                       .result(timeout=600)) for _ in range(2)]
    assert (rows[0] == rows[1]).all()
    st = srv.pool_stats()
    assert st["prefix_reuse_skipped"] - st0["prefix_reuse_skipped"] == 2
    assert st["cached_prompt_tokens"] == 0 and st["radix_nodes"] == 0
    assert st["radix_inserts"] == 0 and st["radix_admissions"] == 0
    assert st["blocks_in_use"] == 0
    assert st["prefill_tokens"] - st0["prefill_tokens"] == 2 * 39


def test_the_state_series_carry_what_pool_stats_counts(session):
    srv = session["srv"]
    st = srv.pool_stats()
    got = {name: value for name, _labels, value in srv._metrics_samples()
           if name.startswith("paddle_tpu_blockpool_")}
    for series, key in (("state_lanes", "state_lanes"),
                        ("state_bytes", "state_bytes"),
                        ("state_resets_total", "state_resets"),
                        ("prefix_reuse_skipped_total",
                         "prefix_reuse_skipped")):
        assert got[f"paddle_tpu_blockpool_{series}"] == st[key], series


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


def _recurrence(xbc, dt, dt_bias, a_log, d_skip, s0, heads, p, groups, n):
    """The sequential recurrence in numpy, float64."""
    d_inner = heads * p
    s = np.asarray(s0, np.float64).copy()
    a = -np.exp(np.asarray(a_log, np.float64))
    ys = []
    for t in range(len(xbc)):
        x = xbc[t, :d_inner].reshape(heads, p).astype(np.float64)
        bm = np.repeat(xbc[t, d_inner:d_inner + groups * n]
                       .reshape(groups, n), heads // groups, 0)
        cm = np.repeat(xbc[t, d_inner + groups * n:]
                       .reshape(groups, n), heads // groups, 0)
        step = np.log1p(np.exp(dt[t].astype(np.float64) + dt_bias))
        s = np.exp(step * a)[:, None, None] * s \
            + (step[:, None] * x)[:, :, None] * bm[:, None, :]
        ys.append((s * cm[:, None, :]).sum(-1) + d_skip[:, None] * x)
    return np.stack(ys).reshape(len(xbc), d_inner), s


@pytest.mark.parametrize("length,block", [(13, 4), (16, 4), (5, 16),
                                          (9, 3)])
def test_chunk_scan_from_a_state_with_padding_equals_the_recurrence(
        length, block):
    """(c) mamba2_chunk_scan on a chunk of 16 rows of which `length`
    are real, from a lane's non-zero state: the real rows' y and the
    state left behind are the sequential recurrence's over the real
    rows; the other lanes' state is untouched; from position 0 the
    stored state is not read."""
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops

    heads, p, groups, n, rows, t = 4, 8, 2, 16, 3, 16
    rng = np.random.default_rng(length)
    width = heads * p + 2 * groups * n
    xbc = rng.normal(size=(t, width)).astype(np.float32)
    dt = rng.normal(size=(t, heads)).astype(np.float32)
    dt_bias = rng.normal(size=heads).astype(np.float32) - 2.0
    a_log = np.log(rng.uniform(1, 16, heads)).astype(np.float32)
    d_skip = rng.normal(size=heads).astype(np.float32)
    state = rng.normal(size=(rows, heads, p, n)).astype(np.float32)

    def run(pos):
        return ssm_ops.mamba2_chunk_scan(Ctx(
            {"n_groups": groups, "block": block}, XBC=jnp.asarray(xbc),
            Dt=jnp.asarray(dt), DtBias=dt_bias, ALog=a_log, D=d_skip,
            State=jnp.asarray(state), Lane=np.array([1]),
            Len=np.array([length]), Pos=np.array([pos])))

    for pos, s0 in ((24, state[1]), (0, np.zeros_like(state[1]))):
        out = run(pos)
        y, s = _recurrence(xbc[:length], dt[:length], dt_bias, a_log,
                           d_skip, s0, heads, p, groups, n)
        assert np.abs(np.asarray(out["Y"])[:length] - y).max() < 2e-4
        new = np.asarray(out["StateOut"])
        assert np.abs(new[1] - s).max() < 2e-4
        assert (new[[0, 2]] == state[[0, 2]]).all()


def test_a_step_of_every_lane_equals_the_recurrence_and_gates():
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops

    heads, p, groups, n, rows = 4, 8, 2, 16, 5
    rng = np.random.default_rng(3)
    width = heads * p + 2 * groups * n
    xbc = rng.normal(size=(rows, width)).astype(np.float32)
    dt = rng.normal(size=(rows, heads)).astype(np.float32)
    dt_bias = rng.normal(size=heads).astype(np.float32)
    a_log = np.log(rng.uniform(1, 16, heads)).astype(np.float32)
    d_skip = np.ones(heads, np.float32)
    state = rng.normal(size=(rows, heads, p, n)).astype(np.float32)
    gate = np.array([1, 0, 1, 1, 0], np.float32)
    pos = np.array([3, 5, 0, 9, 0])
    out = ssm_ops.mamba2_step(Ctx(
        {"n_groups": groups}, XBC=jnp.asarray(xbc), Dt=jnp.asarray(dt),
        DtBias=dt_bias, ALog=a_log, D=d_skip, State=jnp.asarray(state),
        Gate=gate, Pos=pos))
    new = np.asarray(out["StateOut"])
    for r in range(rows):
        if not gate[r]:
            assert (new[r] == state[r]).all()
            continue
        s0 = state[r] if pos[r] else np.zeros_like(state[r])
        y, s = _recurrence(xbc[r:r + 1], dt[r:r + 1], dt_bias, a_log,
                           d_skip, s0, heads, p, groups, n)
        assert np.abs(np.asarray(out["Y"])[r] - y[0]).max() < 1e-4
        assert np.abs(new[r] - s).max() < 1e-4


def test_the_convolution_carries_its_tail_across_chunks_and_ticks():
    """A sequence cut into a chunk of 7 real rows (padded to 8), a
    chunk of 2 (shorter than the tail) and two ticks gives what the
    convolution of the whole sequence gives."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops

    width, taps, rows = 6, 4, 3
    rng = np.random.default_rng(2)
    seq = rng.normal(size=(11, width)).astype(np.float32)
    w = rng.normal(size=(width, taps)).astype(np.float32)
    b = rng.normal(size=width).astype(np.float32)
    padded = np.concatenate([np.zeros((taps - 1, width), np.float32), seq])
    want = np.asarray(jax.nn.silu(sum(
        padded[j:j + 11] * w[:, j] for j in range(taps)) + b))
    junk = rng.normal(size=(rows, taps - 1, width)).astype(np.float32)
    tail, got = jnp.asarray(junk), []
    for at, n, size in ((0, 7, 8), (7, 2, 8)):
        x = np.zeros((size, width), np.float32)
        x[:n] = seq[at:at + n]
        x[n:] = 99.0        # padding never enters the tail
        out = ssm_ops.causal_conv_tail(Ctx(
            X=jnp.asarray(x), Tail=tail, Filter=w, Bias=b,
            Lane=np.array([2]), Len=np.array([n]), Pos=np.array([at])))
        tail = out["TailOut"]
        got.append(np.asarray(out["Out"])[:n])
    assert (np.asarray(tail)[:2] == junk[:2]).all()
    for at in (9, 10):
        x = rng.normal(size=(rows, width)).astype(np.float32)
        x[2] = seq[at]
        out = ssm_ops.causal_conv_tail(Ctx(
            X=jnp.asarray(x), Tail=tail, Filter=w, Bias=b,
            Gate=np.array([0, 0, 1], np.float32),
            Pos=np.array([4, 0, at])))
        tail = out["TailOut"]
        got.append(np.asarray(out["Out"])[2:3])
        assert (np.asarray(tail)[:2] == junk[:2]).all()
    assert np.abs(np.concatenate(got) - want).max() < 1e-5


def test_the_ranks_shares_add_up_to_the_whole_layer():
    """(d) Over all ranks' experts_held, the routed parts (each through
    the whole up-projection) plus the shared expert counted once are
    the uncut layer; and the program's layer, told a rank's experts,
    computes that rank's part in the latent width."""
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe

    c = sizes()
    layer = c["layers"].index("E")
    x = np.random.default_rng(1).normal(size=(24, c["d_model"]))
    whole, shared, chosen = R.moe_layer_parts(
        c, SEED, x, layer, experts=(0, c["n_experts"]))
    held = c["experts_held"]
    total = 0
    for first in range(0, c["n_experts"], held):
        part, same, idx = R.moe_layer_parts(c, SEED, x, layer,
                                            experts=(first, held))
        assert (np.asarray(idx) == np.asarray(chosen)).all()
        assert np.allclose(same, shared)
        p = R.make_layer(SEED, {**R.model_cfg(c), "first_held": first},
                         layer)
        name = f"n{layer}_"
        u = R.rms_norm(jnp.asarray(x, jnp.float32), p[name + "norm.w"],
                       c["norm_eps"])
        out, _, load, pairs = moe.moe_dropless(
            u, p[name + "moe_gate.w"], p[name + "moe_bias"],
            p[name + "moe_w13"], p[name + "moe_w2"], first_held=first,
            top_k=c["top_k"], norm_topk=True,
            scaling=c["routed_scaling"], activation="relu2",
            expert_x=u @ p[name + "lat_down.w"])
        assert out.shape == (24, c["d_latent"])
        up = np.asarray(out @ p[name + "lat_up.w"])
        assert np.abs(up - np.asarray(part)).max() < 5e-5
        assert int(pairs[0]) == int(
            ((np.asarray(idx) >= first)
             & (np.asarray(idx) < first + held)).sum())
        total = total + part
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < 5e-5
    assert np.abs(np.asarray(whole)).max() > 0.1


@pytest.mark.parametrize("m,groups,rows", [
    (256, 16, 256), (512, 16, 512), (2048, 16, 512), (8192, 16, 512),
    (32768, 8, 512),
    (129 * 22, 128, 128), (512 * 22, 128, 128), (2048 * 22, 128, 512)])
def test_the_row_tile_follows_the_rows_a_group_can_have(m, groups, rows):
    """The grouped product picks its row tile from the shapes it is
    traced with: what GLM-5.2's tick and chunks and LFM2's step had
    (one tile of all rows up to 512, 512 beyond), and 128 where a
    held expert cannot get 128 rows (this stack's tick and its chunks
    of 128 and 512 positions)."""
    from paddle_tpu.ops.pallas import grouped_matmul as G

    assert G.row_tile(m, groups) == rows
    assert G._tiling(m, 1024, 2688, groups) == (rows, 1024, 1024)


@pytest.mark.parametrize("tokens,held", [(7, 3), (40, 3), (200, 3),
                                         (200, 6)])
def test_experts_that_are_not_gated_read_their_own_input(tokens, held):
    """(e) moe_dropless with activation relu2 and a separate expert
    input against a dense loop over the held experts: the router reads
    x, the experts read another, narrower input; the row buffer padded
    to whole tiles (600 pairs to 1,024 in tiles of 512 over 3 held
    experts, to 640 in tiles of 128 over 6) changes nothing."""
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe

    d, d_e, f, n_exp, first, k = 24, 12, 20, 8, 2, 3
    rng = np.random.default_rng(tokens)
    x = rng.normal(size=(tokens, d)).astype(np.float32)
    ex = rng.normal(size=(tokens, d_e)).astype(np.float32)
    wg = rng.normal(size=(d, n_exp)).astype(np.float32)
    bias = (rng.normal(size=n_exp) * 0.1).astype(np.float32)
    w1 = rng.normal(size=(held, d_e, f)).astype(np.float32) * d_e ** -0.5
    w2 = rng.normal(size=(held, f, d_e)).astype(np.float32) * f ** -0.5
    out, idx, load, pairs = moe.moe_dropless(
        jnp.asarray(x), wg, bias, w1, w2, first_held=first, top_k=k,
        scaling=2.0, activation="relu2", expert_x=jnp.asarray(ex))
    want_idx, weight = moe.route_dropless(jnp.asarray(x), wg, bias, k,
                                          True, 2.0)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    want = np.zeros((tokens, d_e))
    for j in range(held):
        g = np.where(np.asarray(idx) == first + j, np.asarray(weight),
                     0.0).sum(-1)
        want += g[:, None] * (np.square(np.maximum(ex @ w1[j], 0.0))
                              @ w2[j])
    assert out.shape == (tokens, d_e)
    assert np.abs(np.asarray(out) - want).max() < 1e-4
    assert int(pairs[0]) == int(load.sum()) == int(
        ((np.asarray(idx) >= first)
         & (np.asarray(idx) < first + held)).sum())


def _paged(rng, lanes, pages, bs, width, dtype):
    import jax.numpy as jnp

    n_blocks = lanes * pages + 3
    tab = rng.permutation(n_blocks)[:lanes * pages].reshape(lanes, pages)
    pools = [jnp.asarray(rng.normal(size=(n_blocks * bs, width)), dtype)
             for _ in range(2)]
    return tab.astype(np.int32), pools


def _dense_attention(q, k, v, seen, n_heads, n_kv):
    """q [n, H*Dh], k, v [t, Hkv*Dh], seen [n, t] -> [n, H*Dh], by
    heads, float64."""
    n, t = seen.shape
    dh = q.shape[-1] // n_heads
    q = np.asarray(q, np.float64).reshape(n, n_heads, dh)
    k = np.asarray(k, np.float64).reshape(t, n_kv, dh)
    v = np.asarray(v, np.float64).reshape(t, n_kv, dh)
    out = np.zeros((n, n_heads, dh))
    for h in range(n_heads):
        g = h // (n_heads // n_kv)
        s = q[:, h] @ k[:, g].T * dh ** -0.5
        s = np.where(seen, s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (w / w.sum(-1, keepdims=True)) @ v[:, g]
    return out.reshape(n, n_heads * dh)


def test_grouped_queries_over_paged_keys_and_values():
    """(f) paged_decode_attention with 2 key-value heads under 32 query
    heads, bfloat16 pools, against attention by heads over the lane's
    gathered positions; the route it took is recorded."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_ops
    from paddle_tpu.ops.pallas import record_routes

    lanes, pages, bs, hq, hkv, dh = 5, 4, 8, 32, 2, 16
    rng = np.random.default_rng(4)
    tab, (pk, pv) = _paged(rng, lanes, pages, bs, hkv * dh, jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(lanes, 1, hq * dh)), jnp.bfloat16)
    pos = np.array([0, 7, 8, 20, 31])
    with record_routes() as routes:
        out = paged_ops.paged_decode_attention(Ctx(
            {"block_size": bs, "n_heads": hq, "n_kv_heads": hkv,
             "scale": dh ** -0.5}, Q=q, PoolK=pk, PoolV=pv,
            Table=jnp.asarray(tab), Pos=pos))
    assert out.dtype == jnp.bfloat16 and out.shape == q.shape
    assert ("paged_decode_attention", q.shape, False) in routes
    cells = (tab[:, :, None] * bs + np.arange(bs)).reshape(lanes, -1)
    for r in range(lanes):
        want = _dense_attention(
            np.asarray(q[r], np.float32), np.asarray(pk, np.float32)[cells[r]],
            np.asarray(pv, np.float32)[cells[r]],
            np.arange(pages * bs)[None] <= pos[r], hq, hkv)
        # bfloat16 weights times bfloat16 values, float32 sums
        assert np.abs(np.asarray(out[r], np.float32) - want).max() < 0.03


@pytest.mark.parametrize("first,n", [(0, 12), (19, 9)])
def test_a_chunks_queries_see_the_prefix_and_the_chunk(first, n):
    """paged_prefill_attention: each row of a chunk that starts at
    `first` sees the lane's positions up to its own, whatever the
    pages past them hold."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_ops

    pages, bs, hq, hkv, dh = 6, 8, 4, 2, 16
    rng = np.random.default_rng(first)
    tab, (pk, pv) = _paged(rng, 1, pages, bs, hkv * dh, jnp.float32)
    q = jnp.asarray(rng.normal(size=(n, hq * dh)), jnp.float32)
    pos = first + np.arange(n)
    out = paged_ops.paged_prefill_attention(Ctx(
        {"block_size": bs, "n_heads": hq, "n_kv_heads": hkv,
         "scale": dh ** -0.5}, Q=q, PoolK=pk, PoolV=pv,
        Table=jnp.asarray(tab), Pos=pos))["Out"]
    cells = (tab[0][:, None] * bs + np.arange(bs)).reshape(-1)
    want = _dense_attention(q, np.asarray(pk)[cells], np.asarray(pv)[cells],
                            np.arange(pages * bs)[None] <= pos[:, None],
                            hq, hkv)
    assert np.abs(np.asarray(out) - want).max() < 1e-5


def test_the_layer_pattern_is_checked():
    from paddle_tpu.models import nemotron_h as N

    c = sizes()
    from benchmark.chip.drivers.nemotron_serve import NOT_THE_BUILDERS
    model = {k: v for k, v in R.model_cfg(c).items()
             if k not in NOT_THE_BUILDERS}
    with pytest.raises(ValueError, match="layers_pattern"):
        N.build_nemotron_h_serve_bundle(layers_pattern="MXE", **model)
    with pytest.raises(ValueError, match="divide"):
        N.build_nemotron_h_serve_bundle(
            layers_pattern="M", **{**model, "ssm_groups": 3})
