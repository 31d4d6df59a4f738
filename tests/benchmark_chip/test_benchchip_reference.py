"""The plain reference against the program at a tiny size on the CPU:
the trainer's losses, first gradient and update in float32, and the
logits of the program's own forward pass on the same weights."""
import numpy as np
import pytest

from benchmark.chip import compare, controls, program_map, traffic
from benchmark.chip.drivers import train
from benchmark.chip.reference import transformer2017 as R


def _sizes(name, **over):
    return {**controls._sizes(name, rehearse=True), **over}


def test_reference_follows_the_trainer_in_float32():
    c = _sizes("transformer-base-train", amp=False)
    spec = {**traffic.load("fresh_batches"), "pool_batches": 3}
    feeds = traffic.train_batches(11, spec, c, train.START_ID)
    trainer = train.Trainer(c, seed=11)
    got = trainer.first_steps(feeds)
    trainer.free()
    want = train.reference_readings(c, 11, feeds)
    rows = {r["name"]: r["value"] for r in train.compare_readings(
        got, want, c["limits"]).rows}
    assert max(rows[f"loss_gap_step{i}"] for i in (1, 2, 3)) < 1e-5
    assert rows["grad_norm_gap"] < 1e-3
    assert rows["update_norm_gap"] < 1e-2
    # every leaf of the program is covered, and every leaf moved
    assert set(got["grad_norms"]) == set(want["grad_norms"])
    assert min(want["change_norms"].values()) > 0


def test_rows_of_a_batch_all_differ_and_seeds_repeat():
    c = _sizes("transformer-base-train")
    spec = {**traffic.load("fresh_batches"), "pool_batches": 2}
    a = traffic.train_batches(2 ** 31 + 5, spec, c, train.START_ID)
    b = traffic.train_batches(2 ** 31 + 5, spec, c, train.START_ID)
    other = traffic.train_batches(2 ** 31 + 6, spec, c, train.START_ID)
    for x, y in zip(a, b):
        for k in x:
            assert np.array_equal(x[k], y[k])
    assert not np.array_equal(a[0]["src_ids"], other[0]["src_ids"])
    src = a[0]["src_ids"]
    assert len({row.tobytes() for row in src}) == len(src)
    assert np.array_equal(a[0]["tgt_ids"][:, 1:], a[0]["label"][:, :-1])
    assert (a[0]["tgt_ids"][:, 0] == train.START_ID).all()


def test_every_seed_sends_the_same_repeats_in_other_words():
    """Which request repeats which decides the work of the repeated
    mix (hit, miss or replay), so the sequence of popularity ranks is
    the mix's own; a seed changes what the prompts say, and which
    prompt has which rank."""
    c = _sizes("transformer-big-serve", seq_len=256, vocab=32000)
    spec = traffic.load("repeat_zipf")
    a = traffic.ClosedLoop(7, spec, c)
    b = traffic.ClosedLoop(2 ** 31 + 7, spec, c)
    ranks_a = np.argsort(a.rank_to_pool)[a.order]
    ranks_b = np.argsort(b.rank_to_pool)[b.order]
    assert np.array_equal(ranks_a, ranks_b)
    assert not np.array_equal(a.rank_to_pool, b.rank_to_pool)
    assert not np.array_equal(a.pool, b.pool)
    # Zipf(1.0) over 512: the 128 a warm table holds carry four fifths
    # of every stratum of 128 draws
    for block in ranks_a[:1024].reshape(8, 128):
        assert 0.7 <= (block < 128).mean() <= 0.9
    assert [p.tobytes() for p in a.by_popularity(3)] == [
        a.pool[i].tobytes() for i in a.rank_to_pool[:3]]


def test_reference_logits_match_the_programs_forward():
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import layers, unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.models import transformer as T

    c = _sizes("transformer-big-serve")
    s, t = c["seq_len"], c["max_out_len"]
    prog, start = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(prog, start):
        src = layers.data("src_ids", shape=[s], dtype="int64")
        tgt = layers.data("tgt_ids", shape=[t], dtype="int64")
        label = layers.data("label", shape=[t], dtype="int64")
        _, logits = T.transformer(
            src, tgt, label, src_vocab=c["vocab"], tgt_vocab=c["vocab"],
            max_len=256, d_model=c["d_model"], n_heads=c["n_heads"],
            n_layers=c["n_layers"], d_inner=c["d_inner"],
            dropout_rate=0.0, is_test=True)
    scope, exe = Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(start, scope=scope)
    cfg = {k: c[k] for k in ("d_model", "d_inner", "n_heads",
                             "n_layers", "vocab")}
    params = R.make_params(7, cfg)
    for name, value in program_map.to_program(
            params, c["n_layers"]).items():
        scope._set(name, value)
    rng = np.random.default_rng(0)
    src_ids = rng.integers(3, c["vocab"], (2, s))
    tgt_ids = rng.integers(3, c["vocab"], (2, t))
    got, = exe.run(prog, feed={"src_ids": src_ids, "tgt_ids": tgt_ids,
                               "label": np.zeros_like(tgt_ids)},
                   fetch_list=[logits], scope=scope)
    want = np.asarray(R.forward_logits(
        params, jnp.asarray(src_ids), jnp.asarray(tgt_ids), cfg))
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()


def test_lower_precisions_depart_in_order():
    import jax.numpy as jnp

    c = _sizes("transformer-big-serve")
    cfg = {k: c[k] for k in ("d_model", "d_inner", "n_heads",
                             "n_layers", "vocab")}
    params = R.make_params(3, cfg)
    rng = np.random.default_rng(1)
    src = jnp.asarray(rng.integers(3, c["vocab"], (2, c["seq_len"])))
    tgt = jnp.asarray(rng.integers(3, c["vocab"], (2, c["max_out_len"])))
    hi = np.asarray(R.forward_logits(params, src, tgt, cfg, "highest"))
    err = {p: float(np.abs(np.asarray(R.forward_logits(
        params, src, tgt, cfg, p)) - hi).max())
        for p in ("bf16_ops", "bf16", "fp8")}
    assert 0 < err["bf16_ops"] <= err["bf16"] < err["fp8"]
    with pytest.raises(ValueError, match="unknown precision"):
        R.forward_logits(params, src, tgt, cfg, "fp4")


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-9}
    # c doubles, but against the median leaf (1.0) that is nothing
    gap, leaf = compare.worst_leaf_gap(prog, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)
    assert compare.still_leaves({"a": 1.0, "b": 2.0, "c": 1e-9}) == {"c"}
    assert compare.worst_leaf_gap({"a": float("nan"), "b": 2.0},
                                  {"a": 1.0, "b": 2.0})[0] == float("inf")
