"""The controls, at a size a test run can hold: the reference put in
the program's place and computed in the precision below the one the
configuration states has to come out as not correct, and so has each
planted fault of the batch. (PERF.md gives the same readings on the
chip at the cells' own sizes.)"""
import numpy as np
import pytest

from benchmark.chip import controls, traffic
from benchmark.chip.drivers import serve
from benchmark.chip.reference import transformer2017 as R


@pytest.fixture(scope="module")
def train_readings():
    c = controls._sizes("transformer-base-train", rehearse=True)
    return [controls.train_controls(c, seed, traffic.load("fresh_batches"))
            for seed in (2 ** 31 + 1, 12, 13)]


@pytest.mark.parametrize("what", ["control_fp8", "fault_half_batch"])
def test_training_control_and_fault_come_out_not_correct(
        train_readings, what):
    """Through `compare_readings` and `Compared.correct`, as a run's
    own readings go."""
    for reading in train_readings:
        assert reading[what]["correct"] is False, reading[what]


def _greedy_sample(c, seed, n_requests):
    """Requests as a sound server answers them: the reference's own
    greedy tokens, position by position."""
    import jax
    import jax.numpy as jnp

    cfg = serve._model_cfg(c)
    params = R.make_params(seed, cfg)
    fwd = jax.jit(lambda p, s, t: R.forward_logits(p, s, t, cfg)[0])
    rng = np.random.default_rng(seed)
    sample = []
    for _ in range(n_requests):
        prompt = rng.integers(3, c["vocab"], c["seq_len"])
        row = np.full(c["max_out_len"], -1, np.int64)
        row[0] = serve.START_ID
        for pos in range(1, c["max_out_len"]):
            tgt = np.where(row >= 0, row, 0)[None, :-1]
            logits = fwd(params, jnp.asarray(prompt)[None],
                         jnp.asarray(tgt))
            row[pos] = int(np.asarray(logits)[pos - 1].argmax())
            if row[pos] == serve.END_ID:
                break
        sample.append((prompt, row, list(serve.served_rows(row)[1])))
    return sample


@pytest.fixture(scope="module")
def serve_samples():
    """Replies of 63 tokens: the rehearsal's 15 hold too few positions
    for a lower precision to meet a near-tie on every seed."""
    c = {**controls._sizes("transformer-big-serve", rehearse=True),
         "max_out_len": 64}
    return c, {seed: _greedy_sample(c, seed, 4) for seed in SEEDS}


SEEDS = (22, 9, 2 ** 31 + 2)


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_controls_come_out_not_correct(serve_samples, seed):
    """The sound sample is correct; the tokens bfloat16 throughout (the
    control: the precision below the configuration's float32) and fp8
    put first at the same positions go through the same `check_sample`,
    `hold_sample` and `Compared.correct` and come out not correct."""
    c, samples = serve_samples
    got = controls.serve_controls(c, seed, samples[seed])
    assert got["program"]["correct"] is True, got["program"]
    assert got["program"]["served_logit_gap"] == 0.0
    assert got["control_bf16"]["correct"] is False, got["control_bf16"]
    assert got["control_bf16"]["served_wide_gap_share"] \
        > c["limits"]["served_wide_gap_share"]
    assert got["control_fp8"]["correct"] is False, got["control_fp8"]
    assert got["control_fp8"]["served_logit_gap"] \
        > c["limits"]["served_logit_gap"]


@pytest.mark.parametrize("seed", SEEDS)
def test_default_precision_products_stay_under_the_widest_gap(
        serve_samples, seed):
    """What a default-precision float32 product on the TPU rounds away
    (bfloat16 operands, everything else float32) stays under the limit
    of the widest gap: that number is for a wrong token, the share of
    wide gaps for a precision."""
    c, samples = serve_samples
    read = serve.check_sample(c, seed, samples[seed], control="bf16_ops")
    assert read["gaps"].shape == (4 * 63,) and read["stream_ok"]
    assert read["gaps"].max() < c["limits"]["served_logit_gap"]


def test_altered_answer_comes_out_not_correct(serve_samples):
    c, samples = serve_samples
    for seed, sample in samples.items():
        prompt, row, streamed = sample[0]
        wrong = row.copy()
        wrong[2] = (wrong[2] + 1) % c["vocab"]
        held = serve.hold_sample(c, serve.check_sample(
            c, seed, [(prompt, wrong, streamed)]))
        rows = {r["name"]: r for r in held.rows}
        assert held.correct is False
        assert rows["served_logit_gap"]["value"] \
            > rows["served_logit_gap"]["limit"]
        assert rows["stream_equals_row"]["value"] == 1.0


def test_a_random_model_says_many_tokens_and_never_the_end(
        serve_samples):
    """Why the serve configuration scales `wo` and silences the end
    token (PERF.md section 2): at Glorot's own scale the attention
    sublayers add the same vector at every position, most seeds decode
    every prompt to one token repeated, and where that token's margin
    is wide a lower precision serves the same answers and a comparison
    of answers reads 0."""
    c, samples = serve_samples
    for sample in samples.values():
        said = sorted(len(set(row[1:].tolist())) for _, row, _ in sample)
        assert said[1] >= 12, said     # all but one reply at the least
        assert all(serve.END_ID not in row for _, row, _ in sample)
    plain = {k: v for k, v in c.items()
             if k not in ("init_gain", "silent_ids")}
    seed = 2 ** 31 + 2
    sample = _greedy_sample({**plain, "max_out_len": 16}, seed, 4)
    assert {len(set(row[1:].tolist())) for _, row, _ in sample} == {1}
    assert serve.check_sample(plain, seed, sample,
                              control="fp8")["gaps"].max() == 0.0


def test_sample_files_round_trip(tmp_path):
    c = controls._sizes("transformer-big-serve", rehearse=True)
    sample = _greedy_sample(c, 5, 2)
    path = str(tmp_path / "x.sample.npz")
    np.savez(path, prompts=np.stack([p for p, _, _ in sample]),
             rows=np.stack([r for _, r, _ in sample]), seed=np.int64(5))
    seed, loaded = controls.load_sample(path)
    assert seed == 5 and len(loaded) == 2
    assert np.array_equal(loaded[0][1], sample[0][1])
    assert loaded[0][2] == sample[0][2]
