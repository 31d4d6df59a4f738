"""One dispatch path: every entry point of the executor (run,
run_steps with one dict and with a list, prepare, prepare(steps=K),
the data-parallel CompiledProgram) stages, gathers, calls and stores
through the same code (core/executor.py `_stage_feeds`,
`_BoundStep.dispatch`), so each must agree with K sequential
`Executor.run` calls in what it returns, in the state and the key it
leaves in the scope, in the spans a dispatch enters, in how a scope
that holds no key yet is seeded, and in how a fetch name that does not
exist is refused.
"""
import time
from functools import partial

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.executor import RNG_VAR
from paddle_tpu.observability import tracing

K = 3
BATCH = 16          # a multiple of the lane's 8 CPU devices
ENTRIES = ["run", "run_steps_dict", "run_steps_list", "prepare",
           "prepare_steps", "data_parallel"]
# entry points that take one feed dict for all K steps
SHARED_FEED = {"run_steps_dict", "prepare_steps"}
# ... and make one dispatch of them
ONE_DISPATCH = SHARED_FEED | {"run_steps_list"}


def _train_program():
    """fc -> dropout -> fc -> loss, SGD: state that moves and noise
    that follows the key."""
    from paddle_tpu import unique_name

    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="int64")
            h = fluid.layers.fc(x, size=16, act="relu")
            h = fluid.layers.dropout(h, dropout_prob=0.5)
            logits = fluid.layers.fc(h, size=4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
    main.random_seed = 5
    startup.random_seed = 5
    return main, startup, loss


def _noise_program():
    """Dropout over a fed tensor: no parameter, so no startup run and
    a scope that holds no key when the first step is dispatched."""
    from paddle_tpu import unique_name

    with unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            out = fluid.layers.dropout(x, dropout_prob=0.5)
    main.random_seed = 23
    return main, out


def _feeds(entry):
    r = np.random.RandomState(0)
    feeds = [{"x": r.randn(BATCH, 8).astype(np.float32),
              "y": r.randint(0, 4, (BATCH, 1)).astype(np.int64)}
             for _ in range(K)]
    return [feeds[0]] * K if entry in SHARED_FEED else feeds


def _calls(entry, exe, program, feeds, fetches, scope):
    """One callable for each dispatch the K steps make through one
    entry point, each returning the dispatch's fetch list (and taking
    `return_numpy=`); what an entry point builds once (a prepared
    handle, a CompiledProgram) is built here, once."""
    run = dict(fetch_list=fetches, scope=scope)
    if entry == "run":
        return [partial(exe.run, program, feed=f, **run) for f in feeds]
    if entry == "run_steps_dict":
        return [partial(exe.run_steps, program, feed=feeds[0],
                        steps=len(feeds), **run)]
    if entry == "run_steps_list":
        return [partial(exe.run_steps, program, feed=feeds, **run)]
    if entry == "prepare":
        prepared = exe.prepare(program, feeds[0], **run)
        return [partial(prepared.run, f) for f in feeds]
    if entry == "prepare_steps":
        prepared = exe.prepare(program, feeds[0], steps=len(feeds),
                               **run)
        assert prepared.fallback_reason is None
        return [partial(prepared.run, feeds[0])]
    compiled = fluid.CompiledProgram(program).with_data_parallel()
    return [partial(exe.run, compiled, feed=f, **run) for f in feeds]


def _entry(entry, exe, program, feeds, fetch, scope):
    """A callable that takes the K steps through one entry point and
    returns [K, ...] of `fetch`."""
    calls = _calls(entry, exe, program, feeds, [fetch], scope)

    def steps():
        outs = [call()[0] for call in calls]
        return outs[0] if entry in ONE_DISPATCH else outs
    return lambda: np.stack([np.asarray(v) for v in steps()])


def _drive(entry, exe, program, feeds, fetch, scope):
    return _entry(entry, exe, program, feeds, fetch, scope)()


def _started(startup):
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    return exe, scope


def _scope_values(scope, names):
    return {n: np.asarray(scope._get(n)) for n in names}


@pytest.mark.parametrize("entry", ENTRIES)
def test_agrees_with_sequential_runs(entry):
    main, startup, loss = _train_program()
    feeds = _feeds(entry)
    exe, ref_scope = _started(startup)
    want = _drive("run", exe, main, feeds, loss, ref_scope)
    exe, scope = _started(startup)
    got = _drive(entry, exe, main, feeds, loss, scope)
    np.testing.assert_allclose(got.reshape(K), want.reshape(K),
                               rtol=1e-5, atol=1e-6)
    assert len(set(want.reshape(K).tolist())) == K     # state moved
    names = [n for n in ref_scope._vars if n != RNG_VAR]
    assert names
    ref_state, state = (_scope_values(s, names)
                        for s in (ref_scope, scope))
    for n in names:
        np.testing.assert_allclose(state[n], ref_state[n], rtol=1e-5,
                                   atol=1e-6, err_msg=n)
    np.testing.assert_array_equal(np.asarray(scope._get(RNG_VAR)),
                                  np.asarray(ref_scope._get(RNG_VAR)))


@pytest.mark.parametrize("entry", ENTRIES)
def test_enters_each_span_once_a_dispatch(entry):
    main, startup, loss = _train_program()
    feeds = _feeds(entry)
    exe, scope = _started(startup)
    steps = _entry(entry, exe, main, feeds, loss, scope)
    steps()                                             # compiled
    trace = tracing.Trace("dispatch-spans", 1)
    compiles = exe.compile_count
    with tracing.ambient([trace]):
        steps()
    assert exe.compile_count == compiles
    entered = {}
    for s in trace.spans:
        entered[s.name] = entered.get(s.name, 0) + 1
    dispatches = 1 if entry in ONE_DISPATCH else K
    assert {n: entered.get(n, 0) for n in (
        "exe.feed", "exe.state", "exe.call", "exe.store",
        "exe.fetch")} == dict.fromkeys(
        ("exe.feed", "exe.state", "exe.call", "exe.store",
         "exe.fetch"), dispatches)
    assert "exe.compile" not in entered
    assert entered["exe.lookup"] == dispatches


@pytest.mark.parametrize("entry", ENTRIES)
def test_seeds_a_keyless_scope_from_the_program(entry):
    main, out = _noise_program()
    x = np.ones((BATCH, 8), np.float32)
    feeds = [{"x": x}] * K
    exe, ref_scope = fluid.Executor(fluid.TPUPlace(0)), fluid.Scope()
    assert ref_scope._get(RNG_VAR) is None
    want = _drive("run", exe, main, feeds, out, ref_scope)
    assert 0 < (want[0] == 0).mean() < 1        # noise was drawn
    assert (want[0] != want[1]).any()           # ... and the key moved
    scope = fluid.Scope()
    got = _drive(entry, fluid.Executor(fluid.TPUPlace(0)), main, feeds,
                 out, scope)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(scope._get(RNG_VAR)),
                                  np.asarray(ref_scope._get(RNG_VAR)))


@pytest.mark.parametrize("entry", ENTRIES)
def test_missing_fetch_is_the_same_key_error(entry):
    main, startup, _loss = _train_program()
    exe, scope = _started(startup)
    with pytest.raises(KeyError) as err:
        _drive(entry, exe, main, _feeds(entry), "no_such_var", scope)
    assert err.value.args == (
        "fetch target 'no_such_var' does not exist in the program",)


# --- the transfers at a dispatch's boundary ----------------------------
# A dispatch hands a single-device program's host feeds to the
# executable as they are (it puts them on its device inside the
# call) and the data-parallel step's onto their sharding in one
# transfer, places the variables it finds in the scope as host arrays
# in one more, and reads all its fetches back together: what a
# slot-pool server's cycle looks like
# (inference/serving.py `_pre_dispatch` writes the block table, the
# prompt references and the active mask as host arrays before every
# prepared run, which fetches a dozen small arrays).

HOST_WRITTEN = ("served_tab", "served_ref", "served_act")
N_FEEDS, N_FETCHES = 3, 4
SPANS = ("exe.feed", "exe.state", "exe.call", "exe.store", "exe.fetch")


def _served_program():
    """Three feeds, four fetches, three persistable variables that the
    caller overwrites in the scope; one of them (`served_act`) the
    step also advances, so it is state that moves."""
    from paddle_tpu import unique_name

    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="int64")
            g = fluid.layers.data(name="g", shape=[8], dtype="float32")
            tab = fluid.layers.create_global_var(
                [4, 8], 0.0, "float32", persistable=True,
                name="served_tab")
            ref = fluid.layers.create_global_var(
                [8], 0.0, "float32", persistable=True,
                name="served_ref")
            act = fluid.layers.create_global_var(
                [1], 0.0, "float32", persistable=True,
                name="served_act")
            h = fluid.layers.elementwise_add(
                fluid.layers.elementwise_mul(x, g), ref)
            logits = fluid.layers.fc(h, size=4)
            total = fluid.layers.reduce_sum(tab)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            fluid.layers.increment(act, 1.0)
            fluid.optimizer.SGD(0.1).minimize(loss)
    main.random_seed = 7
    startup.random_seed = 7
    return main, startup, [loss, logits, total, act]


def _served_feeds(entry):
    r = np.random.RandomState(1)
    feeds = [{"x": r.randn(BATCH, 8).astype(np.float32),
              "y": r.randint(0, 4, (BATCH, 1)).astype(np.int64),
              "g": r.rand(BATCH, 8).astype(np.float32)}
             for _ in range(K)]
    return [feeds[0]] * K if entry in SHARED_FEED else feeds


def _write_host_state(scope, call):
    """What a scheduler does before a dispatch: host arrays into the
    scope, new ones every call."""
    scope._set("served_tab", np.full((4, 8), call + 1.0, np.float32))
    scope._set("served_ref", np.full((8,), 0.25 * call, np.float32))
    scope._set("served_act", np.array([10.0 * call], np.float32))


def _served_entry(entry, exe, program, feeds, fetches, scope,
                  return_numpy=True, write=_write_host_state):
    """A callable that takes the K steps through one entry point with
    `write(scope, call)` before every call of it, and returns the
    calls' fetch lists."""
    calls = _calls(entry, exe, program, feeds, fetches, scope)

    def steps():
        outs = []
        for i, call in enumerate(calls):
            if write is not None:
                write(scope, i)
            outs.append(call(return_numpy=return_numpy))
        return outs
    return steps


class _CountedJax:
    """`jax` as core/executor.py sees it, with the times of its
    `device_put` calls kept."""

    def __init__(self, real):
        self._real, self.put_at = real, []

    def __getattr__(self, name):
        return getattr(self._real, name)

    def device_put(self, *args, **kwargs):
        self.put_at.append(time.monotonic())
        return self._real.device_put(*args, **kwargs)


def _traced(steps, exe, monkeypatch):
    """(the spans of one more `steps()` by name in order, the
    device_put calls core/executor.py made inside each, the growth of
    the executor's transfer counters)."""
    from paddle_tpu.core import executor as executor_module

    counted = _CountedJax(executor_module.jax)
    monkeypatch.setattr(executor_module, "jax", counted)
    before = _transfer_counts(exe)
    trace = tracing.Trace("dispatch-transfers", 1)
    with tracing.ambient([trace]):
        steps()
    monkeypatch.undo()
    grown = {k: v - before[k] for k, v in _transfer_counts(exe).items()}
    spans = {n: sorted((s for s in trace.spans if s.name == n),
                       key=lambda s: s.t0) for n in SPANS}
    puts = {n: [sum(s.t0 <= t <= s.t1 for t in counted.put_at)
                for s in spans[n]] for n in SPANS}
    assert sum(map(sum, puts.values())) == len(counted.put_at)
    return spans, puts, grown


def _transfer_counts(exe):
    return {name.replace("paddle_tpu_executor_", ""): value
            for name, _labels, value in exe._metrics_samples()
            if name in ("paddle_tpu_executor_dispatches_total",
                        "paddle_tpu_executor_placed_arrays_total",
                        "paddle_tpu_executor_placements_total",
                        "paddle_tpu_executor_fetched_arrays_total")}


@pytest.mark.parametrize("entry", ENTRIES)
def test_numpy_fetches_are_the_device_fetches(entry):
    main, startup, fetches = _served_program()
    feeds = _served_feeds(entry)
    got = {}
    for as_numpy in (True, False):
        exe, scope = _started(startup)
        outs = _served_entry(entry, exe, main, feeds, fetches, scope,
                             return_numpy=as_numpy)()
        names = sorted(n for n in scope._vars)
        got[as_numpy] = outs, _scope_values(scope, names)
        # placed, as a dispatch leaves it (under a rule of its own the
        # scope keeps what it held of a constant)
        for n in HOST_WRITTEN if entry != "data_parallel" \
                else ("served_act",):
            assert not isinstance(scope._get(n), np.ndarray), n
    (outs, state), (dev_outs, dev_state) = got[True], got[False]
    assert len(outs) == len(dev_outs) == (
        1 if entry in ONE_DISPATCH else K)
    for call, dev_call in zip(outs, dev_outs):
        assert len(call) == len(dev_call) == N_FETCHES
        for v, dv in zip(call, dev_call):
            assert type(v) is np.ndarray
            assert not isinstance(dv, np.ndarray)
            want = np.asarray(dv)
            assert (v.dtype, v.shape) == (want.dtype, want.shape)
            np.testing.assert_array_equal(v, want)
    assert sorted(state) == sorted(dev_state)
    for n in state:
        np.testing.assert_array_equal(state[n], dev_state[n], err_msg=n)
    # the host-written values reached the step: the table's sum is
    # fetched, the counter went on from what was written
    last = len(outs) - 1
    np.testing.assert_array_equal(
        np.asarray(outs[last][2]).reshape(-1)[-1], 32.0 * (last + 1))
    steps_a_call = K if entry in ONE_DISPATCH else 1
    np.testing.assert_array_equal(
        state["served_act"], [10.0 * last + steps_a_call])


@pytest.mark.parametrize("entry", ENTRIES)
def test_at_most_one_placement_for_feeds_and_one_for_state(
        entry, monkeypatch):
    main, startup, fetches = _served_program()
    feeds = _served_feeds(entry)
    exe, scope = _started(startup)
    steps = _served_entry(entry, exe, main, feeds, fetches, scope)
    steps()                                             # compiled
    spans, puts, _grown = _traced(steps, exe, monkeypatch)
    dispatches = 1 if entry in ONE_DISPATCH else K
    assert [len(spans[n]) for n in SPANS] == [dispatches] * len(SPANS)
    # the data-parallel path puts its feeds on their sharding and has
    # a rule of its own, outside core/executor.py, for each variable
    dp = entry == "data_parallel"
    assert puts["exe.feed"] == [1 if dp else 0] * dispatches
    assert puts["exe.state"] == [0 if dp else 1] * dispatches
    assert puts["exe.call"] == puts["exe.store"] \
        == puts["exe.fetch"] == [0] * dispatches
    # nothing wrote the scope since: its state is on the device
    quiet = _served_entry(entry, exe, main, feeds, fetches, scope,
                          write=None)
    _spans, puts, _grown = _traced(quiet, exe, monkeypatch)
    assert puts["exe.feed"] == [1 if dp else 0] * dispatches
    assert puts["exe.state"] == [0] * dispatches


@pytest.mark.parametrize("entry", ENTRIES)
def test_spans_and_counters_say_what_went(entry, monkeypatch):
    main, startup, fetches = _served_program()
    feeds = _served_feeds(entry)
    exe, scope = _started(startup)
    steps = _served_entry(entry, exe, main, feeds, fetches, scope)
    steps()                                             # compiled
    spans, puts, grown = _traced(steps, exe, monkeypatch)
    dispatches = 1 if entry in ONE_DISPATCH else K
    dp = entry == "data_parallel"
    put_feeds = N_FEEDS if dp else 0
    host_state = 0 if dp else len(HOST_WRITTEN)
    for n, arrays in (("exe.feed", put_feeds),
                      ("exe.state", host_state)):
        assert [s.attrs["arrays"] for s in spans[n]] \
            == [arrays] * dispatches
        assert [s.attrs["puts"] for s in spans[n]] == puts[n]
    assert [s.attrs["arrays"] for s in spans["exe.fetch"]] \
        == [N_FETCHES] * dispatches
    assert grown == {
        "dispatches_total": dispatches,
        "placed_arrays_total": dispatches * (put_feeds + host_state),
        "placements_total": sum(puts["exe.feed"] + puts["exe.state"]),
        "fetched_arrays_total": dispatches * N_FETCHES}
