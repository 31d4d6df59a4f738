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


def _entry(entry, exe, program, feeds, fetch, scope):
    """A callable that takes the K steps through one entry point and
    returns [K, ...] of `fetch`; what an entry point builds once (a
    prepared handle, a CompiledProgram) is built here, once."""
    run = dict(fetch_list=[fetch], scope=scope)
    if entry == "run":
        def steps():
            return [exe.run(program, feed=f, **run)[0] for f in feeds]
    elif entry == "run_steps_dict":
        def steps():
            return exe.run_steps(program, feed=feeds[0],
                                 steps=len(feeds), **run)[0]
    elif entry == "run_steps_list":
        def steps():
            return exe.run_steps(program, feed=feeds, **run)[0]
    elif entry == "prepare":
        prepared = exe.prepare(program, feeds[0], **run)

        def steps():
            return [prepared.run(f)[0] for f in feeds]
    elif entry == "prepare_steps":
        prepared = exe.prepare(program, feeds[0], steps=len(feeds),
                               **run)
        assert prepared.fallback_reason is None

        def steps():
            return prepared.run(feeds[0])[0]
    else:
        compiled = fluid.CompiledProgram(program).with_data_parallel()

        def steps():
            return [exe.run(compiled, feed=f, **run)[0] for f in feeds]
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
