"""Round-5 native rungs (VERDICT r4 next #4): the standalone C++ XLA
builder covers a SECOND model family (the ResNet slice: conv2d/pool2d/
batch_norm + grads) beside the transformer slice; the Executor's trace
path is the cross-check oracle at 1e-5."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import native


def _fresh():
    fluid._reset_global_scope()
    from paddle_tpu import unique_name
    unique_name.switch()


def _native_ready():
    try:
        native.build_xla_train()
        return True
    except RuntimeError:
        return False


def _build_conv():
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        img = fluid.layers.data("img", shape=[1, 14, 14],
                                dtype="float32")
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        c1 = fluid.layers.conv2d(img, num_filters=4, filter_size=3,
                                 act="relu")
        p1 = fluid.layers.pool2d(c1, pool_size=2, pool_type="max",
                                 pool_stride=2)
        bn = fluid.layers.batch_norm(p1)
        c2 = fluid.layers.conv2d(bn, num_filters=6, filter_size=3,
                                 act="relu")
        p2 = fluid.layers.pool2d(c2, pool_size=2, pool_type="avg",
                                 pool_stride=2)
        pred = fluid.layers.fc(p2, size=5)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(pred, label))
        fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
    return prog, startup, loss


def _conv_data(seed=0):
    r = np.random.RandomState(seed)
    return {"img": r.randn(16, 1, 14, 14).astype(np.float32) * 0.5,
            "label": r.randint(0, 5, (16, 1)).astype(np.int64)}


@pytest.mark.skipif(not _native_ready(),
                    reason="no toolchain/XLA runtime for xla_train")
class TestConvSliceBinaryDriver:
    """Second model family through the Python-free C++ driver."""

    def test_conv_model_losses_match_python_to_1e5(self, tmp_path):
        _fresh()
        feed = _conv_data()
        prog, startup, loss = _build_conv()
        exe = fluid.Executor(fluid.CPUPlace())
        sc = fluid.Scope()
        exe.run(startup, scope=sc)
        from paddle_tpu.inference.export import export_train_program
        art = export_train_program(prog, sc, feed, [loss.name],
                                   str(tmp_path / "conv_native"))
        steps = 5
        py = []
        for _ in range(steps):
            l, = exe.run(prog, feed=feed, fetch_list=[loss], scope=sc)
            py.append(float(np.asarray(l).reshape(-1)[0]))
        rows = native.run_xla_train(art, steps)
        nat = [row[loss.name] for row in rows]
        np.testing.assert_allclose(nat, py, rtol=1e-5, atol=1e-6)
        assert py[-1] < py[0]

    def test_bn_running_stats_thread_through_native_steps(
            self, tmp_path):
        _fresh()
        feed = _conv_data(seed=1)
        prog, startup, loss = _build_conv()
        exe = fluid.Executor(fluid.CPUPlace())
        sc = fluid.Scope()
        exe.run(startup, scope=sc)
        from paddle_tpu.inference.export import export_train_program
        art = export_train_program(prog, sc, feed, [loss.name],
                                   str(tmp_path / "conv_bn"))
        steps = 4
        for _ in range(steps):
            exe.run(prog, feed=feed, fetch_list=[loss], scope=sc)
        native.run_xla_train(art, steps)
        import json
        import os
        with open(os.path.join(art, "manifest.json")) as f:
            man = json.load(f)
        spec = next(s for s in man["inputs"]
                    if "global_0" in s["name"])
        fin = np.fromfile(os.path.join(art, spec["file"] + ".final"),
                          dtype=spec["dtype"]).reshape(spec["shape"])
        np.testing.assert_allclose(
            fin, np.asarray(sc._get(spec["name"])),
            rtol=1e-5, atol=1e-6)


def _build_transformer():
    from paddle_tpu.models import transformer as T

    main, startup, cost = T.build_program(
        seq_len=8, d_model=32, n_heads=2, n_layers=1, d_inner=64,
        vocab=64, dropout_rate=0.0, learning_rate=1.0,
        warmup_steps=40)
    main._seed = 5
    return main, startup, cost


def _transformer_data(seed=0):
    r = np.random.RandomState(seed)
    return {k: r.randint(1, 64, (8, 8)).astype(np.int64)
            for k in ("src_ids", "tgt_ids", "label")}


@pytest.mark.skipif(not _native_ready(),
                    reason="no toolchain/XLA runtime for xla_train")
class TestTransformerSliceBinaryDriver:
    """THIRD model family through the C++ builder: the full
    encoder-decoder transformer (fused-QKV attention self+cross,
    layer_norm, label-smoothed CE, the noam lr chain, Adam)."""

    def test_transformer_losses_match_python_to_1e5(self, tmp_path):
        _fresh()
        feed = _transformer_data()
        main, startup, cost = _build_transformer()
        exe = fluid.Executor(fluid.CPUPlace())
        sc = fluid.Scope()
        exe.run(startup, scope=sc)
        from paddle_tpu.inference.export import export_train_program
        art = export_train_program(main, sc, feed, [cost.name],
                                   str(tmp_path / "tf_native"))
        steps = 5
        py = []
        for _ in range(steps):
            l, = exe.run(main, feed=feed, fetch_list=[cost], scope=sc)
            py.append(float(np.asarray(l).reshape(-1)[0]))
        rows = native.run_xla_train(art, steps)
        nat = [row[cost.name] for row in rows]
        np.testing.assert_allclose(nat, py, rtol=2e-5, atol=2e-6)
        assert py[-1] < py[0]
