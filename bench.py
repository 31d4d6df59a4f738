"""Benchmark: all 5 BASELINE.md configs on the real chip.

Mirrors the reference harness semantics (reference benchmark/fluid/
fluid_benchmark.py:296-300: examples/sec = num_samples / elapsed), one
JSON line per config, the flagship Transformer-base line FIRST (the
driver's headline metric). Each config also asserts its loss decreases
over the timed window (the reference's loss-parity oracle, reduced to
the single-chip case).

Transformer runs under bf16 AMP (paddle_tpu/amp.py) with the Pallas
flash-attention forward+backward kernels and reports achieved MFU
against the chip's bf16 peak. vs_baseline for the two north-star
configs (BASELINE.json: v5e-16 pod >= 1x H100) is measured-per-chip /
(H100-equivalent / 16 chips): transformer 100k tok/s -> 6250 tok/s/chip,
ResNet-50 2500 imgs/s -> 156.25 imgs/s/chip. The other three configs
have no reference absolute number (BASELINE.md: "trains with loss
parity"); their vs_baseline is measured / the same per-chip-sliced
self-derived target recorded in TARGETS below.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import traceback

import numpy as np

TARGETS = {
    # per-chip north-star slices (see module docstring)
    "transformer": 6250.0,     # tokens/sec/chip
    "resnet50": 156.25,        # imgs/sec/chip
    # self-derived: no reference absolute exists (BASELINE.md)
    "stacked_lstm": 3125.0,    # words/sec/chip (50k wps H100-class / 16)
    "ctr": 6250.0,             # examples/sec/chip (100k eps / 16)
    "mnist": 10000.0,          # examples/sec/chip
}

# bf16 peak FLOP/s by device kind substring
_PEAKS = (("v6", 918e12), ("v5p", 459e12), ("v5", 197e12),
          ("v4", 275e12), ("h100", 989e12))


# Shared measurement scaffolding (benchmark/harness.py): interleaved
# best-of-N legs, telemetry snapshots, and the BENCH_SELF schema
# guard — one implementation for all configs.
from benchmark import harness as _harness

_telemetry_snapshot = _harness.telemetry_snapshot
_write_bench_self = _harness.write_bench_self


def _peak_flops(device_kind: str) -> float:
    kind = device_kind.lower().replace(" ", "")
    for sub, peak in _PEAKS:
        if sub in kind:
            return peak
    raise ValueError(
        f"no bf16 peak on record for device_kind {device_kind!r}; a "
        f"utilisation against a guessed peak is not a measurement "
        f"(add the device to _PEAKS with its source)")


def _analytic_train_flops(prog, batch, seq=None):
    """FLOPs per TRAINING step from the program graph: walk the
    forward ops and count the matmul-class work (conv2d, mul/matmul,
    lstm recurrent matmuls) from declared shapes, then apply the
    standard train = 3x forward (backward re-does each matmul twice).
    Elementwise/norm work is ignored — on TPU it is fused into the
    matmuls and contributes negligibly to the FLOP count (not
    necessarily to the runtime; that gap IS what MFU exposes).

    Dynamic dims resolve positionally: a leading -1 is the batch;
    later -1s are the (padded) sequence length `seq`."""
    block = prog.global_block

    def shape_of(name):
        v = block._find_var_recursive(name)
        if v is None or not v.shape:
            return None
        out = []
        for i, d in enumerate(v.shape):
            if d != -1:
                out.append(d)
            elif i == 0:
                out.append(batch)
            else:
                if seq is None:
                    return None
                out.append(seq)
        return tuple(out)

    total = 0.0
    for op in block.ops:
        if op.attrs.get("op_role") in ("backward", "optimize"):
            continue
        if op.type in ("conv2d", "depthwise_conv2d"):
            w = shape_of(op.inputs["Filter"][0])
            out = shape_of(op.outputs["Output"][0])
            if w and out:
                # [F, Cin/g, kh, kw] x [B, F, Ho, Wo]
                total += 2.0 * out[0] * out[2] * out[3] * out[1] \
                    * w[1] * w[2] * w[3]
        elif op.type in ("mul", "matmul", "matmul_v2"):
            x = shape_of(op.inputs["X"][0])
            y = shape_of(op.inputs["Y"][0])
            if x and y and len(y) >= 2:
                numel_x = 1
                for d in x:
                    numel_x *= d
                if op.type == "mul":
                    # mul flattens x's trailing dims into the
                    # contraction (x_num_col_dims semantics):
                    # FLOPs = 2 * |x| * cols
                    y_ncd = op.attrs.get("y_num_col_dims", 1)
                    cols = 1
                    for d in y[y_ncd:]:
                        cols *= d
                else:
                    # matmul: output columns depend on transpose_Y
                    # (QK^T-style calls contract y's LAST dim)
                    ty = op.attrs.get("transpose_Y",
                                      op.attrs.get("transpose_y",
                                                   False))
                    cols = y[-2] if ty else y[-1]
                total += 2.0 * numel_x * cols
        elif op.type in ("dynamic_lstm", "lstm", "cudnn_lstm"):
            x = shape_of(op.inputs.get("Input", [None])[0]
                         or op.inputs.get("X", [None])[0])
            w = shape_of(op.inputs.get("Weight", [None])[0])
            if x and w:
                # recurrent matmul per timestep: [B, h] x [h, 4h]
                t_steps = x[1] if len(x) >= 3 else 1
                b = x[0]
                total += 2.0 * b * t_steps * w[0] * w[1]
        elif op.type == "switch_moe":
            x = shape_of(op.inputs["X"][0])
            w1 = shape_of(op.inputs["W1"][0])
            if x and w1:
                toks = 1
                for d in x[:-1]:
                    toks *= d
                k = op.attrs.get("top_k", 1)
                # each routed token does up+down expert matmuls
                total += 2.0 * 2 * toks * k * w1[1] * w1[2]
    return 3.0 * total


def _mfu(value_per_sec, flops_per_unit):
    import jax

    peak = _peak_flops(jax.devices()[0].device_kind)
    return round(value_per_sec * flops_per_unit / peak, 4)


def _transformer_flops_tok(d_model, d_inner, seq, n_layers, vocab):
    """Analytic matmul+attention FLOPs per token (fwd); train = 3x."""
    d, di, t = d_model, d_inner, seq
    enc = n_layers * (8 * d * d + 4 * d * di + 4 * t * d)
    dec = n_layers * (16 * d * d + 4 * d * di + 8 * t * d)
    logits = 2 * d * vocab
    return 3.0 * (enc + dec + logits)


def _time_loop(exe, prog, feed, fetch, steps, warmup):
    """Timed window = ONE prepared K-step scan call: the whole K-step
    loop is a single device-resident lax.scan, so the window holds
    zero Python dispatches and exactly one host readback (vs one
    pipelined dispatch per step before -- PERF.md "Host dispatch &
    the multi-step scan"). Programs that cannot scan fall back to the
    per-step path inside the prepared handle (named reason on
    exe.last_run_steps_fallback) and this loop still measures them.

    Warmup-K trap, guarded at the source (CLAUDE.md r6 learning): the
    scan executable is specialized on K, so a warmup at a different K
    silently times a cold compile. Here warmup and the timed window
    go through ONE Executor.prepare(steps=K) handle -- the same K by
    construction -- and a belt-and-braces assertion verifies the
    timed window compiled nothing.
    """
    import jax

    # the same batch is fed every step (reference fluid_benchmark feeds
    # synthetic batches too); transfer it once so the timed window
    # measures training, not repeated uploads of identical bytes
    feed = {k: jax.device_put(v) for k, v in feed.items()}
    # prepared dispatch: executable + binding plans resolve once (and
    # load from the warm-start disk cache under FLAGS_compile_cache)
    prepared = exe.prepare(prog, feed, fetch_list=[fetch], steps=steps)
    loss0 = None
    if warmup > 0:
        # pays the XLA compile of the K-step scan (or the disk load)
        out = prepared.run(feed, return_numpy=False)
        loss0 = float(np.asarray(out[0][-1]).reshape(-1)[0])
    compiles_before = exe.compile_count
    t0 = time.perf_counter()
    out = prepared.run(feed, return_numpy=False)
    # fetching ONE element of the stacked losses drains the scan --
    # the single host round-trip of the whole window
    loss1 = float(np.asarray(out[0][-1]).reshape(-1)[0])
    elapsed = time.perf_counter() - t0
    if warmup > 0 and exe.compile_count != compiles_before:
        raise AssertionError(
            f"bench _time_loop: the timed window compiled "
            f"{exe.compile_count - compiles_before} executable(s) -- "
            f"warmup did not warm the K={steps} scan cache "
            f"(warmup-K mismatch trap); the measurement timed a cold "
            f"compile and is invalid")
    if loss0 is None:
        loss0 = float(np.asarray(out[0][0]).reshape(-1)[0])
    return elapsed, loss0, loss1


def bench_transformer():
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.models import transformer as T

    seq, batch, vocab = 256, 128, 32000
    d_model, n_heads, n_layers, d_inner = 512, 8, 6, 2048
    steps, warmup = 15, 5

    main_prog, startup, cost = T.build_program(
        seq_len=seq, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        d_inner=d_inner, vocab=vocab, dropout_rate=0.0,
        with_optimizer=True, learning_rate=2.0, warmup_steps=8000)
    exe = fluid.Executor(fluid.TPUPlace())
    r = np.random.RandomState(0)
    feed = {
        "src_ids": r.randint(0, vocab, (batch, seq)).astype(np.int64),
        "tgt_ids": r.randint(0, vocab, (batch, seq)).astype(np.int64),
        "label": r.randint(0, vocab, (batch, seq)).astype(np.int64),
    }
    with amp.amp_guard(True):
        exe.run(startup)
        elapsed, loss0, loss1 = _time_loop(exe, main_prog, feed, cost,
                                           steps, warmup)
    tokens_per_sec = steps * batch * seq / elapsed
    flops_tok = _transformer_flops_tok(d_model, d_inner, seq,
                                       n_layers, vocab)
    peak = _peak_flops(jax.devices()[0].device_kind)
    mfu = tokens_per_sec * flops_tok / peak
    return {
        "metric": "transformer_base_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tokens_per_sec / TARGETS["transformer"], 3),
        "mfu": round(mfu, 4),
        "loss0": round(loss0, 4), "loss1": round(loss1, 4),
        "loss_decreased": bool(loss1 < loss0),
        "batch": batch, "seq_len": seq, "amp": "bf16",
    }


def bench_resnet50():
    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.models import resnet

    batch, steps, warmup = 64, 10, 3
    main_prog, startup, cost = resnet.build_program(
        depth=50, class_dim=1000, image_shape=(3, 224, 224), lr=0.1)
    exe = fluid.Executor(fluid.TPUPlace())
    r = np.random.RandomState(0)
    feed = {
        "img": r.randn(batch, 3, 224, 224).astype(np.float32),
        "label": r.randint(0, 1000, (batch, 1)).astype(np.int64),
    }
    with amp.amp_guard(True):
        exe.run(startup)
        elapsed, loss0, loss1 = _time_loop(exe, main_prog, feed, cost,
                                           steps, warmup)
    imgs_per_sec = steps * batch / elapsed
    flops_img = _analytic_train_flops(main_prog, batch) / batch
    return {
        "metric": "resnet50_train_imgs_per_sec_per_chip",
        "value": round(imgs_per_sec, 1),
        "unit": "imgs/sec",
        "vs_baseline": round(imgs_per_sec / TARGETS["resnet50"], 3),
        "mfu": _mfu(imgs_per_sec, flops_img),
        "loss0": round(loss0, 4), "loss1": round(loss1, 4),
        "loss_decreased": bool(loss1 < loss0),
        "batch": batch, "amp": "bf16",
    }


def bench_stacked_lstm():
    import paddle_tpu as fluid
    from paddle_tpu.models import stacked_dynamic_lstm as M

    batch, seq, steps, warmup = 32, 100, 10, 3
    main_prog, startup, cost, _ = M.build_program(
        dict_dim=10000, emb_dim=512, hid_dim=512, stacked_num=3)
    exe = fluid.Executor(fluid.TPUPlace())
    r = np.random.RandomState(0)
    # variable-length batch, padded + @SEQ_LEN (LoD capability)
    lens = r.randint(seq // 2, seq + 1, (batch,)).astype(np.int32)
    words = np.zeros((batch, seq), dtype=np.int64)
    for i, n in enumerate(lens):
        words[i, :n] = r.randint(1, 10000, (n,))
    feed = {
        "words": words,
        "words@SEQ_LEN": lens,
        "label": r.randint(0, 2, (batch, 1)).astype(np.int64),
    }
    exe.run(startup)
    elapsed, loss0, loss1 = _time_loop(exe, main_prog, feed, cost,
                                       steps, warmup)
    words_per_sec = steps * int(lens.sum()) / elapsed
    # per processed (padded) word: the chip computes padded timesteps
    # regardless, so MFU is vs padded work while words/sec counts real
    # words — both reported, the gap is the padding tax
    flops_word = _analytic_train_flops(main_prog, batch, seq=seq) \
        / (batch * seq)
    padded_words_per_sec = steps * batch * seq / elapsed
    return {
        "metric": "stacked_dynamic_lstm_train_words_per_sec_per_chip",
        "value": round(words_per_sec, 1),
        "unit": "words/sec",
        "vs_baseline": round(words_per_sec / TARGETS["stacked_lstm"], 3),
        "mfu": _mfu(padded_words_per_sec, flops_word),
        "loss0": round(loss0, 4), "loss1": round(loss1, 4),
        "loss_decreased": bool(loss1 < loss0),
        "batch": batch, "amp": "fp32",
    }


def bench_ctr():
    import paddle_tpu as fluid
    from paddle_tpu.models import ctr as M

    batch, slots, steps, warmup = 8192, 10, 10, 3
    # lr raised from the reference's 1e-4 so the loss-decrease oracle
    # moves visibly within the short timed window (throughput is the
    # metric; the oracle needs signal at 4-decimal rounding)
    main_prog, startup, cost, _ = M.build_program(lr=0.05)
    exe = fluid.Executor(fluid.TPUPlace())
    r = np.random.RandomState(0)
    feed = {
        "dnn_data": r.randint(1, 10001, (batch, slots)).astype(np.int64),
        "dnn_data@SEQ_LEN": np.full((batch,), slots, dtype=np.int32),
        "lr_data": r.randint(1, 10001, (batch, slots)).astype(np.int64),
        "lr_data@SEQ_LEN": np.full((batch,), slots, dtype=np.int32),
    }
    # click is a deterministic function of the ids so the loss oracle
    # has actual signal (random labels pin bce at ln2 and the
    # loss_decreased check degenerates to float noise); a per-id
    # threshold is directly learnable by the embeddings in few steps
    feed["click"] = (feed["dnn_data"][:, :1] > 5000).astype(np.int64)
    exe.run(startup)
    elapsed, loss0, loss1 = _time_loop(exe, main_prog, feed, cost,
                                       steps, warmup)
    examples_per_sec = steps * batch / elapsed
    return {
        "metric": "ctr_train_examples_per_sec_per_chip",
        "value": round(examples_per_sec, 1),
        "unit": "examples/sec",
        "vs_baseline": round(examples_per_sec / TARGETS["ctr"], 3),
        "loss0": round(loss0, 4), "loss1": round(loss1, 4),
        "loss_decreased": bool(loss1 < loss0),
        "batch": batch, "amp": "fp32",
        "note": "batch re-baselined 512->8192 in r2 (chip-filling config; r1 value 7.1k eps at 512)",
    }


def bench_mnist():
    import paddle_tpu as fluid
    from paddle_tpu.models import mnist as M

    batch, steps, warmup = 4096, 10, 3
    main_prog, startup, cost, _ = M.build_program(use_conv=True)
    with fluid.program_guard(main_prog, startup):
        fluid.optimizer.SGD(learning_rate=0.01).minimize(cost)
    exe = fluid.Executor(fluid.TPUPlace())
    r = np.random.RandomState(0)
    lab = r.randint(0, 10, (batch, 1)).astype(np.int64)
    img = r.randn(batch, 1, 28, 28).astype(np.float32) * 0.1
    img[np.arange(batch), 0, 0, lab[:, 0]] += 2.0  # separable signal
    feed = {"img": img, "label": lab}
    exe.run(startup)
    elapsed, loss0, loss1 = _time_loop(exe, main_prog, feed, cost,
                                       steps, warmup)
    examples_per_sec = steps * batch / elapsed
    return {
        "metric": "mnist_train_examples_per_sec_per_chip",
        "value": round(examples_per_sec, 1),
        "unit": "examples/sec",
        "vs_baseline": round(examples_per_sec / TARGETS["mnist"], 3),
        "loss0": round(loss0, 4), "loss1": round(loss1, 4),
        "loss_decreased": bool(loss1 < loss0),
        "batch": batch, "amp": "fp32",
        "note": "batch re-baselined 256->4096 in r2 (chip-filling "
                "config; r1 value 3.6k eps at 256)",
    }


def bench_transformer_scan(batch=256, seq=256):
    """Transformer-base trained through scan-over-layers
    (PipelineTrainer pp=1): the HLO stops growing linearly in depth,
    which is the framework-native fix for the remote compile helper
    500ing on the fully-unrolled batch>=256 program (PERF.md). OPT-IN
    (run `python bench.py transformer_scan`): kept out of the default
    driver window until A/B'd on the real chip."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.models import transformer as T
    from paddle_tpu.parallel.pipeline_program import (PipelineTrainer,
                                                      propose_loops)

    vocab = 32000
    d_model, n_heads, n_layers, d_inner = 512, 8, 6, 2048
    steps, warmup = 15, 5
    main_prog, startup, cost = T.build_program(
        seq_len=seq, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_inner=d_inner, vocab=vocab,
        dropout_rate=0.0, with_optimizer=True, learning_rate=2.0,
        warmup_steps=8000)
    loops = propose_loops(main_prog, cost.name)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    r = np.random.RandomState(0)
    feed = {
        "src_ids": r.randint(0, vocab, (batch, seq)).astype(np.int64),
        "tgt_ids": r.randint(0, vocab, (batch, seq)).astype(np.int64),
        "label": r.randint(0, vocab, (batch, seq)).astype(np.int64),
    }
    with amp.amp_guard(True):
        exe.run(startup, scope=scope)
        tr = PipelineTrainer(main_prog, cost, loops=loops)
        tr.initialize(scope)
        for _ in range(warmup):
            out = tr.run(feed=feed)
        loss0 = float(np.asarray(out[0]).reshape(-1)[0])
        t0 = time.perf_counter()
        for _ in range(steps):
            out = tr.run(feed=feed, return_numpy=False)
        loss1 = float(np.asarray(out[0]).reshape(-1)[0])
        elapsed = time.perf_counter() - t0
    tokens_per_sec = steps * batch * seq / elapsed
    flops_tok = _transformer_flops_tok(d_model, d_inner, seq,
                                       n_layers, vocab)
    return {
        "metric": "transformer_scan_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tokens_per_sec / TARGETS["transformer"], 3),
        "mfu": _mfu(tokens_per_sec, flops_tok),
        "loss0": round(loss0, 4), "loss1": round(loss1, 4),
        "loss_decreased": bool(loss1 < loss0),
        "batch": batch, "seq_len": seq, "amp": "bf16",
        "lowering": "scan-over-layers",
    }


def bench_moe_transformer(batch=64, seq=256):
    """Switch-MoE decoder LM (models/moe_transformer.py): dense FLOPs
    of a 4-layer model, 8x expert capacity on the alternating layers.
    Reports tokens/s + the per-layer drop fractions. OPT-IN
    (`python bench.py moe_transformer`)."""
    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.models import moe_transformer as M

    vocab = 32000
    d_model, n_heads, n_layers, d_inner = 512, 8, 4, 2048
    steps, warmup = 15, 5
    main_prog, startup, cost = M.build_program(
        seq_len=seq, vocab=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_inner=d_inner, n_experts=8, top_k=1,
        capacity_factor=2.0, dropout_rate=0.0, learning_rate=2.0,
        warmup_steps=8000)
    exe = fluid.Executor(fluid.TPUPlace())
    r = np.random.RandomState(0)
    feed = {
        "src_ids": r.randint(0, vocab, (batch, seq)).astype(np.int64),
        "label": r.randint(0, vocab, (batch, seq)).astype(np.int64),
    }
    drops = main_prog._moe_drop_vars
    with amp.amp_guard(True):
        exe.run(startup)
        elapsed, loss0, loss1 = _time_loop(exe, main_prog, feed, cost,
                                           steps, warmup)
        drop_vals = [
            float(np.asarray(v).reshape(-1)[0])
            for v in exe.run(main_prog, feed=feed, fetch_list=drops)]
    tokens_per_sec = steps * batch * seq / elapsed
    # dense-equivalent FLOPs: attention stack + top-1 expert FFN per
    # token (same matmul work per token as a dense FFN) + logits
    d, di = d_model, d_inner
    flops_tok = 3.0 * (n_layers * (8 * d * d + 4 * d * di
                                   + 4 * seq * d)
                       + 2 * d * vocab)
    return {
        "metric": "moe_transformer_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tokens_per_sec / TARGETS["transformer"], 3),
        "mfu": _mfu(tokens_per_sec, flops_tok),
        "loss0": round(loss0, 4), "loss1": round(loss1, 4),
        "loss_decreased": bool(loss1 < loss0),
        "drop_fracs": [round(v, 4) for v in drop_vals],
        "batch": batch, "seq_len": seq, "amp": "bf16",
        "n_experts": 8,
    }


BENCHES = [("transformer", bench_transformer),
           ("resnet50", bench_resnet50),
           ("stacked_lstm", bench_stacked_lstm),
           ("ctr", bench_ctr),
           ("mnist", bench_mnist)]

def bench_transformer_fused():
    """Transformer-base with the whole-layer fused attention block
    (PADDLE_TPU_FUSE_ATTN_BLOCK=1 -> ops/pallas/attention_block.py):
    the PERF.md MFU lever, built in r5 and never run on a chip
    (ROADMAP S4/S5). A/B recipe:
        python bench.py transformer        # unfused baseline
        python bench.py transformer_fused  # fused block
    Same params/init/math (tests/test_attention_block.py), so the
    tokens/s and mfu fields are directly comparable."""
    import os

    prev = os.environ.get("PADDLE_TPU_FUSE_ATTN_BLOCK")
    os.environ["PADDLE_TPU_FUSE_ATTN_BLOCK"] = "1"
    try:
        res = bench_transformer()
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_FUSE_ATTN_BLOCK", None)
        else:
            os.environ["PADDLE_TPU_FUSE_ATTN_BLOCK"] = prev
    res["metric"] = "transformer_fused_train_tokens_per_sec_per_chip"
    res["lowering"] = "fused-attention-block"
    return res


def bench_transformer_scan_fused():
    """scan-over-layers lowering AND the whole-layer fused kernels
    together — the likely best batch-256 config (the scan dodges the
    compile-service 500, the fused blocks cut the HBM/exp cost);
    parity pinned by tests/test_attention_block.py."""
    import os

    prev = os.environ.get("PADDLE_TPU_FUSE_ATTN_BLOCK")
    os.environ["PADDLE_TPU_FUSE_ATTN_BLOCK"] = "1"
    try:
        res = bench_transformer_scan()
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_FUSE_ATTN_BLOCK", None)
        else:
            os.environ["PADDLE_TPU_FUSE_ATTN_BLOCK"] = prev
    res["metric"] = \
        "transformer_scan_fused_train_tokens_per_sec_per_chip"
    res["lowering"] = "scan-over-layers+fused-blocks"
    return res


def bench_serving(n_requests=400):
    """Inference serving throughput at batch-of-1 arrivals: the naive
    per-request `AnalysisPredictor.run` loop vs the DynamicBatcher
    server (inference/serving.py), cold and AOT-warmed. The win is
    the run_steps dispatch-amortization arithmetic applied to serving
    (PERF.md "Serving path")."""
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu.inference import (AnalysisConfig, InferenceServer,
                                      PaddleTensor,
                                      create_paddle_predictor)

    in_dim, hidden, classes = 256, 512, 32
    max_batch = 16
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[in_dim],
                              dtype="float32")
        h = fluid.layers.fc(input=x, size=hidden, act="relu")
        out = fluid.layers.fc(input=h, size=classes, act="softmax")
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    mdir = tempfile.mkdtemp(prefix="serving_bench_")
    fluid.save_inference_model(mdir, ["x"], [out], exe,
                               main_program=prog)
    pred = create_paddle_predictor(AnalysisConfig(mdir))
    r = np.random.RandomState(0)
    reqs = [r.randn(1, in_dim).astype(np.float32)
            for _ in range(n_requests)]

    def timed_naive():
        pred.run([PaddleTensor(reqs[0], name="x")])  # warm the shape
        t0 = time.perf_counter()
        for a in reqs:
            pred.run([PaddleTensor(a, name="x")])
        return n_requests / (time.perf_counter() - t0)

    def timed_server(warm):
        # share_cache=False isolates each measurement's compile work
        worker = pred.clone(share_cache=False)
        with InferenceServer(worker, max_batch_size=max_batch,
                             max_wait_ms=2.0) as srv:
            if warm:
                srv.aot_warmup()
            t0 = time.perf_counter()
            replies = [srv.submit({"x": a}) for a in reqs]
            for rep in replies:
                rep.result(timeout=600.0)
            rps = n_requests / (time.perf_counter() - t0)
            st = srv.stats()
        return rps, st

    naive_rps = timed_naive()
    cold_rps, _ = timed_server(warm=False)
    warm_rps, st = timed_server(warm=True)
    return {
        "metric": "serving_requests_per_sec_batch1_arrivals",
        "value": round(warm_rps, 1),
        "unit": "requests/sec",
        "naive_rps": round(naive_rps, 1),
        "batched_rps": round(cold_rps, 1),
        "batched_warmed_rps": round(warm_rps, 1),
        "speedup_batched": round(cold_rps / naive_rps, 2),
        "speedup_warmed": round(warm_rps / naive_rps, 2),
        "batch_occupancy": st["batch_occupancy"],
        "p50_ms": st["latency_ms"]["p50"],
        "p99_ms": st["latency_ms"]["p99"],
        "compile_count": st["compile_count"],
        "max_batch_size": max_batch,
        "n_requests": n_requests,
        "model": f"fc {in_dim}->{hidden}->{classes}",
        "telemetry": _telemetry_snapshot(st),
    }


def _coldstart_child(model_dir, cache_dir, n_requests):
    """Subprocess leg of bench_coldstart: a FRESH process loads the
    exported model, AOT-warms every bucket (loading executables from
    the disk compile cache when populated), and serves. Prints one
    JSON line; the parent interprets it. t_first_response_s counts
    from bench.py entry, so jax/XLA init, model load, warmup, and the
    first request are all inside it."""
    t_start = time.perf_counter()
    # CPU-only (see bench_coldstart): the parent pins this process
    # with JAX_PLATFORMS=cpu in its environment
    import jax

    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"coldstart child must run on the CPU backend, found "
            f"{platform!r}")
    from paddle_tpu.flags import set_flags

    set_flags({"FLAGS_compile_cache": "rw",
               "FLAGS_compile_cache_dir": cache_dir})
    from paddle_tpu.core.compile_cache import active_cache
    from paddle_tpu.inference import (AnalysisConfig, InferenceServer,
                                      create_paddle_predictor)

    pred = create_paddle_predictor(AnalysisConfig(model_dir))
    r = np.random.RandomState(0)
    in_dim = 256
    with InferenceServer(pred, max_batch_size=16,
                         max_wait_ms=2.0) as srv:
        srv.aot_warmup()
        srv.infer({"x": r.randn(1, in_dim).astype(np.float32)})
        t_first = time.perf_counter() - t_start
        reqs = [r.randn(1, in_dim).astype(np.float32)
                for _ in range(n_requests)]

        def _served_pass():
            t0 = time.perf_counter()
            replies = [srv.submit({"x": a}) for a in reqs]
            for rep in replies:
                rep.result(timeout=600.0)
            return n_requests / (time.perf_counter() - t0)

        # best-of-3, same as the naive leg (shared-CPU hosts are
        # noisy; harness discipline)
        rps = _harness.best_of(_served_pass, 3)
        st = srv.stats()
    cc = active_cache()
    print(json.dumps({
        "platform": platform,
        "t_first_response_s": round(t_first, 3),
        "rps": round(rps, 1),
        "compile_count": st["compile_count"],
        "disk_load_count": st["disk_load_count"],
        "p50_ms": st["latency_ms"]["p50"],
        "p99_ms": st["latency_ms"]["p99"],
        "disk_cache": cc.stats() if cc is not None else None,
    }), flush=True)


def bench_coldstart(n_requests=400):
    """Warm-start bench: time-to-first-response and compile/disk-hit
    counts for (a) a cold process and (b) a cold process whose disk
    compile cache was populated by (a) -- the PERF.md cold-path cost
    the warm-start layer (core/compile_cache.py) eliminates --
    alongside the naive per-request leg for the rps floor. Each leg
    is a REAL fresh python process (subprocess), so jax/XLA init and
    model load are honestly inside the measurement.

    CPU-ONLY by design, and the result says so (`platform`): a chip
    belongs to one process, so a parent that has touched JAX and two
    children cannot all use it. The parent pins itself and hands the
    children JAX_PLATFORMS=cpu in their environment; what this config
    reports are counts (compiles, disk loads) and CPU-host times, not
    device metrics."""
    import shutil
    import subprocess
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid
    from paddle_tpu.inference import (AnalysisConfig, PaddleTensor,
                                      create_paddle_predictor)

    in_dim, hidden, classes = 256, 512, 32
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[in_dim],
                              dtype="float32")
        h = fluid.layers.fc(input=x, size=hidden, act="relu")
        out = fluid.layers.fc(input=h, size=classes, act="softmax")
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    mdir = tempfile.mkdtemp(prefix="coldstart_bench_")
    fluid.save_inference_model(mdir, ["x"], [out], exe,
                               main_program=prog)

    # naive per-request floor (same model/arrivals as bench_serving)
    pred = create_paddle_predictor(AnalysisConfig(mdir))
    r = np.random.RandomState(0)
    reqs = [r.randn(1, in_dim).astype(np.float32)
            for _ in range(n_requests)]
    pred.run([PaddleTensor(reqs[0], name="x")])  # warm the shape

    def _naive_pass():
        t0 = time.perf_counter()
        for a in reqs:
            pred.run([PaddleTensor(a, name="x")])
        return n_requests / (time.perf_counter() - t0)

    # best-of-3 (harness discipline): shared-CPU hosts are noisy
    naive_rps = _harness.best_of(_naive_pass, 3)

    # a fixed path under the one cache root, emptied so the first
    # child really is cold
    from paddle_tpu.core.compile_cache import cache_root

    cache_dir = os.path.join(cache_root(), "coldstart_bench")
    shutil.rmtree(cache_dir, ignore_errors=True)

    def child(tag):
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, __file__, "_coldstart_child", mdir,
             cache_dir, str(n_requests)],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=900)
        wall = time.perf_counter() - t0
        if proc.returncode != 0:
            raise RuntimeError(
                f"coldstart child ({tag}) failed: "
                f"{proc.stderr[-2000:]}")
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        res["process_wall_s"] = round(wall, 3)
        return res

    cold = child("cold")           # populates cache_dir
    warm = child("disk-warmed")    # must serve with ZERO compiles
    return {
        "metric": "serving_coldstart_time_to_first_response",
        "value": warm["t_first_response_s"],
        "unit": "seconds",
        "platform": "cpu",  # CPU-only config (docstring)
        "cold": cold,
        "disk_warmed": warm,
        "naive_rps": round(naive_rps, 1),
        "warm_speedup_vs_naive": round(warm["rps"] / naive_rps, 2),
        "coldstart_speedup": round(
            cold["t_first_response_s"] / warm["t_first_response_s"],
            2),
        "zero_compile_warm_start": warm["compile_count"] == 0,
        "max_batch_size": 16,
        "n_requests": n_requests,
        "model": f"fc {in_dim}->{hidden}->{classes}",
        "telemetry": _telemetry_snapshot(),
    }


def bench_generation(n_requests=96):
    """Generation serving on a mixed-length (Zipf-ish) workload:
    static whole-loop GenerationServer vs ContinuousGenerationServer
    (slot pool + fused admission/decode-burst cycles). The static
    server pays head-of-line blocking — every batch runs to its
    LONGEST member's length — while the slot pool retires EOS'd lanes
    immediately and refills from the queue, so its advantage scales
    with the workload's length variance (PERF.md "Continuous
    batching").

    CPU-PINNED in code (ROADMAP S1 replaces this function): what it
    yields are counts and CPU-host times, not device metrics.
    Best-of-3 per leg: the CPU host it was written on swung
    single-pass walls ~3x."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import (ContinuousGenerationServer,
                                      GenerationServer,
                                      apply_eos_sentinel,
                                      count_generated_tokens)
    from paddle_tpu.models import transformer as T

    V, D, L, S, maxT = 16, 128, 2, 12, 64
    n_slots = 8
    end_id = 1
    rng = np.random.RandomState(7)

    def zipf_prompts(n, r):
        # terminator-copy prompts: EOS planted early for most rows
        # (short generations), none for a ~1-in-8 tail (full-buffer
        # runs) — the Zipf-ish mix where almost every static batch is
        # poisoned by one long member while most of its rows idle
        src = r.randint(3, V, (n, S)).astype(np.int64)
        for i in range(n):
            p = int(r.choice([1, 2, 3, S], p=[.45, .25, .175, .125]))
            if p < S:
                src[i, p:] = end_id
        return src

    # train the terminator-copy task so decode lengths are
    # model-driven (EOS mid-stream), then build both serving paths
    # over the same weights
    scope = Scope()
    with unique_name.guard():
        main_p, startup, loss = T.build_program(
            seq_len=S, d_model=D, n_heads=2, n_layers=L, d_inner=128,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
        with fluid.program_guard(main_p, startup):
            fluid.optimizer.Adam(learning_rate=0.002).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    for _ in range(600):
        src = zipf_prompts(8, rng)
        tgt_in = np.concatenate(
            [np.full((8, 1), 2, np.int64), src[:, :-1]], 1)
        exe.run(main_p, feed={"src_ids": src, "tgt_ids": tgt_in,
                              "label": src}, fetch_list=[loss],
                scope=scope)
    kwargs = dict(seq_len=S, max_out_len=maxT, d_model=D, n_heads=2,
                  n_layers=L, d_inner=128, vocab=V, start_id=2,
                  end_id=end_id)
    with unique_name.guard():
        inc_m, _, _, inc_buf = T.build_incremental_decode_program(
            **kwargs)
    with unique_name.guard():
        bundle = T.build_decode_step_program(n_slots=n_slots,
                                             **kwargs)

    srcs = zipf_prompts(n_requests, np.random.RandomState(31))
    ref, = exe.run(inc_m, feed={"src_ids": srcs},
                   fetch_list=[inc_buf], scope=scope)
    want = apply_eos_sentinel(np.asarray(ref), end_id)
    lens = count_generated_tokens(want, end_id)
    total_tokens = int(lens.sum())
    short = lens <= int(np.median(lens))

    def run_leg(make_server, submit):
        srv = make_server()
        try:
            done_at = [None] * n_requests
            t0 = time.perf_counter()
            replies = [submit(srv, s) for s in srcs]
            for i, rep in enumerate(replies):
                rep.add_done_callback(
                    lambda _f, i=i: done_at.__setitem__(
                        i, time.perf_counter()))
            outs = [rep.result(600.0) for rep in replies]
            wall = time.perf_counter() - t0
            # done-callbacks fire on the server thread AFTER result()
            # unblocks; wait for the stragglers before reading
            deadline = time.perf_counter() + 5.0
            while any(d is None for d in done_at) \
                    and time.perf_counter() < deadline:
                time.sleep(0.001)
            st = srv.stats()
        finally:
            srv.close()
        comp_ms = np.array([((d if d is not None else t0 + wall)
                             - t0) * 1e3 for d in done_at])
        return {"wall_s": wall, "tok_s": total_tokens / wall,
                "short_p50_ms": float(np.median(comp_ms[short])),
                "stats": st, "outs": outs}

    def static_leg():
        return run_leg(
            lambda: GenerationServer(
                inc_m, inc_buf, executor=exe, scope=scope,
                end_id=end_id, max_batch_size=n_slots,
                max_wait_ms=2.0),
            lambda srv, s: srv.submit({"src_ids": s[None]}))

    def continuous_leg():
        return run_leg(
            lambda: ContinuousGenerationServer(
                bundle, executor=exe, scope=scope, steps_per_tick=8),
            lambda srv, s: srv.submit(s))

    static_leg()       # warm the static bucket executables
    compiles_before = exe.compile_count
    warm_leg = continuous_leg()  # warms the serve executables
    # INTERLEAVED best-of-3 (harness.interleave_rounds): this host's
    # CPU-share throttle windows last seconds, so alternating legs
    # samples both servers under the same conditions — a sequential
    # best-of-3 can land one whole server inside a slow window and
    # report a 2x-off ratio. The two warm legs above are excluded
    # from the mins so BOTH sides are a best-of-3 over the same
    # interleaved windows (no sample-count asymmetry flattering
    # either ratio).
    rounds = _harness.interleave_rounds(
        [("static", static_leg), ("continuous", continuous_leg)],
        rounds=3)
    sbest = _harness.best_leg(rounds, "static")
    cbest = _harness.best_leg(rounds, "continuous")
    # warmup happens in the first server __init__; later legs and all
    # steady-state traffic must compile NOTHING
    steady_compiles = exe.compile_count - compiles_before \
        - warm_leg["stats"]["warmed_compiles"]
    # token-exact parity of the measured leg (sentinel rows vs the
    # whole-loop oracle) — a fast continuous leg that decoded wrong
    # tokens would be meaningless
    parity = all(
        np.array_equal(np.asarray(o), want[i])
        for leg in [warm_leg] + [r["continuous"] for r in rounds]
        for i, o in enumerate(leg["outs"]))
    cst = cbest["stats"]
    return {
        "metric": "generation_tokens_per_sec_mixed_len",
        "value": round(cbest["tok_s"], 1),
        "unit": "tokens/sec",
        "static_tok_s": round(sbest["tok_s"], 1),
        "continuous_tok_s": round(cbest["tok_s"], 1),
        "speedup_continuous": round(cbest["tok_s"] / sbest["tok_s"],
                                    2),
        "short_req_p50_ms": {
            "static": round(sbest["short_p50_ms"], 1),
            "continuous": round(cbest["short_p50_ms"], 1)},
        "token_parity_vs_whole_loop": parity,
        "steady_state_compiles": int(steady_compiles),
        "slot_occupancy": cst["slot_occupancy"],
        "ttft_p50_ms": cst["ttft_ms"]["p50"],
        "retired_per_s": cst["retired_per_s"],
        "serve_executables": len(bundle.serves),
        "n_requests": n_requests,
        "total_tokens": total_tokens,
        "len_histogram": {int(k): int(v) for k, v in
                          zip(*np.unique(lens, return_counts=True))},
        "workload": "zipf-ish terminator-copy",
        "model": (f"transformer d{D} L{L} S{S} maxT{maxT} "
                  f"slots{n_slots}"),
        "best_of": 3,
        "telemetry": _telemetry_snapshot(cst),
    }


def bench_paged(n_requests=192):
    """Paged KV cache + prefix reuse vs the r10 dense slot pool
    (models/decode_engine.py paged layout +
    PagedContinuousGenerationServer), at MATCHED KV byte budgets —
    the capacity story: the dense layout reserves the full
    [maxT, ...] self-KV and a private cross-KV per lane, so its KV
    budget carries 8 lanes; the same bytes as a shared block pool +
    refcounted prompt entries carry 16 lanes at this workload's
    mixed lengths, and a shared system prompt prefills ONCE (hit
    admissions skip the encoder entirely).

    Workload: 80% of requests use one of a few common prompts
    (Zipf-weighted "system prompts" with model-driven mixed output
    lengths via the terminator-copy task), 20% are unique — the
    million-user traffic shape ROADMAP names.

    Three INTERLEAVED legs (throttled-host discipline): the
    whole-loop GenerationServer (the r10 baseline), the dense-slot
    continuous server, and the paged server. Asserted (r13
    acceptance, not just reported): token-exact parity vs the dense
    whole-loop decode in the SAME measured legs, KV bytes per
    admitted request >= 2x lower paged vs dense-slot, zero
    steady-state compiles, and paged >= 1.5x the WHOLE-LOOP dense
    decode's tok/s. The paged-vs-dense-SLOT ratio is recorded
    unasserted: on this 2-core host, per-tick cost is LINEAR in
    static lanes, so doubling lanes at matched KV bytes roughly
    doubles tick cost and the capacity lever cannot show up as CPU
    tok/s — on the real chip the decode matmuls underutilize the MXU
    and extra lanes are nearly free, which is where requests-per-
    HBM-byte converts to throughput (PERF.md "Paged KV + prefix
    reuse" has the arithmetic).

    CPU-PINNED in code (same caveat as bench_generation). Writes
    BENCH_SELF_r13.json."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import (ContinuousGenerationServer,
                                      GenerationServer,
                                      PagedContinuousGenerationServer,
                                      apply_eos_sentinel,
                                      count_generated_tokens)
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.decode_engine import CacheConfig

    V, D, L, S, maxT = 16, 128, 2, 12, 64
    end_id = 1
    dense_slots, paged_slots = 8, 12
    rng = np.random.RandomState(7)

    def term_prompt(r, p):
        src = r.randint(3, V, (S,)).astype(np.int64)
        if p < S:
            src[p:] = end_id
        return src

    # train the terminator-copy task (d128/L2 needs the lr/steps
    # ladder from CLAUDE.md) so output lengths are model-driven; the
    # workload below must only use terminator placements the model
    # SAW here, or untrained placements decode to full buffers and
    # silently flip the length mix
    scope = Scope()
    with unique_name.guard():
        main_p, startup, loss = T.build_program(
            seq_len=S, d_model=D, n_heads=2, n_layers=L, d_inner=128,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
        with fluid.program_guard(main_p, startup):
            fluid.optimizer.Adam(learning_rate=0.002).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    for _ in range(600):
        src = np.stack([term_prompt(
            rng, int(rng.choice([2, 3, 5, S], p=[.4, .25, .15, .2])))
            for _ in range(8)])
        tgt_in = np.concatenate(
            [np.full((8, 1), 2, np.int64), src[:, :-1]], 1)
        exe.run(main_p, feed={"src_ids": src, "tgt_ids": tgt_in,
                              "label": src}, fetch_list=[loss],
                scope=scope)

    kwargs = dict(seq_len=S, max_out_len=maxT, d_model=D, n_heads=2,
                  n_layers=L, d_inner=128, vocab=V, start_id=2,
                  end_id=end_id)
    with unique_name.guard():
        inc_m, _, _, inc_buf = T.build_incremental_decode_program(
            **kwargs)
    with unique_name.guard():
        dense = T.build_decode_step_program(n_slots=dense_slots,
                                            **kwargs)
    # 12 lanes / 24 blocks: ~2.1x fewer KV bytes per admitted
    # request than dense-8, with the static-row count low enough that
    # the CPU's lane-linear tick cost doesn't eat the whole capacity
    # win (16 lanes measured 1.1x the whole-loop leg; the full
    # CPU-vs-TPU arithmetic is in PERF.md), and enough blocks that
    # the 20%-long Zipf tail paginates without preemption thrash
    cache = CacheConfig(layout="paged", block_size=16, n_blocks=24,
                        n_prompt_entries=8)
    with unique_name.guard():
        paged = T.build_decode_step_program(
            n_slots=paged_slots, state_prefix="@pgb/", cache=cache,
            **kwargs)
    # the capacity premise: 2x the lanes in FEWER KV bytes
    assert paged.kv_state_bytes() <= dense.kv_state_bytes(), (
        paged.kv_state_bytes(), dense.kv_state_bytes())

    # shared-prefix workload: 80% of traffic uses one of 4 common
    # "system prompts" (Zipf-weighted, mixed model-driven lengths),
    # 20% unique prompts
    wl_rng = np.random.RandomState(31)
    common = [term_prompt(wl_rng, p) for p in (1, 2, 3, S)]
    zipf = np.array([1.0 / (r + 1) ** 1.1 for r in range(4)])
    zipf = 0.8 * zipf / zipf.sum()
    srcs = []
    for _ in range(n_requests):
        u = wl_rng.rand()
        acc = 0.0
        row = None
        for k in range(4):
            acc += zipf[k]
            if u < acc:
                row = common[k]
                break
        if row is None:
            row = term_prompt(wl_rng, int(wl_rng.choice(
                [1, 2, 3, S], p=[.4, .25, .15, .2])))
        srcs.append(row)
    srcs = np.stack(srcs)
    ref, = exe.run(inc_m, feed={"src_ids": srcs},
                   fetch_list=[inc_buf], scope=scope)
    want = apply_eos_sentinel(np.asarray(ref), end_id)
    lens = count_generated_tokens(want, end_id)
    total_tokens = int(lens.sum())

    def run_leg(make_server):
        srv = make_server()
        try:
            t0 = time.perf_counter()
            replies = [srv.submit(s) for s in srcs]
            outs = [rep.result(600.0) for rep in replies]
            wall = time.perf_counter() - t0
            st = srv.stats()
        finally:
            srv.close()
        # parity IN the measured leg: a fast leg that decoded wrong
        # tokens would be meaningless
        assert all(np.array_equal(np.asarray(o), want[i])
                   for i, o in enumerate(outs)), \
            "token parity vs the whole-loop decode failed"
        return {"wall_s": wall, "tok_s": total_tokens / wall,
                "stats": st}

    def whole_loop_leg():
        srv = GenerationServer(
            inc_m, inc_buf, executor=exe, scope=scope, end_id=end_id,
            max_batch_size=dense_slots, max_wait_ms=2.0)
        try:
            t0 = time.perf_counter()
            replies = [srv.submit({"src_ids": s[None]}) for s in srcs]
            outs = [apply_eos_sentinel(
                np.asarray(rep.result(600.0)[0]), end_id)[0]
                for rep in replies]
            wall = time.perf_counter() - t0
            st = srv.stats()
        finally:
            srv.close()
        assert all(np.array_equal(o, want[i])
                   for i, o in enumerate(outs)), \
            "whole-loop leg parity failed"
        return {"wall_s": wall, "tok_s": total_tokens / wall,
                "stats": st}

    def dense_leg():
        return run_leg(lambda: ContinuousGenerationServer(
            dense, executor=exe, scope=scope, steps_per_tick=8))

    def paged_leg():
        return run_leg(lambda: PagedContinuousGenerationServer(
            paged, executor=exe, scope=scope, steps_per_tick=8))

    whole_loop_leg()  # warm all three serve sets (all compiles here)
    dense_leg()
    paged_leg()
    compiles_before = exe.compile_count
    # INTERLEAVED best-of-3 (r10 discipline, harness.interleave_
    # rounds): adjacent legs share this host's CPU-share throttle
    # windows
    rounds = _harness.interleave_rounds(
        [("whole", whole_loop_leg), ("dense", dense_leg),
         ("paged", paged_leg)], rounds=3)
    steady_compiles = exe.compile_count - compiles_before
    assert steady_compiles == 0, (
        f"steady-state legs compiled {steady_compiles}")
    wbest = _harness.best_leg(rounds, "whole")
    dbest = _harness.best_leg(rounds, "dense")
    pbest = _harness.best_leg(rounds, "paged")
    # the ASSERTED ratio is the best PAIRED one (the r10 guard-test
    # method, harness.paired_ratio_max): adjacent legs of a round
    # share this host's throttle window, while ratios of global bests
    # can pit one leg's lucky window against another's throttled one
    speedup_vs_whole = _harness.paired_ratio_max(rounds, "paged",
                                                 "whole")
    ratio_vs_dense_slot = _harness.paired_ratio_max(rounds, "paged",
                                                    "dense")
    triples = [(r["whole"], r["dense"], r["paged"]) for r in rounds]
    triple_toks = [(round(w["tok_s"]), round(d["tok_s"]),
                    round(p["tok_s"])) for w, d, p in triples]
    assert speedup_vs_whole >= 1.5, (
        f"paged tok/s only {speedup_vs_whole:.2f}x the whole-loop "
        f"decode on the shared-prefix workload (paired triples: "
        f"{triple_toks})")

    dense_kv_req = dense.kv_state_bytes() / dense_slots
    paged_kv_req = paged.kv_state_bytes() / paged_slots
    kv_ratio = dense_kv_req / paged_kv_req
    assert kv_ratio >= 2.0, (
        f"KV bytes per admitted request only {kv_ratio:.2f}x lower")
    pst = pbest["stats"]
    bp = pst["block_pool"]
    hit_rate = bp["prefix_hits"] / max(
        1, bp["prefix_hits"] + bp["prefix_misses"] + bp["cow_copies"])
    result = {
        "metric": "paged_kv_tokens_per_sec_shared_prefix",
        "value": round(pbest["tok_s"], 1),
        "unit": "tokens/sec",
        "whole_loop_tok_s": round(wbest["tok_s"], 1),
        "dense_slot_tok_s": round(dbest["tok_s"], 1),
        "paged_tok_s": round(pbest["tok_s"], 1),
        "speedup_vs_whole_loop": round(speedup_vs_whole, 2),
        "ratio_vs_dense_slot": round(ratio_vs_dense_slot, 2),
        "ratio_vs_dense_slot_note": (
            "unasserted: CPU tick cost is linear in static lanes, so "
            "2x lanes at matched KV bytes ~2x the tick — the "
            "capacity lever converts to tok/s only where lanes are "
            "near-free (real-chip MXU; PERF.md)"),
        "triple_tok_s": [[round(w["tok_s"], 1), round(d["tok_s"], 1),
                          round(p["tok_s"], 1)]
                         for w, d, p in triples],
        "token_parity_vs_whole_loop": True,  # asserted per leg
        "steady_state_compiles": int(steady_compiles),
        "kv_bytes_per_request": {
            "dense": int(dense_kv_req), "paged": int(paged_kv_req),
            "ratio": round(kv_ratio, 2)},
        "requests_per_kv_byte": {
            "dense": dense_slots / dense.kv_state_bytes(),
            "paged": paged_slots / paged.kv_state_bytes()},
        "prefix_hit_rate": round(hit_rate, 3),
        "block_pool": bp,
        "slots": {"dense": dense_slots, "paged": paged_slots},
        "cache": {"block_size": cache.block_size,
                  "n_blocks": cache.n_blocks,
                  "n_prompt_entries": cache.n_prompt_entries},
        "workload": "80% shared system prompts (Zipf over 4), "
                    "20% unique; terminator-copy mixed lengths",
        "len_histogram": {int(k): int(v) for k, v in
                          zip(*np.unique(lens, return_counts=True))},
        "n_requests": n_requests,
        "total_tokens": total_tokens,
        "model": f"transformer d{D} L{L} S{S} maxT{maxT}",
        "best_of": 3,
    }
    return _write_bench_self("BENCH_SELF_r13.json", result,
                             stats_json_dict=pst)


def bench_multiturn(n_conversations=12, n_turns=3):
    """Multi-turn chat sessions over the radix block-prefix tree
    (ISSUE 16): each conversation submits a prompt, then extends the
    RETAINED decoded history turn by turn (``submit(session_id=,
    extend_tokens=)``). The radix leg resumes from the longest
    shared block prefix — only the divergent tail is chunk-
    prefilled; the re-prefill leg (``radix_reuse=False``, same
    programs, same session API) replays every turn's FULL history
    into fresh blocks, which is what every turn costs without the
    tree.

    Workload: conversations share prompts Zipf-weighted over 4
    "personas" (greedy decode is deterministic, so same-prompt
    conversations share turn chains CROSS-session through the tree,
    not just within one session). Each turn's extension ends in the
    terminator, so histories grow by a bounded amount and the turn
    structure is model-independent.

    Measured per interleaved round (best-of-3, throttled-host
    discipline): prefilled KV bytes per turn (the radix win:
    ``radix_hit_blocks`` pages are NOT re-computed), TTFT
    percentiles (the replay leg spends P forcing ticks before its
    first new token; radix spends P - h*BS), the prefix hit-DEPTH
    histogram, and BYTE-EXACT token parity radix-vs-replay on every
    turn of every conversation (the replay leg IS the cold decode).
    Zero steady-state compiles across the measured rounds.

    CPU-PINNED by design (same reasoning as bench_generation).
    Writes BENCH_SELF_r16.json."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.decode_engine import CacheConfig

    V, D, H, L, S, maxT = 16, 64, 2, 1, 10, 48
    end_id = 1
    BS, NB, E, n_slots = 4, 72, 6, 4
    rng = np.random.RandomState(7)

    def term_prompt(r, p):
        src = r.randint(3, V, (S,)).astype(np.int64)
        if p < S:
            src[p:] = end_id
        return src

    # terminator-copy training (d64 needs the CLAUDE.md lr/steps
    # ladder) — turn-1 lengths are model-driven copies
    fluid.seed(0)
    scope = Scope()
    with unique_name.guard():
        main_p, startup, loss = T.build_program(
            seq_len=S, d_model=D, n_heads=H, n_layers=L, d_inner=128,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
        with fluid.program_guard(main_p, startup):
            fluid.optimizer.Adam(learning_rate=0.005).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    for _ in range(400):
        src = np.stack([term_prompt(
            rng, int(rng.choice([5, 6, 7, 8], p=[.25, .25, .25, .25])))
            for _ in range(8)])
        tgt_in = np.concatenate(
            [np.full((8, 1), 2, np.int64), src[:, :-1]], 1)
        exe.run(main_p, feed={"src_ids": src, "tgt_ids": tgt_in,
                              "label": src}, fetch_list=[loss],
                scope=scope)

    kwargs = dict(seq_len=S, max_out_len=maxT, d_model=D, n_heads=H,
                  n_layers=L, d_inner=128, vocab=V, start_id=2,
                  end_id=end_id)
    cache = CacheConfig(layout="paged", block_size=BS, n_blocks=NB,
                        n_prompt_entries=E)
    with unique_name.guard():
        paged = T.build_decode_step_program(
            n_slots=n_slots, state_prefix="@mt/", cache=cache,
            **kwargs)

    # Zipf persona prompts (all conversations draw from 4 personas:
    # entries stay bounded by the persona count, since same-prompt
    # sessions PIN one shared refcounted entry)
    wl = np.random.RandomState(31)
    personas = [term_prompt(wl, p) for p in (5, 6, 7, 8)]
    zipf = np.array([1.0 / (r + 1) ** 1.1 for r in range(4)])
    zipf = zipf / zipf.sum()
    conv_prompt = [personas[int(wl.choice(4, p=zipf))]
                   for _ in range(n_conversations)]
    # per-turn extensions, terminator-closed (bounded histories) and
    # drawn from a small shared pool so same-persona conversations
    # extend identically and share turn-2+ chains cross-session
    ext_pool = [[4, 9, end_id], [6, 3, end_id], [11, 5, end_id]]
    conv_ext = [[ext_pool[int(wl.choice(3))]
                 for _ in range(n_turns - 1)]
                for _ in range(n_conversations)]

    ptok_bytes = L * 2 * H * (D // H) * 4  # self-KV bytes per token

    def leg(radix):
        srv = PagedContinuousGenerationServer(
            paged, executor=exe, scope=scope, steps_per_tick=4,
            radix_reuse=radix)
        turns = [[] for _ in range(n_conversations)]
        positions = 0  # total (history + emitted) positions, for
        #                the prefilled-KV accounting below
        try:
            t0 = time.perf_counter()
            for t in range(n_turns):
                reps = []
                for c in range(n_conversations):
                    if t == 0:
                        reps.append(srv.submit(
                            conv_prompt[c], session_id=c))
                    else:
                        reps.append(srv.submit(
                            conv_prompt[c], session_id=c,
                            extend_tokens=conv_ext[c][t - 1]))
                for c, rep in enumerate(reps):
                    out = np.asarray(rep.result(600.0))
                    turns[c].append(out)
                    positions += int((out != -1).sum())
            wall = time.perf_counter() - t0
            st = srv.stats()
            pst = srv.pool_stats()
            hd = srv._hit_depth
            hit_hist = {str(b): int(n) for b, n in
                        zip(list(hd.buckets) + ["inf"], hd._counts)}
            for c in range(n_conversations):
                srv.close_session(c)
        finally:
            srv.close()
        # prefilled-KV accounting: every (history + emitted) position
        # was WRITTEN except the radix_hit_blocks pages mapped
        # read-only from the tree
        kv_written = (positions - BS * pst["radix_hit_blocks"]) \
            * ptok_bytes
        return {"wall_s": wall, "turns": turns,
                "kv_bytes_per_turn":
                    kv_written / (n_conversations * n_turns),
                "ttft_p50_ms": st["ttft_ms"]["p50"],
                "ttft_p99_ms": st["ttft_ms"]["p99"],
                "hit_depth_histogram": hit_hist,
                "pool": pst, "stats": st}

    def radix_leg():
        return leg(True)

    def replay_leg():
        return leg(False)

    replay_leg()   # warm both serve-tier sets (all compiles here)
    radix_leg()
    compiles_before = exe.compile_count
    rounds = _harness.interleave_rounds(
        [("replay", replay_leg), ("radix", radix_leg)], rounds=3)
    steady_compiles = exe.compile_count - compiles_before
    assert steady_compiles == 0, (
        f"steady-state legs compiled {steady_compiles}")
    # BYTE-EXACT parity on every turn of every conversation, per
    # round: the replay leg is the cold full-history decode
    for r in rounds:
        for c in range(n_conversations):
            for t in range(n_turns):
                assert np.array_equal(r["radix"]["turns"][c][t],
                                      r["replay"]["turns"][c][t]), (
                    f"conv {c} turn {t}: radix decode diverged from "
                    f"cold re-prefill")
    rbest = _harness.best_leg(rounds, "radix")
    pbest = _harness.best_leg(rounds, "replay")
    # paired ratios (the r10 discipline): KV-per-turn is
    # deterministic, TTFT rides the throttle windows
    kv_ratio = min(r["radix"]["kv_bytes_per_turn"]
                   / r["replay"]["kv_bytes_per_turn"]
                   for r in rounds)
    ttft_ratio = min(r["radix"]["ttft_p50_ms"]
                     / r["replay"]["ttft_p50_ms"]
                     for r in rounds)
    assert kv_ratio < 0.8, (
        f"radix leg prefilled {kv_ratio:.2f}x the replay leg's KV "
        f"bytes per turn — the tree is not reusing blocks")
    assert ttft_ratio < 1.0, (
        f"radix TTFT p50 {ttft_ratio:.2f}x replay — resume did not "
        f"shorten time-to-first-token in any paired round")
    result = {
        "metric": "multiturn_kv_bytes_per_turn_radix",
        "value": round(rbest["kv_bytes_per_turn"], 1),
        "unit": "bytes/turn",
        "replay_kv_bytes_per_turn":
            round(pbest["kv_bytes_per_turn"], 1),
        "kv_per_turn_ratio": round(kv_ratio, 3),
        "ttft_p50_ms": {"radix": round(rbest["ttft_p50_ms"], 2),
                        "replay": round(pbest["ttft_p50_ms"], 2),
                        "paired_ratio": round(ttft_ratio, 3)},
        "ttft_p99_ms": {"radix": round(rbest["ttft_p99_ms"], 2),
                        "replay": round(pbest["ttft_p99_ms"], 2)},
        "token_parity_radix_vs_replay": True,  # asserted per round
        "steady_state_compiles": int(steady_compiles),
        "hit_depth_histogram": rbest["hit_depth_histogram"],
        "radix_pool": {k: rbest["pool"][k] for k in
                       ("radix_nodes", "radix_hit_blocks",
                        "radix_inserts", "radix_adoptions",
                        "radix_evicted_blocks", "radix_admissions",
                        "shared_blocks")},
        "workload": f"{n_conversations} conversations x {n_turns} "
                    f"turns, Zipf over 4 personas, terminator-"
                    f"closed extensions",
        "cache": {"block_size": BS, "n_blocks": NB,
                  "n_prompt_entries": E},
        "model": f"transformer d{D} L{L} S{S} maxT{maxT}",
        "best_of": 3,
    }
    return _write_bench_self("BENCH_SELF_r16.json", result,
                             stats_json_dict=rbest["stats"])


def bench_prefill(n_longs=3, shorts_per_long=6):
    """Chunked prefill vs monolithic admission (ISSUE 17): the
    TTFT-vs-ITL coupling. Today a miss-tier admission runs the FULL
    encoder prefill inside the serve program, so one 2k-token
    arrival stalls every live lane's decode tick; chunked prefill
    (Sarathi-style, C prompt tokens per tick through the
    ``("chunked", p)`` phase programs) bounds the stall at one
    chunk.

    ONE bundle (seq_len=2048, chunk_tokens=256 -> 8 chunks x 4
    phases), TWO legs over the same executor/scope:

    * ``chunked`` — the default two-tier schedule: chunk ticks
      interleave with decode bursts;
    * ``mono``    — ``chunked_prefill=False``: the same programs
      minus the chunk tier; cold admissions prefill monolithically.

    Each leg measures two windows (stats(reset=True) between them):
    a LONG-ONLY window (two cold 2k prompts back-to-back -> server
    ttft_ms is long-only by construction) and the INTERLEAVED window
    — hit-tier shorts stream while a cold 2k prompt arrives; each
    short's inter-token latency is client-side wall / tokens, so
    the monolithic stall lands in the short ITL p99 directly.

    Discipline (PERF.md, throttled 2-core host): both legs warmed
    once (all compiles), then interleave_rounds best-of-3 — paired
    per-round ITL ratios only; BYTE-EXACT token parity chunked vs
    mono on every request of every round (phase-major chunking is
    exact, not approximate); zero steady-state compiles across the
    measured rounds; executable count bounded by the bundle's serve
    programs (#bucket tiers + #chunk phases) + slot-state init.

    ``radix_reuse=False`` on BOTH legs: identical repeat shorts
    would otherwise resume from the radix tree (near-free decode)
    and thin the very decode traffic the stall is measured against.

    CPU-PINNED by design (the stall is host-observable wall time;
    same reasoning as bench_generation). Writes BENCH_SELF_r18.json.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import PagedContinuousGenerationServer
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.decode_engine import CacheConfig

    V, D, H, L, S, maxT = 16, 32, 2, 1, 2048, 16
    BS, NB, E, n_slots, C = 8, 24, 6, 4, 256
    NC = (S + C - 1) // C
    NPH = 2 * L + 2

    # untrained, seed-pinned: greedy decode is deterministic either
    # way, and parity/latency need no trained weights at S=2048
    fluid.seed(0)
    scope = Scope()
    with unique_name.guard():
        _, startup, _ = T.build_program(
            seq_len=S, d_model=D, n_heads=H, n_layers=L, d_inner=64,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    with unique_name.guard():
        bundle = T.build_decode_step_program(
            n_slots=n_slots, admit_buckets=[1], state_prefix="@pf/",
            seq_len=S, max_out_len=maxT, d_model=D, n_heads=H,
            n_layers=L, d_inner=64, vocab=V, start_id=2, end_id=1,
            cache=CacheConfig(layout="paged", block_size=BS,
                              n_blocks=NB, n_prompt_entries=E,
                              chunk_tokens=C))
    compiles0 = exe.compile_count

    # fixed prompt sets, identical across legs and rounds: 2 shorts
    # (hit tier after the warm pass) + 5 distinct cold 2k longs
    # (2 for the long-only TTFT window, n_longs for the interleaved
    # one). E=6 entries: the shorts stay MRU through the interleaved
    # stream, so entry eviction only ever recycles a long's entry.
    rng = np.random.RandomState(11)
    shorts = [rng.randint(3, V, (1, S)).astype(np.int64)
              for _ in range(2)]
    longs = [rng.randint(3, V, (1, S)).astype(np.int64)
             for _ in range(2 + n_longs)]

    def _p99(vals):
        srt = sorted(vals)
        return srt[max(0, int(np.ceil(0.99 * len(srt))) - 1)]

    def leg(chunked):
        srv = PagedContinuousGenerationServer(
            bundle, executor=exe, scope=scope, steps_per_tick=4,
            chunked_prefill=chunked, radix_reuse=False)
        toks = []
        try:
            for p in shorts:  # warm the hit tier (cold exactly once)
                toks.append(np.asarray(srv.submit(p).result(600.0)))
            srv.stats(reset=True)
            # LONG-ONLY window: server ttft_ms sees only cold 2k
            # prompts here
            long_walls = []
            for p in longs[:2]:
                t0 = time.perf_counter()
                toks.append(np.asarray(srv.submit(p).result(600.0)))
                long_walls.append((time.perf_counter() - t0) * 1e3)
            st_long = srv.stats(reset=True)
            # INTERLEAVED window: shorts stream while a cold 2k
            # prompt chunks in (or stalls the loop, mono leg)
            itl = []
            for k in range(n_longs):
                rep = srv.submit(longs[2 + k])
                for j in range(shorts_per_long):
                    t0 = time.perf_counter()
                    out = np.asarray(
                        srv.submit(shorts[j % 2]).result(600.0))
                    ntok = max(int((out != -1).sum()), 1)
                    itl.append(
                        (time.perf_counter() - t0) * 1e3 / ntok)
                    toks.append(out)
                toks.append(np.asarray(rep.result(600.0)))
            st = srv.stats()
            pst = srv.pool_stats()
        finally:
            srv.close()
        return {"wall_s": sum(long_walls) / 1e3, "toks": toks,
                "itl_p99_ms": _p99(itl), "itl_ms": itl,
                "long_ttft_ms": st_long["ttft_ms"],
                "long_wall_p50_ms": sorted(long_walls)[
                    len(long_walls) // 2],
                "stats": st, "pool": pst}

    def chunked_leg():
        return leg(True)

    def mono_leg():
        return leg(False)

    mono_leg()     # warm both serve-tier sets (all compiles here)
    chunked_leg()
    warm_compiles = exe.compile_count - compiles0
    # #bucket tiers + #chunk phases (+ slot-state init/reset bits):
    # the whole point of the two-tier schedule is that chunking adds
    # NPH programs, not NC x NPH
    exe_bound = len(bundle.serves) + 4
    assert warm_compiles <= exe_bound, (
        f"warm legs compiled {warm_compiles} executables — bound is "
        f"{len(bundle.serves)} serve programs + 4 init")
    compiles_before = exe.compile_count
    rounds = _harness.interleave_rounds(
        [("mono", mono_leg), ("chunked", chunked_leg)], rounds=3)
    steady_compiles = exe.compile_count - compiles_before
    assert steady_compiles == 0, (
        f"steady-state legs compiled {steady_compiles}")
    # BYTE-EXACT parity on every request of every round: phase-major
    # chunking must not change one served token
    for r in rounds:
        assert len(r["chunked"]["toks"]) == len(r["mono"]["toks"])
        for i, (a, b) in enumerate(zip(r["chunked"]["toks"],
                                       r["mono"]["toks"])):
            assert np.array_equal(a, b), (
                f"request {i}: chunked decode diverged from "
                f"monolithic admission")
    # paired per-round ITL ratios (the r10 discipline)
    ratios = [r["chunked"]["itl_p99_ms"] / r["mono"]["itl_p99_ms"]
              for r in rounds]
    med_ratio = sorted(ratios)[len(ratios) // 2]
    assert min(ratios) < 1.0 and med_ratio < 1.0, (
        f"short-request ITL p99 paired ratios {ratios}: chunked "
        f"prefill did not beat the monolithic stall")
    cbest = _harness.best_leg(rounds, "chunked",
                              key=lambda r: r["itl_p99_ms"])
    mbest = _harness.best_leg(rounds, "mono",
                              key=lambda r: r["itl_p99_ms"])
    result = {
        "metric": "prefill_short_itl_p99_chunked",
        "value": round(cbest["itl_p99_ms"], 2),
        "unit": "ms/token",
        "mono_itl_p99_ms": round(mbest["itl_p99_ms"], 2),
        "itl_p99_paired_ratios": [round(r, 3) for r in ratios],
        "itl_p99_ratio_median": round(med_ratio, 3),
        "long_ttft_ms": {
            "chunked": cbest["long_ttft_ms"],
            "mono": mbest["long_ttft_ms"],
        },
        "long_wall_p50_ms": {
            "chunked": round(cbest["long_wall_p50_ms"], 1),
            "mono": round(mbest["long_wall_p50_ms"], 1),
        },
        "token_parity_chunked_vs_mono": True,  # asserted per round
        "steady_state_compiles": int(steady_compiles),
        "warm_compiles": int(warm_compiles),
        "executable_bound": int(exe_bound),
        "chunk": {
            "chunk_tokens": C, "n_chunks": NC, "phases": NPH,
            "chunk_jobs": cbest["pool"]["chunk_jobs"],
            "chunk_ticks": cbest["pool"]["chunk_ticks"],
        },
        "workload": f"{shorts_per_long} hit-tier shorts streamed per "
                    f"cold {S}-token arrival x {n_longs} arrivals; "
                    f"2-long TTFT window per leg",
        "cache": {"block_size": BS, "n_blocks": NB,
                  "n_prompt_entries": E},
        "model": f"transformer d{D} L{L} S{S} maxT{maxT}",
        "best_of": 3,
    }
    return _write_bench_self("BENCH_SELF_r18.json", result,
                             stats_json_dict=cbest["stats"])


def bench_sharded(n_requests=120):
    """Sharded serving: tensor-parallel decode + data-parallel lanes
    on the virtual 8-device mesh (models/decode_engine.ShardingConfig
    + core/sharding_plan.py + runtime/placement.py).

    CPU-ONLY: XLA fixes the host-platform device count at backend
    init, so the measurement runs in a CHILD process whose
    environment carries JAX_PLATFORMS=cpu and
    ``--xla_force_host_platform_device_count=8``; it never needs the
    chip, and says which platform it found on stderr. The child
    writes BENCH_SELF_r17.json and prints the record; this parent
    relays it.

    Three INTERLEAVED legs (throttled-host discipline), all on the
    paged serve path with identical geometry and token-exact parity
    vs the whole-loop decode asserted per leg:

      single — the r13 paged server, one device;
      tp2    — the same bundle tensor-parallel over devices [0,1]
               (head-sharded KV pool, row/column-parallel
               projections, vocab-sharded logits);
      tp2+dp — TWO tp=2 models on disjoint slices [0,1] / [2,3],
               traffic split between them (the runtime placement
               carve, minus the fc lanes the tests cover).

    The ASSERTED wins are per-device KV bytes (pool shard bytes
    exactly 1/tp, >= 1.8x smaller) and the dp-lane AGGREGATE over one
    tp model; the tp2-vs-single tok/s ratio is recorded UNASSERTED
    with the CPU caveat: on this 2-core host every psum is a
    same-core memcpy + sync that costs a visible slice of the tick,
    while on the real chip the decode matmuls underutilize the MXU
    and the collectives ride the ICI (PERF.md "Sharded serving" has
    the arithmetic)."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count"
                            "=8").strip())
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "_sharded_child",
         str(n_requests)],
        env=env, capture_output=True, text=True, timeout=3600)
    sys.stderr.write(proc.stderr[-4000:])
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded child failed (rc {proc.returncode}); stderr "
            f"tail above")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _bench_sharded_impl(n_requests):
    """The child-process body of bench_sharded (8 virtual devices)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    assert jax.device_count() >= 8, jax.device_count()
    print(f"# sharded child: platform={jax.devices()[0].platform} "
          f"devices={jax.device_count()} (CPU-only config: counts "
          f"and CPU-host times, no device metric)", file=sys.stderr)

    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import (PagedContinuousGenerationServer,
                                      apply_eos_sentinel,
                                      count_generated_tokens)
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.decode_engine import (CacheConfig,
                                                 ShardingConfig)

    V, D, H, L, S, maxT = 16, 64, 4, 1, 12, 64
    end_id = 1
    n_slots = 8
    rng = np.random.RandomState(7)

    def term_prompt(r, p):
        src = r.randint(3, V, (S,)).astype(np.int64)
        if p < S:
            src[p:] = end_id
        return src

    scope = Scope()
    with unique_name.guard():
        main_p, startup, loss = T.build_program(
            seq_len=S, d_model=D, n_heads=H, n_layers=L, d_inner=128,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
        with fluid.program_guard(main_p, startup):
            fluid.optimizer.Adam(learning_rate=0.005).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    for _ in range(400):
        src = np.stack([term_prompt(
            rng, int(rng.choice([2, 3, 5, S], p=[.4, .25, .15, .2])))
            for _ in range(8)])
        tgt_in = np.concatenate(
            [np.full((8, 1), 2, np.int64), src[:, :-1]], 1)
        exe.run(main_p, feed={"src_ids": src, "tgt_ids": tgt_in,
                              "label": src}, fetch_list=[loss],
                scope=scope)

    kwargs = dict(seq_len=S, max_out_len=maxT, d_model=D, n_heads=H,
                  n_layers=L, d_inner=128, vocab=V, start_id=2,
                  end_id=end_id)
    cache = CacheConfig(layout="paged", block_size=16, n_blocks=24,
                        n_prompt_entries=8)
    with unique_name.guard():
        inc_m, _, _, inc_buf = T.build_incremental_decode_program(
            **kwargs)
    with unique_name.guard():
        b_single = T.build_decode_step_program(
            n_slots=n_slots, state_prefix="@sg/", cache=cache,
            **kwargs)
    with unique_name.guard():
        b_tp = T.build_decode_step_program(
            n_slots=n_slots, state_prefix="@tp/", cache=cache,
            sharding=ShardingConfig(tp=2), **kwargs)
    with unique_name.guard():
        b_tp2 = T.build_decode_step_program(
            n_slots=n_slots, state_prefix="@tq/", cache=cache,
            sharding=ShardingConfig(tp=2), **kwargs)

    # shared-prefix workload (the r13 shape: 80% Zipf over 4 system
    # prompts, 20% unique, model-driven mixed lengths)
    wl_rng = np.random.RandomState(31)
    common = [term_prompt(wl_rng, p) for p in (2, 3, 5, S)]
    srcs = []
    for _ in range(n_requests):
        u = wl_rng.rand()
        if u < 0.8:
            zipf = np.array([1.0 / (r + 1) ** 1.1 for r in range(4)])
            zipf = zipf / zipf.sum()
            srcs.append(common[int(wl_rng.choice(4, p=zipf))])
        else:
            srcs.append(term_prompt(wl_rng, int(wl_rng.choice(
                [2, 3, 5, S], p=[.4, .25, .15, .2]))))
    srcs = np.stack(srcs)
    ref, = exe.run(inc_m, feed={"src_ids": srcs},
                   fetch_list=[inc_buf], scope=scope)
    want = apply_eos_sentinel(np.asarray(ref), end_id)
    total_tokens = int(count_generated_tokens(want, end_id).sum())

    def fork_scope():
        fork = Scope()
        for name in list(scope._vars):
            val = scope._get(name)
            fork._set(name, np.asarray(val)
                      if hasattr(val, "shape") else val)
        return fork

    def run_one(bundle, devices, prompts, expect):
        srv = PagedContinuousGenerationServer(
            bundle, executor=exe, scope=fork_scope(),
            steps_per_tick=8, mesh_devices=devices)
        try:
            t0 = time.perf_counter()
            replies = [srv.submit(s) for s in prompts]
            outs = [rep.result(600.0) for rep in replies]
            wall = time.perf_counter() - t0
            st = srv.stats()
        finally:
            srv.close()
        assert all(np.array_equal(np.asarray(o), expect[i])
                   for i, o in enumerate(outs)), \
            "token parity vs the whole-loop decode failed"
        return wall, st

    def single_leg():
        wall, st = run_one(b_single, None, srcs, want)
        return {"wall_s": wall, "tok_s": total_tokens / wall,
                "stats": st}

    def tp2_leg():
        wall, st = run_one(b_tp, jax.devices()[:2], srcs, want)
        return {"wall_s": wall, "tok_s": total_tokens / wall,
                "stats": st}

    def tp2dp_leg():
        # two tp=2 models on disjoint slices, traffic split: the
        # dp-lane aggregate (run concurrently via the servers' own
        # scheduler threads)
        import threading

        half = len(srcs) // 2
        walls, stats, errs = [None, None], [None, None], []

        def lane(i, bundle, devices, prompts, expect):
            try:
                walls[i], stats[i] = run_one(bundle, devices,
                                             prompts, expect)
            except BaseException as e:  # surfaced below
                errs.append(e)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=lane, args=(
                0, b_tp, jax.devices()[:2], srcs[:half],
                want[:half])),
            threading.Thread(target=lane, args=(
                1, b_tp2, jax.devices()[2:4], srcs[half:],
                want[half:]))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errs:
            raise errs[0]
        # the leg's headline is the TWO-lane aggregate, so the pool
        # accounting must cover both lanes (lane 0's stats alone
        # described half the traffic); telemetry keeps lane 0's full
        # stats dict as the per-lane sample
        pools = [st["block_pool"] for st in stats]
        return {"wall_s": wall, "tok_s": total_tokens / wall,
                "stats": stats[0],
                "pool_sum": {k: sum(p[k] for p in pools)
                             for k in ("prefix_hits", "prefix_misses",
                                       "cow_copies")}}

    # per-device KV bytes: the placed pool's addressable shard is
    # EXACTLY total/tp (heads divide evenly)
    probe = PagedContinuousGenerationServer(
        b_tp, executor=exe, scope=fork_scope(),
        mesh_devices=jax.devices()[:2], start=False)
    pool = probe.scope._get("@tp/self_k0@POOL")
    per_dev = int(pool.addressable_shards[0].data.nbytes)
    full = int(np.prod(pool.shape)) * pool.dtype.itemsize
    probe.close()
    kv_ratio = full / per_dev
    assert kv_ratio >= 1.8, (full, per_dev)

    single_leg()
    tp2_leg()
    tp2dp_leg()  # warm (all compiles land here)
    compiles_before = exe.compile_count
    rounds = _harness.interleave_rounds(
        [("single", single_leg), ("tp2", tp2_leg),
         ("tp2dp", tp2dp_leg)], rounds=3)
    steady_compiles = exe.compile_count - compiles_before
    assert steady_compiles == 0, steady_compiles
    sbest = _harness.best_leg(rounds, "single")
    tbest = _harness.best_leg(rounds, "tp2")
    dbest = _harness.best_leg(rounds, "tp2dp")
    dp_over_tp2 = _harness.paired_ratio_max(rounds, "tp2dp", "tp2")
    tp2_over_single = _harness.paired_ratio_max(rounds, "tp2",
                                                "single")
    # BOTH throughput ratios are recorded UNASSERTED beyond sanity
    # floors: all 8 virtual devices share 2 throttled cores, so the
    # dp lanes compete for the same cycles (paired dp/tp2 measured
    # 0.76x-1.52x across runs — unresolvable, the PERF.md r12
    # discipline) and tp trades latency for per-device bytes. The
    # HARD assertions of this bench are the layout/compile
    # invariants: per-device KV exactly 1/tp, parity per leg, zero
    # steady-state compiles. On disjoint REAL chips dp lanes scale
    # by construction (PERF.md "Sharded serving").
    assert dp_over_tp2 >= 0.5, (
        f"dp aggregate collapsed to {dp_over_tp2:.2f}x one tp model")
    bp = dbest["pool_sum"]  # both dp lanes' pools (the aggregate leg)
    result = {
        "metric": "sharded_dp_aggregate_tokens_per_sec",
        "value": round(dbest["tok_s"], 1),
        "unit": "tokens/sec",
        "single_tok_s": round(sbest["tok_s"], 1),
        "tp2_tok_s": round(tbest["tok_s"], 1),
        "tp2dp_tok_s": round(dbest["tok_s"], 1),
        "dp_aggregate_over_tp2": round(dp_over_tp2, 2),
        "dp_aggregate_note": (
            "unasserted beyond a 0.5 sanity floor: the dp lanes "
            "share this host's 2 cores, paired ratios swing "
            "0.76-1.52x across runs (unresolvable); on disjoint "
            "real chips lanes scale by construction"),
        "tp2_over_single": round(tp2_over_single, 2),
        "tp2_over_single_note": (
            "unasserted: on this 2-core host every per-tick psum is "
            "a same-core copy+sync, so tp trades latency for the "
            "per-device KV bytes; the real-chip tok/s arithmetic is "
            "argued in PERF.md 'Sharded serving'"),
        "per_device_kv": {"full_pool_bytes": full,
                          "per_device_bytes": per_dev,
                          "ratio": round(kv_ratio, 2)},
        "token_parity_vs_whole_loop": True,  # asserted per leg
        "steady_state_compiles": int(steady_compiles),
        "triple_tok_s": [[round(r["single"]["tok_s"], 1),
                          round(r["tp2"]["tok_s"], 1),
                          round(r["tp2dp"]["tok_s"], 1)]
                         for r in rounds],
        "mesh": {"devices": 8, "tp": 2, "tp_models": 2,
                 "slices": [[0, 1], [2, 3]]},
        "cache": {"block_size": cache.block_size,
                  "n_blocks": cache.n_blocks,
                  "n_prompt_entries": cache.n_prompt_entries},
        "workload": "80% shared system prompts (Zipf over 4), "
                    "20% unique; terminator-copy mixed lengths",
        "n_requests": n_requests,
        "total_tokens": total_tokens,
        "model": f"transformer d{D} L{L} S{S} maxT{maxT}",
        "best_of": 3,
        "prefix_hit_rate": round(
            bp["prefix_hits"] / max(1, bp["prefix_hits"]
                                    + bp["prefix_misses"]
                                    + bp["cow_copies"]), 3),
    }
    return _write_bench_self("BENCH_SELF_r17.json", result,
                             stats_json_dict=dbest["stats"])


def bench_speculative(n_requests=96, spec_k=3):
    """Speculative draft-and-verify decoding vs the plain decode
    burst and the whole-loop server (models/decode_engine.py
    DraftConfig; BENCH_SELF_r14.json).

    Workload: the terminator-copy task where BOTH the d128/L2 target
    and the d32/L1 draft learn near-deterministic copying, so the
    draft's k proposals mostly match the target's greedy stream —
    the high-acceptance regime speculative decoding amortizes: per
    device tick, k tiny draft steps + ONE batched (k+1)-query target
    step emit up to k+1 tokens where the plain burst's tick emits 1.
    Greedy acceptance is TOKEN-EXACT vs the whole-loop decode, so
    every measured leg asserts byte parity (a fast leg with wrong
    tokens would be meaningless).

    Three INTERLEAVED legs per triple (r10/r13 throttled-host
    discipline), best PAIRED ratios asserted: speculative > 1x the
    plain burst's tok/s, zero steady-state compiles. Draft-vs-target
    step accounting (the real cost model: CPU time is ~linear in
    FLOPs, so the win is k*draft_cost + verify_cost vs
    tokens-per-tick — PERF.md "Speculative decoding" has the
    arithmetic for this host and the real chip). CPU-PINNED like
    bench_generation; fail-fast exit 3 inherited from main()."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import (ContinuousGenerationServer,
                                      GenerationServer,
                                      apply_eos_sentinel,
                                      count_generated_tokens)
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.decode_engine import DraftConfig

    V, D, L, S, maxT = 16, 128, 2, 12, 64
    DD, DL = 64, 1   # draft dims: ~8x fewer decode FLOPs/step — a
    #                  d32 draft measured acceptance 0.69/accepted
    #                  len 2.81, UNDER the 2.54 tick-cost threshold;
    #                  d64 hits 0.89/3.42 and clears it
    n_slots = 8
    end_id = 1
    rng = np.random.RandomState(7)

    # FIXED prompt pool (the ISSUE's "repeated-suffix mix"): 8
    # memorizable sequences with varied planted EOS. Random-content
    # terminator-copy leaves both models' CONTENT tokens noisy
    # (measured: loss plateaus ~1.7 and draft/target agreement sits
    # at chance), which starves acceptance; a small pool is
    # memorized by BOTH capacities, so the draft accepts — the
    # production analogue is templated / repeated-system-prompt
    # traffic, the same shape bench_paged's prefix cache exploits.
    # EVERY row terminates within the trained S-token horizon: a
    # no-EOS row would decode ~maxT-S positions PAST anything either
    # model saw in training, where their extrapolations disagree
    # chaotically — measured mean accepted length collapsed to ~1.75
    # (< the 2.54 spec-vs-plain tick-cost ratio on this host) with
    # 25% no-EOS traffic, vs ~3+ when generations stay on-horizon.
    pool_rng = np.random.RandomState(5)
    pool = []
    for p in (4, 5, 6, 7, 8, 9, 10, 11):
        row = pool_rng.randint(3, V, (S,)).astype(np.int64)
        row[p:] = end_id
        pool.append(row)
    pool = np.stack(pool)

    def term_prompts(n, r):
        return pool[r.randint(0, len(pool), n)]

    # train target AND draft on the same stream into ONE scope
    # (disjoint names via the draft_ prefix; ONE unique_name guard so
    # their auto-named optimizer moments cannot collide). Target per
    # the CLAUDE.md size ladder (d128/L2 lr.002x600); the draft gets
    # an lr DECAY (.01 x300 then .003 x300, two programs sharing the
    # scope with separate moments — both startups run BEFORE any
    # training): acceptance is the whole game, and the flat-lr draft
    # plateaued ~0.1 loss above the target, costing ~0.2 of mean
    # accepted length.
    scope = Scope()
    with unique_name.guard():
        t_main, t_st, t_loss = T.build_program(
            seq_len=S, d_model=D, n_heads=2, n_layers=L, d_inner=128,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
        with fluid.program_guard(t_main, t_st):
            fluid.optimizer.Adam(learning_rate=0.002).minimize(
                t_loss)
        d_main, d_st, d_loss = T.build_program(
            seq_len=S, d_model=DD, n_heads=2, n_layers=DL,
            d_inner=128, vocab=V, with_optimizer=False,
            dropout_rate=0.0, name_prefix="draft_")
        with fluid.program_guard(d_main, d_st):
            fluid.optimizer.Adam(learning_rate=0.01).minimize(d_loss)
        d_main2, d_st2, d_loss2 = T.build_program(
            seq_len=S, d_model=DD, n_heads=2, n_layers=DL,
            d_inner=128, vocab=V, with_optimizer=False,
            dropout_rate=0.0, name_prefix="draft_")
        with fluid.program_guard(d_main2, d_st2):
            fluid.optimizer.Adam(learning_rate=0.003).minimize(
                d_loss2)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(t_st, scope=scope)
    exe.run(d_st, scope=scope)
    exe.run(d_st2, scope=scope)  # fine-tune moments (re-inits draft
    #                              params — runs BEFORE training)
    for i in range(600):
        src = term_prompts(8, rng)
        tgt_in = np.concatenate(
            [np.full((8, 1), 2, np.int64), src[:, :-1]], 1)
        feed = {"src_ids": src, "tgt_ids": tgt_in, "label": src}
        exe.run(t_main, feed=feed, fetch_list=[t_loss], scope=scope)
        if i < 300:
            exe.run(d_main, feed=feed, fetch_list=[d_loss],
                    scope=scope)
        else:
            exe.run(d_main2, feed=feed, fetch_list=[d_loss2],
                    scope=scope)

    kwargs = dict(seq_len=S, max_out_len=maxT, d_model=D, n_heads=2,
                  n_layers=L, d_inner=128, vocab=V, start_id=2,
                  end_id=end_id)
    with unique_name.guard():
        inc_m, _, _, inc_buf = T.build_incremental_decode_program(
            **kwargs)
    with unique_name.guard():
        plain = T.build_decode_step_program(n_slots=n_slots, **kwargs)
    with unique_name.guard():
        spec = T.build_decode_step_program(
            n_slots=n_slots, state_prefix="@spec/",
            draft=DraftConfig(d_model=DD, n_heads=2, n_layers=DL,
                              d_inner=128, k=spec_k), **kwargs)

    srcs = term_prompts(n_requests, np.random.RandomState(31))
    ref, = exe.run(inc_m, feed={"src_ids": srcs},
                   fetch_list=[inc_buf], scope=scope)
    want = apply_eos_sentinel(np.asarray(ref), end_id)
    lens = count_generated_tokens(want, end_id)
    total_tokens = int(lens.sum())

    def run_leg(make_server):
        srv = make_server()
        try:
            t0 = time.perf_counter()
            replies = [srv.submit(s) for s in srcs]
            outs = [rep.result(600.0) for rep in replies]
            wall = time.perf_counter() - t0
            st = srv.stats()
        finally:
            srv.close()
        assert all(np.array_equal(np.asarray(o), want[i])
                   for i, o in enumerate(outs)), \
            "token parity vs the whole-loop decode failed"
        return {"wall_s": wall, "tok_s": total_tokens / wall,
                "stats": st}

    def whole_loop_leg():
        srv = GenerationServer(
            inc_m, inc_buf, executor=exe, scope=scope, end_id=end_id,
            max_batch_size=n_slots, max_wait_ms=2.0)
        try:
            t0 = time.perf_counter()
            replies = [srv.submit({"src_ids": s[None]}) for s in srcs]
            outs = [apply_eos_sentinel(
                np.asarray(rep.result(600.0)[0]), end_id)[0]
                for rep in replies]
            wall = time.perf_counter() - t0
            st = srv.stats()
        finally:
            srv.close()
        assert all(np.array_equal(o, want[i])
                   for i, o in enumerate(outs)), \
            "whole-loop leg parity failed"
        return {"wall_s": wall, "tok_s": total_tokens / wall,
                "stats": st}

    def plain_leg():
        return run_leg(lambda: ContinuousGenerationServer(
            plain, executor=exe, scope=scope, steps_per_tick=8))

    def spec_leg():
        return run_leg(lambda: ContinuousGenerationServer(
            spec, executor=exe, scope=scope, steps_per_tick=8))

    whole_loop_leg()  # warm all three serve sets (all compiles here)
    plain_leg()
    spec_leg()
    compiles_before = exe.compile_count
    rounds = _harness.interleave_rounds(
        [("whole", whole_loop_leg), ("plain", plain_leg),
         ("spec", spec_leg)], rounds=3)
    steady_compiles = exe.compile_count - compiles_before
    assert steady_compiles == 0, (
        f"steady-state legs compiled {steady_compiles}")
    wbest = _harness.best_leg(rounds, "whole")
    pbest = _harness.best_leg(rounds, "plain")
    sbest = _harness.best_leg(rounds, "spec")
    # asserted ratios are the best PAIRED ones (adjacent legs share
    # this host's CPU-throttle windows — the r10 method,
    # harness.paired_ratio_max)
    speedup_vs_plain = _harness.paired_ratio_max(rounds, "spec",
                                                 "plain")
    speedup_vs_whole = _harness.paired_ratio_max(rounds, "spec",
                                                 "whole")
    triple_toks = [(round(r["whole"]["tok_s"]),
                    round(r["plain"]["tok_s"]),
                    round(r["spec"]["tok_s"])) for r in rounds]
    sp = sbest["stats"]["speculative"]
    assert speedup_vs_plain > 1.0, (
        f"speculative tok/s only {speedup_vs_plain:.2f}x the plain "
        f"decode burst on the high-acceptance workload (paired "
        f"triples: {triple_toks}; acceptance_rate="
        f"{sp['acceptance_rate']}, mean_accepted_len="
        f"{sp['mean_accepted_len']} — PERF.md 'Speculative "
        f"decoding' has the a > c_spec/c_1 threshold arithmetic)")
    result = {
        "metric": "speculative_tokens_per_sec_terminator_copy",
        "value": round(sbest["tok_s"], 1),
        "unit": "tokens/sec",
        "whole_loop_tok_s": round(wbest["tok_s"], 1),
        "plain_burst_tok_s": round(pbest["tok_s"], 1),
        "speculative_tok_s": round(sbest["tok_s"], 1),
        "speedup_vs_plain_burst": round(speedup_vs_plain, 2),
        "speedup_vs_whole_loop": round(speedup_vs_whole, 2),
        "triple_tok_s": [[round(r["whole"]["tok_s"], 1),
                          round(r["plain"]["tok_s"], 1),
                          round(r["spec"]["tok_s"], 1)]
                         for r in rounds],
        "token_parity_vs_whole_loop": True,  # asserted per leg
        "steady_state_compiles": int(steady_compiles),
        "spec": {
            "k": spec_k,
            "draft_model": f"d{DD} L{DL}",
            "target_model": f"d{D} L{L}",
            "acceptance_rate": sp["acceptance_rate"],
            "mean_accepted_len": sp["mean_accepted_len"],
            "proposed": sp["proposed"],
            "accepted": sp["accepted"],
            "emitted": sp["emitted"],
            "draft_steps": sp["draft_steps"],
            "target_steps": sp["target_steps"],
            "tokens_per_target_step": (
                round(sp["emitted"] / sp["target_steps"], 2)
                if sp["target_steps"] else None),
        },
        "n_requests": n_requests,
        "total_tokens": total_tokens,
        "len_histogram": {int(k): int(v) for k, v in
                          zip(*np.unique(lens, return_counts=True))},
        "workload": "terminator-copy over an 8-prompt pool "
                    "(repeated-suffix mix; high draft acceptance)",
        "model": (f"transformer d{D} L{L} S{S} maxT{maxT} "
                  f"slots{n_slots}, draft d{DD} L{DL} k{spec_k}"),
        "best_of": 3,
    }
    return _write_bench_self("BENCH_SELF_r14.json", result,
                             stats_json_dict=sbest["stats"])


def bench_speculative_adaptive(n_easy=48, n_hard=48):
    """Adaptive speculation (r19): distilled draft + per-lane
    acceptance controller + model-free n-gram lane
    (BENCH_SELF_r19.json; inference/spec_controller.py,
    models/distill.py, DraftConfig k_options).

    Narrative measured end to end: task training alone leaves the
    d128/L2-target x d64/L1-draft pair at LOW serve acceptance (the
    r14 recipe's outcome is training-luck bistable on this tiny
    memorization task — at current head it lands near chance), so
    (1) `distill_draft` trains the draft on the TARGET's own greedy
    pool streams + softened logits — acceptance is manufactured, not
    hoped for; (2) the `SpecController` reads per-lane device
    acceptance counters each dispatch and re-buckets lanes across
    the PRE-BUILT k in {0,3,4} serve variants — it holds a positive
    rung on easy (pool) traffic and parks at the k=0 plain burst
    (with periodic re-probes) on off-horizon traffic where
    acceptance collapses; (3) the n-gram lane drafts from each
    lane's own emitted suffix (zero draft FLOPs) through the same
    verify path.

    Legs (interleaved best-of-3, r10/r13 throttled-host discipline;
    BYTE PARITY vs the whole-loop decode asserted inside every leg):
    fixed-k3 vs adaptive on PHASED MIXED traffic (easy pool wave,
    then hard off-horizon wave), fixed-k3 vs pinned-k0 vs adaptive
    on hard-only traffic (the degradation claim), and the n-gram
    lane on pool traffic. Asserted: adaptive > fixed-k3 on mixed
    tok/s (best paired) AND on spec-window tokens/target-step;
    adaptive-hard > fixed-k3-hard (paired) and within 0.6x of the
    pinned plain burst; distilled acceptance lifts > +0.15 absolute;
    ZERO steady-state compiles across all legs (the executable bill
    is fixed at build — re-bucketing is pure program selection).
    Honest accounting caveat: the k=0 rung deliberately bumps NO
    spec counters, so adaptive per-leg acceptance/emitted cover only
    its spec-rung dispatches (PERF.md "Adaptive speculation")."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.inference import (ContinuousGenerationServer,
                                      SpecController,
                                      apply_eos_sentinel,
                                      count_generated_tokens)
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.distill import distill_draft
    from paddle_tpu.models.decode_engine import DraftConfig

    V, D, L, S, maxT = 16, 128, 2, 12, 64
    DD, DL = 64, 1
    n_slots = 8
    end_id = 1
    rng = np.random.RandomState(7)

    # the r14 8-prompt repeated-suffix pool (easy/templated traffic)
    pool_rng = np.random.RandomState(5)
    pool = []
    for p in (4, 5, 6, 7, 8, 9, 10, 11):
        row = pool_rng.randint(3, V, (S,)).astype(np.int64)
        row[p:] = end_id
        pool.append(row)
    pool = np.stack(pool)

    def term_prompts(n, r):
        return pool[r.randint(0, len(pool), n)]

    def hard_prompts(n, r):
        # off-horizon: random content with NO planted EOS — the
        # generation runs past anything either model trained on, so
        # draft/target extrapolations disagree and acceptance
        # collapses (PERF.md r14 "dead end (2)")
        return r.randint(3, V, (n, S)).astype(np.int64)

    # same training recipe as bench_speculative (d128/L2 lr.002x600
    # target; d64/L1 draft with the .01x300/.003x300 lr decay)
    scope = Scope()
    with unique_name.guard():
        t_main, t_st, t_loss = T.build_program(
            seq_len=S, d_model=D, n_heads=2, n_layers=L, d_inner=128,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
        with fluid.program_guard(t_main, t_st):
            fluid.optimizer.Adam(learning_rate=0.002).minimize(
                t_loss)
        d_main, d_st, d_loss = T.build_program(
            seq_len=S, d_model=DD, n_heads=2, n_layers=DL,
            d_inner=128, vocab=V, with_optimizer=False,
            dropout_rate=0.0, name_prefix="draft_")
        with fluid.program_guard(d_main, d_st):
            fluid.optimizer.Adam(learning_rate=0.01).minimize(d_loss)
        d_main2, d_st2, d_loss2 = T.build_program(
            seq_len=S, d_model=DD, n_heads=2, n_layers=DL,
            d_inner=128, vocab=V, with_optimizer=False,
            dropout_rate=0.0, name_prefix="draft_")
        with fluid.program_guard(d_main2, d_st2):
            fluid.optimizer.Adam(learning_rate=0.003).minimize(
                d_loss2)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(t_st, scope=scope)
    exe.run(d_st, scope=scope)
    exe.run(d_st2, scope=scope)
    for i in range(600):
        src = term_prompts(8, rng)
        tgt_in = np.concatenate(
            [np.full((8, 1), 2, np.int64), src[:, :-1]], 1)
        feed = {"src_ids": src, "tgt_ids": tgt_in, "label": src}
        exe.run(t_main, feed=feed, fetch_list=[t_loss], scope=scope)
        if i < 300:
            exe.run(d_main, feed=feed, fetch_list=[d_loss],
                    scope=scope)
        else:
            exe.run(d_main2, feed=feed, fetch_list=[d_loss2],
                    scope=scope)

    kwargs = dict(seq_len=S, max_out_len=maxT, d_model=D, n_heads=2,
                  n_layers=L, d_inner=128, vocab=V, start_id=2,
                  end_id=end_id)
    LADDER = (0, 3, 4)
    draft_cfg = DraftConfig(d_model=DD, n_heads=2, n_layers=DL,
                            d_inner=128, k=3, k_options=LADDER)
    with unique_name.guard():
        inc_m, _, _, inc_buf = T.build_incremental_decode_program(
            **kwargs)
    with unique_name.guard():
        adapt = T.build_decode_step_program(
            n_slots=n_slots, state_prefix="@ak/",
            admit_buckets=[n_slots], draft=draft_cfg, **kwargs)
    with unique_name.guard():
        ngram = T.build_decode_step_program(
            n_slots=n_slots, state_prefix="@an/",
            admit_buckets=[n_slots],
            draft=DraftConfig(k=2, kind="ngram", ngram=2,
                              k_options=(0, 2)), **kwargs)

    def oracle(srcs):
        ref, = exe.run(inc_m, feed={"src_ids": srcs},
                       fetch_list=[inc_buf], scope=scope)
        return apply_eos_sentinel(np.asarray(ref), end_id)

    easy = term_prompts(n_easy, np.random.RandomState(31))
    hard = hard_prompts(n_hard, np.random.RandomState(33))
    w_easy, w_hard = oracle(easy), oracle(hard)
    easy_tokens = int(count_generated_tokens(w_easy, end_id).sum())
    hard_tokens = int(count_generated_tokens(w_hard, end_id).sum())

    class _Pinned:
        """Constant-k controller — the fixed-k baselines route
        through the SAME bundle and programs (zero extra compiles),
        isolating the adaptation policy as the only variable."""

        def __init__(self, k):
            self.k = k

        def choose(self):
            return self.k

        def observe(self, accepted, proposed, k):
            pass

        def reset_lane(self, lane):
            pass

        def stats(self):
            return {"pinned_k": self.k}

    def _auto():
        # draft_cost_ratio = the honest d64/L1-vs-d128/L2 per-step
        # FLOPs ratio (~1/8); the objective is expected tokens per
        # VERIFY step net of draft cost — the real-chip lever (on
        # CPU the (k+1)-query verify also scales with k, which the
        # wall-clock legs below price in). ewma=0.5: one observation
        # here is a WHOLE fused dispatch (~8 ticks x 8 lanes x k
        # proposals pooled), so the fast constant still averages
        # hundreds of proposals — at the library default 0.25 the
        # estimate needs ~5 dispatches to cross the park threshold
        # after a traffic shift, which is most of a wave at this
        # burst size (measured; the r10 lesson again: everything
        # must amortize against BIG dispatches).
        return SpecController(LADDER, default_k=3,
                              draft_cost_ratio=0.125, ewma=0.5,
                              probe_every=8)

    def run_leg(bundle, make_ctl, phases, tag):
        srv = ContinuousGenerationServer(
            bundle, executor=exe, scope=scope, steps_per_tick=8,
            spec_controller=make_ctl())
        try:
            t0 = time.perf_counter()
            for srcs, want in phases:
                replies = [srv.submit(s) for s in srcs]
                outs = [rep.result(600.0) for rep in replies]
                assert all(
                    np.array_equal(np.asarray(o), want[i])
                    for i, o in enumerate(outs)), \
                    f"{tag}: token parity vs whole-loop decode failed"
            wall = time.perf_counter() - t0
            st = srv.stats()
        finally:
            srv.close()
        toks = sum(int(count_generated_tokens(w, end_id).sum())
                   for _, w in phases)
        sp = st["speculative"]
        tps = (round(sp["emitted"] / sp["target_steps"], 2)
               if sp.get("target_steps") else None)
        return {"wall_s": wall, "tok_s": toks / wall, "stats": st,
                "acceptance": sp["acceptance_rate"],
                "mean_accepted_len": sp["mean_accepted_len"],
                "tokens_per_target_step": tps,
                "per_k_dispatches": {
                    k: v["dispatches"]
                    for k, v in (sp.get("per_k") or {}).items()}}

    mixed = [(easy, w_easy), (hard, w_hard)]
    legs = {
        "fixed3_mixed": lambda: run_leg(
            adapt, lambda: _Pinned(3), mixed, "fixed3_mixed"),
        "adaptive_mixed": lambda: run_leg(
            adapt, _auto, mixed, "adaptive_mixed"),
        "fixed3_hard": lambda: run_leg(
            adapt, lambda: _Pinned(3), [(hard, w_hard)],
            "fixed3_hard"),
        "plain_hard": lambda: run_leg(
            adapt, lambda: _Pinned(0), [(hard, w_hard)],
            "plain_hard"),
        "adaptive_hard": lambda: run_leg(
            adapt, _auto, [(hard, w_hard)], "adaptive_hard"),
        "ngram_easy": lambda: run_leg(
            ngram, lambda: _Pinned(2), [(easy, w_easy)],
            "ngram_easy"),
    }

    # warm every serve rung of both bundles (all compiles land here)
    for k in (3, 4, 0):
        run_leg(adapt, lambda k=k: _Pinned(k),
                [(easy[:n_slots], w_easy[:n_slots])], f"warm_k{k}")
    for k in (2, 0):
        run_leg(ngram, lambda k=k: _Pinned(k),
                [(easy[:n_slots], w_easy[:n_slots])],
                f"warm_ng{k}")

    # BEFORE: task-training-only acceptance at the default rung
    pre = run_leg(adapt, lambda: _Pinned(3), [(easy, w_easy)],
                  "pre_distill")
    acc_before = pre["acceptance"]

    # the tentpole: distill the draft on the TARGET's own greedy
    # pool streams (draft params update in place in the live scope;
    # target params untouched, so every oracle/want above stays
    # valid — asserted again by per-leg parity below)
    t0 = time.perf_counter()
    dres = distill_draft(
        exe, scope, draft_cfg, decode_fn=oracle,
        prompts_fn=lambda r, n: term_prompts(n, r),
        rounds=12, batch=8, inner_steps=4, learning_rate=0.005,
        seed=3, **kwargs)
    distill_wall = time.perf_counter() - t0

    post = run_leg(adapt, lambda: _Pinned(3), [(easy, w_easy)],
                   "post_distill")
    acc_after = post["acceptance"]
    assert acc_after > acc_before + 0.15, (
        f"distillation lifted pool acceptance only {acc_before} -> "
        f"{acc_after} (teacher-forced agree trajectory: "
        f"{dres['agree']})")

    compiles_before = exe.compile_count
    rounds = _harness.interleave_rounds(
        list(legs.items()), rounds=3)
    steady_compiles = exe.compile_count - compiles_before
    assert steady_compiles == 0, (
        f"steady-state legs compiled {steady_compiles} — the k "
        f"ladder must be fully pre-built")

    best = {name: _harness.best_leg(rounds, name) for name in legs}
    adaptive_vs_fixed = _harness.paired_ratio_max(
        rounds, "adaptive_mixed", "fixed3_mixed")
    # the max can ride a throttle window the OTHER leg fell into even
    # with interleaving; the min is the claim's floor — record both
    adaptive_vs_fixed_min = min(
        r["adaptive_mixed"]["tok_s"] / r["fixed3_mixed"]["tok_s"]
        for r in rounds)
    adaptive_vs_fixed_hard = _harness.paired_ratio_max(
        rounds, "adaptive_hard", "fixed3_hard")
    degradation = _harness.paired_ratio_max(
        rounds, "adaptive_hard", "plain_hard")
    pair_toks = [[round(r["fixed3_mixed"]["tok_s"], 1),
                  round(r["adaptive_mixed"]["tok_s"], 1)]
                 for r in rounds]
    assert adaptive_vs_fixed > 1.0, (
        f"adaptive tok/s only {adaptive_vs_fixed:.2f}x fixed-k3 on "
        f"the phased mixed traffic (paired [fixed, adaptive]: "
        f"{pair_toks})")
    ab, fb = best["adaptive_mixed"], best["fixed3_mixed"]
    assert ab["tokens_per_target_step"] > fb[
        "tokens_per_target_step"], (
        f"adaptive spec-window tokens/target-step "
        f"{ab['tokens_per_target_step']} did not beat fixed-k3's "
        f"{fb['tokens_per_target_step']}")
    assert adaptive_vs_fixed_hard > 1.0, (
        f"adaptive only {adaptive_vs_fixed_hard:.2f}x fixed-k3 on "
        f"off-horizon traffic — the controller failed to park")
    assert degradation > 0.6, (
        f"adaptive off-horizon throughput {degradation:.2f}x the "
        f"pinned k=0 plain burst — parking overhead too high")
    # the adaptive mixed leg must actually EXERCISE the ladder:
    # a positive rung during the pool wave, k=0 during the hard wave
    adisp = ab["per_k_dispatches"]
    assert adisp.get(0, 0) > 0 and (
        adisp.get(3, 0) + adisp.get(4, 0)) > 0, adisp
    ng = best["ngram_easy"]
    ng_sp = ng["stats"]["speculative"]
    assert ng_sp["draft_steps"] == 0 and ng_sp["proposed"] > 0, ng_sp

    result = {
        "metric": "adaptive_spec_tokens_per_sec_mixed",
        "value": round(ab["tok_s"], 1),
        "unit": "tokens/sec",
        "adaptive_mixed_tok_s": round(ab["tok_s"], 1),
        "fixed3_mixed_tok_s": round(fb["tok_s"], 1),
        "adaptive_vs_fixed3_mixed": round(adaptive_vs_fixed, 2),
        "adaptive_vs_fixed3_mixed_min": round(
            adaptive_vs_fixed_min, 2),
        "adaptive_vs_fixed3_hard": round(adaptive_vs_fixed_hard, 2),
        "adaptive_hard_vs_plain_burst": round(degradation, 2),
        "paired_mixed_tok_s": pair_toks,
        "tokens_per_target_step": {
            "fixed3_mixed": fb["tokens_per_target_step"],
            "adaptive_mixed_spec_window":
                ab["tokens_per_target_step"]},
        "adaptive_per_k_dispatches": adisp,
        "controller": {"k_options": list(LADDER), "default_k": 3,
                       "draft_cost_ratio": 0.125},
        "distillation": {
            "acceptance_before": acc_before,
            "acceptance_after": acc_after,
            "mean_accepted_len_before": pre["mean_accepted_len"],
            "mean_accepted_len_after": post["mean_accepted_len"],
            "teacher_forced_agree": [round(a, 3)
                                     for a in dres["agree"]],
            "rounds": 12, "inner_steps": 4, "batch": 8,
            "wall_s": round(distill_wall, 1)},
        "ngram": {
            "tok_s": round(ng["tok_s"], 1),
            "acceptance": ng_sp["acceptance_rate"],
            "mean_accepted_len": ng_sp["mean_accepted_len"],
            "draft_steps": ng_sp["draft_steps"],
            "proposed": ng_sp["proposed"]},
        "token_parity_vs_whole_loop": True,  # asserted per leg
        "steady_state_compiles": int(steady_compiles),
        "workload": {
            "easy": f"{n_easy} reqs / {easy_tokens} toks from the "
                    "8-prompt repeated-suffix pool",
            "hard": f"{n_hard} reqs / {hard_tokens} toks "
                    "off-horizon (random content, no planted EOS)"},
        "model": (f"target d{D} L{L}, draft d{DD} L{DL} distilled, "
                  f"k_options={list(LADDER)}, slots{n_slots}"),
        "best_of": 3,
    }
    return _write_bench_self("BENCH_SELF_r19.json", result,
                             stats_json_dict=ab["stats"])


def bench_multitenant(n_requests=900):
    """Restore-safe wrapper: the body flips FLAGS_observability
    across legs with hard asserts in between, and main() keeps going
    after a failed config — a tripped assert must not leave the flag
    at metrics/trace for every later bench in the process."""
    from paddle_tpu.flags import FLAGS, set_flags

    prev = FLAGS.observability
    try:
        return _bench_multitenant_body(n_requests=n_requests)
    finally:
        set_flags({"FLAGS_observability": prev})


def _bench_multitenant_body(n_requests=900):
    """Multi-tenant serving runtime (inference/runtime): ONE process
    serves the 3-model runtime zoo under mixed Zipf traffic from 3
    tenants through the ModelRegistry + SLO-aware Router, then hot-
    swaps the most popular model mid-traffic. Asserted invariants
    (the r11 acceptance criteria, not just reported): bounded
    executable count (<= N x (buckets + 1) in the SHARED LRU), ZERO
    steady-state compiles after warm, zero accepted-request loss
    across the swap, and (r12) a complete slow-request span tree from
    the observability layer. Writes BENCH_SELF_r12.json next to this
    file, including the off/metrics/trace interleaved A/B and the
    `telemetry` snapshot.

    CPU-PINNED in code (same caveat as bench_generation). Best-of-3 traffic
    legs: this 2-core host swings single-pass walls ~3x (the
    interleave discipline is for A/B server comparisons; one system
    best-of-N is the PERF.md fallback)."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.inference.runtime import ServingRuntime, zoo

    max_batch = 16
    rt = ServingRuntime()
    models = []
    for prefix, in_dim, hidden, classes in zoo.DEFAULT_ZOO:
        server, _scope = zoo.make_fc_server(
            prefix, in_dim, hidden, classes, executor=rt.executor(),
            max_batch_size=max_batch, max_wait_ms=2.0)
        rt.load_model(prefix, server)
        models.append((prefix, in_dim, hidden, classes))
    n_models = len(models)
    ladder = len(rt.registry.get(models[0][0]).server.batch_buckets)

    def total_compiles():
        return sum(h.executor.compile_count
                   for h in rt.registry.aliases().values())

    compiles_after_warm = total_compiles()

    # tenants: a heavy free tier (70% of traffic), a mid tier (20%),
    # and a small paid tenant (10%, 2x weight, tight SLO) — the
    # noisy-neighbor mix the WDRR scheduler exists for
    rt.add_tenant("heavy", weight=1.0, max_queue=1 << 16)
    rt.add_tenant("mid", weight=1.0, max_queue=1 << 16)
    rt.add_tenant("small", weight=2.0, max_queue=1 << 16,
                  target_p99_ms=500.0)
    rng = np.random.RandomState(0)
    zipf = np.array([1.0 / (r + 1) ** 1.1 for r in range(n_models)])
    zipf /= zipf.sum()
    tenant_mix = rng.choice(["heavy", "mid", "small"],
                            size=n_requests, p=[0.7, 0.2, 0.1])
    model_mix = rng.choice(n_models, size=n_requests, p=zipf)
    schedule = []
    for k in range(n_requests):
        prefix, in_dim = models[model_mix[k]][:2]
        schedule.append(
            (str(tenant_mix[k]), prefix,
             {f"{prefix}_x": rng.randn(1, in_dim).astype(np.float32)}))

    def leg(repeat=1):
        t0 = time.perf_counter()
        replies = [rt.submit(t, m, f)
                   for _ in range(repeat)
                   for t, m, f in schedule]
        for rep in replies:
            rep.result(600.0)
        wall = time.perf_counter() - t0
        return repeat * n_requests / wall, rt.stats(reset=True)

    # observability-overhead A/B (the r12 acceptance gate): the SAME
    # traffic leg alternating FLAGS_observability off/metrics/trace,
    # interleaved best-of-3 per the PERF.md discipline (sequential
    # legs land in different throttle windows on this 2-core host and
    # report 2x-off ratios). The metrics level is pull-based
    # (weakref providers read at expose() time), so the expected
    # delta is noise-level; the interleave is what makes 3% resolvable.
    from paddle_tpu import observability as obs
    from paddle_tpu.flags import FLAGS, set_flags

    leg()  # discard: very first traffic leg is cold (thread pools,
    #        allocator)
    # headline: best-of-3 at the r11 leg length, observability off —
    # the value stays comparable across rounds
    set_flags({"FLAGS_observability": "off"})
    legs = [leg() for _ in range(3)]
    best_rps, best_st = max(legs, key=lambda x: x[0])

    def ab_pair(mode_a, mode_b, reps, repeat=4):
        """Paired-median A/B over FLAGS_observability modes
        (harness.paired_median_ab has the throttle-defense
        rationale); legs run the schedule ``repeat``x so each spans
        multiple throttle windows instead of landing inside one."""
        return _harness.paired_median_ab(
            lambda: leg(repeat=repeat),
            lambda mode: set_flags({"FLAGS_observability": mode}),
            mode_a, mode_b, reps)

    obs_ratio, metrics_ratios, mo_legs = ab_pair("metrics", "off", 6)
    trace_ratio, trace_ratios, to_legs = ab_pair("trace", "off", 4)
    ab_legs = {"off": mo_legs["off"] + to_legs["off"],
               "metrics": mo_legs["metrics"],
               "trace": to_legs["trace"]}

    # The A/B above records the acceptance protocol, but this host's
    # CPU-share throttle swings IDENTICAL adjacent legs up to 1.7x
    # (see the recorded pair ratios) — no end-to-end estimator tried
    # here (paired median, ABBA quads, best-of-20 interleaved, 15 s
    # legs) resolves 3% run-to-run. The budget is therefore checked
    # against a DIRECT measurement: time the exact per-request work
    # the metrics level adds (the flag gate, the request id, and the
    # coarse flight-recorder entry — everything else runs at off too)
    # and compare it to the measured per-request wall. This is
    # deterministic to a few percent where the macro ratio is not.
    from paddle_tpu.observability import flight as obs_flight
    from paddle_tpu.observability import tracing as obs_tracing
    from paddle_tpu.observability.metrics import metrics_on

    set_flags({"FLAGS_observability": "metrics"})
    scratch = obs_flight.FlightRecorder(max_recent=8)  # not the
    #   global ring: the telemetry snapshot must not count bench spins
    K = 50_000
    t0 = time.perf_counter()
    for _ in range(K):
        metrics_on()
        rid = obs_tracing.TRACER.next_request_id()
        scratch.record(
            {"request_id": rid, "status": "ok",
             "slo_violated": False, "tenant": "bench",
             "model": "tiny", "latency_ms": 12.3, "queue_ms": 1.2},
            incident=False)
    direct_us = (time.perf_counter() - t0) / K * 1e6
    mean_off_rps = (sum(r for r, _ in ab_legs["off"])
                    / len(ab_legs["off"]))
    wall_us = 1e6 / mean_off_rps  # conservative: per-request WALL,
    #   not the 2-core CPU budget (which is ~2x larger)
    overhead_frac = direct_us / wall_us
    # back to the headline level: the hot-swap phase below (swap_s,
    # post-swap compile window, zero-loss leg) must run at the SAME
    # observability level as the headline legs and the r11 record it
    # is compared against — not at the microbench's metrics level
    set_flags({"FLAGS_observability": "off"})

    # forensic demo (acceptance): the SLOWEST traced request's span
    # tree must be complete — router.queue -> server.queue ->
    # server.dispatch -> execute -> readback under the request root,
    # with cache-tier annotations — and the whole sink dumps to one
    # chrome trace (written under /tmp; the timeline summary is
    # recorded in the result JSON)
    with obs.TRACER._lock:
        traced = list(obs.TRACER.completed)
    slow = max(traced, key=lambda t: (t.t_end or t.t_start) - t.t_start)
    slow_tl = slow.timeline()
    slow_names = {s["name"] for s in slow_tl["spans"]}
    need = {"request", "router.queue", "server.queue",
            "server.dispatch", "execute", "readback"}
    assert need <= slow_names, (
        f"slow-request trace incomplete: missing "
        f"{sorted(need - slow_names)} in {sorted(slow_names)}")
    obs.dump_trace("/tmp/paddle_tpu_multitenant_trace_r12")
    steady_compiles = total_compiles() - compiles_after_warm
    assert steady_compiles == 0, (
        f"steady-state traffic compiled {steady_compiles} fresh "
        f"executable(s)")
    exe_count = best_st["cache"]["executable"]["size"]
    bound = n_models * (ladder + 1)
    assert exe_count <= bound, (
        f"executable count {exe_count} exceeds the "
        f"N x (buckets + 1) bound {bound}")

    # --- mid-traffic hot swap of the most popular model -------------
    popular, pop_dim, pop_hidden, pop_classes = models[0]
    import threading

    accepted, rejected, stop = [], [], [False]

    def traffic():
        # A submit exception must not kill the thread silently: the
        # zero-loss assertion below would then pass vacuously against
        # near-zero traffic. Rejections are collected and asserted
        # empty after the window.
        while not stop[0]:
            try:
                accepted.append(rt.submit(
                    "heavy", popular,
                    {f"{popular}_x": rng.randn(1, pop_dim).astype(
                        np.float32)}))
            except Exception as e:
                rejected.append(repr(e))
            time.sleep(0.0005)

    th = threading.Thread(target=traffic)
    th.start()
    time.sleep(0.3)
    new_server, _ = zoo.make_fc_server(
        popular, pop_dim, pop_hidden + 64, pop_classes,
        executor=rt.executor(), max_batch_size=max_batch,
        max_wait_ms=2.0)
    t0 = time.perf_counter()
    rt.load_model(popular, new_server)     # warm -> flip -> drain
    swap_s = time.perf_counter() - t0
    compiles_post_swap_warm = total_compiles()
    time.sleep(0.3)
    stop[0] = True
    th.join()
    lost = []
    for rep in accepted:
        try:
            rep.result(600.0)
        except Exception as e:
            lost.append(repr(e))
    swap_steady = total_compiles() - compiles_post_swap_warm
    assert swap_steady == 0, (
        f"post-swap steady state compiled {swap_steady}")
    assert not rejected, (
        f"hot swap rejected {len(rejected)} submission(s) at "
        f"admission: {rejected[:3]}")
    swap_st = rt.stats()
    zero_loss = (not lost
                 and swap_st["tenants"]["heavy"]["failed"] == 0)
    assert zero_loss, (
        f"hot swap lost {len(lost)} accepted request(s): {lost[:3]}")
    rt.close()

    result = {
        "metric": "multitenant_aggregate_requests_per_sec",
        "value": round(best_rps, 1),
        "unit": "requests/sec",
        "rps_legs": [round(r, 1) for r, _ in legs],
        "n_models": n_models,
        "models": [f"{p} fc {i}->{h}->{c}"
                   for p, i, h, c in models],
        "zipf_model_probs": [round(float(p), 3) for p in zipf],
        "tenant_mix": {"heavy": 0.7, "mid": 0.2, "small": 0.1},
        "per_tenant": {
            name: {
                "completed": ts["completed"],
                "p50_ms": ts["latency_ms"]["p50"],
                "p99_ms": ts["latency_ms"]["p99"],
                "queue_p99_ms": ts["queue_ms"]["p99"],
                "slo_violations": ts["slo_violations"],
                "target_p99_ms": ts["target_p99_ms"],
            } for name, ts in best_st["tenants"].items()},
        "p99_isolation_small_over_heavy": round(
            best_st["tenants"]["small"]["latency_ms"]["p99"]
            / best_st["tenants"]["heavy"]["latency_ms"]["p99"], 3),
        "executable_count": exe_count,
        "executable_bound": bound,
        "steady_state_compiles": int(steady_compiles),
        "hot_swap": {
            "swap_s": round(swap_s, 3),
            "accepted_during_leg": len(accepted),
            "completed": len(accepted) - len(lost),
            "zero_loss": bool(zero_loss),
            "post_swap_steady_compiles": int(swap_steady),
            "swaps": swap_st["registry"]["swaps"],
        },
        "cache": best_st["cache"]["executable"],
        "observability_overhead": {
            "ab_method": ("median of paired adjacent-leg ratios, "
                          "order alternated per pair; evidence only "
                          "— host throttle noise floor >> 3% (see "
                          "PERF.md 'Observability overhead')"),
            "metrics_over_off": round(obs_ratio, 4),
            "trace_over_off": round(trace_ratio, 4),
            "metrics_pair_ratios": [round(r, 4)
                                    for r in metrics_ratios],
            "trace_pair_ratios": [round(r, 4) for r in trace_ratios],
            "rps_legs": {m: [round(r, 1) for r, _ in ab_legs[m]]
                         for m in ("off", "metrics", "trace")},
            "budget": "metrics within 3% of off",
            "direct_overhead_us_per_request": round(direct_us, 3),
            "per_request_wall_us_at_off": round(wall_us, 1),
            "direct_overhead_fraction": round(overhead_frac, 5),
            "within_budget": bool(overhead_frac < 0.03),
        },
        "slow_request_trace": slow_tl,
        "trace_dump": "/tmp/paddle_tpu_multitenant_trace_r12.json",
        "n_requests": n_requests,
        "max_batch_size": max_batch,
        "best_of": 3,
    }
    return _write_bench_self("BENCH_SELF_r12.json", result,
                             stats_json_dict=best_st)


def bench_frontdoor():
    """Streaming front door under overload (ISSUE 20): per-token
    delivery, cancellation that frees device state, and
    deadline-aware shedding. Four leg families, interleaved
    best-of-3 (throttled-host discipline):

    * ``stream`` / ``whole`` — the SAME long prompts decoded
      sequentially on an idle server, delivered per burst
      (``submit(stream=True)``; TTFT = client-observed first-burst
      latency, ``StreamingReply.ttft_s``) vs as one whole-response
      future (there "TTFT" IS completion latency — the thing
      streaming exists to fix). Byte parity streamed-vs-whole and
      vs the incremental-decode oracle asserted per leg.
    * ``shed_Mx`` / ``noshed_Mx``, M in 1, 2, 4 — a cancel-heavy
      open-loop workload offered at M x measured idle capacity:
      every 3rd request is an ABANDONER (streamed at the server,
      cancelled right after its first burst — the teardown returns
      its lane/blocks/entry MID-decode), the rest carry a completion
      deadline (5 x the calibrated per-request service estimate —
      the SLO is stated in the controller's own units) through
      ``router.submit(deadline_ms=)``. Every request in a leg is a
      DISTINCT prompt: a repeated prompt re-admits through the
      radix-reuse tier and decodes nearly for free, which silently
      deflates the very service cost the overload is supposed to
      stress. The shed leg rejects
      unmeetable deadlines PRE-SLOT on the calibrated costmodel
      estimate (typed ``DeadlineUnmeetable``); the noshed leg is the
      same front door with the estimator uncalibrated (an
      uncalibrated estimator must not shed anyone), so it admits
      everything and burns prefills + decode bursts on requests that
      then expire at burst boundaries. Goodput = deadline-met
      completions / wall-to-all-resolved. The PAIRED shed/noshed
      goodput ratio must exceed 1 at >= 2x overload — under
      overload the box must spend capacity only on requests that
      can still meet their SLO.

    Every leg drains its pools to fully-free before closing
    (radix-aware: plain retirements ADOPT full blocks into the
    tree, so the gauge contract is prefix.in_use == 0 and
    radix-evicted == blocks held), and the measured rounds compile
    NOTHING (streaming adds no fetches and no programs).

    CPU-PINNED in code (the shed/cancel/stream mechanics are
    host-side; same caveat as bench_generation). Writes
    BENCH_SELF_r20.json."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.flags import FLAGS, set_flags
    from paddle_tpu.inference import (PagedContinuousGenerationServer,
                                      apply_eos_sentinel,
                                      count_generated_tokens)
    from paddle_tpu.inference.runtime import (AdmissionError,
                                              DeadlineUnmeetable,
                                              ModelRegistry, Router)
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.decode_engine import CacheConfig

    # metrics level for the whole bench: the costmodel calibration
    # behind the shed estimate and the flight-recorder incident trail
    # are both front-door features under measure here
    prev_obs = FLAGS.observability
    set_flags({"FLAGS_observability": "metrics"})
    obs.reset()

    V, D, H, L, S, maxT = 16, 32, 2, 1, 10, 32
    end_id = 1
    BS, NB, E, n_slots = 8, 24, 6, 4
    rng = np.random.RandomState(7)

    def term_prompt(r, p):
        src = r.randint(3, V, (S,)).astype(np.int64)
        if p < S:
            src[p:] = end_id
        return src

    # terminator-copy training (the d32 lr/steps point of the
    # CLAUDE.md ladder): planted-EOS prompts give model-driven
    # mixed-length generations; the p=10 rows never plant one, so
    # their decodes run long — the abandoners' mid-decode window
    fluid.seed(0)
    scope = Scope()
    with unique_name.guard():
        main_p, startup, loss = T.build_program(
            seq_len=S, d_model=D, n_heads=H, n_layers=L, d_inner=64,
            vocab=V, with_optimizer=False, dropout_rate=0.0)
        with fluid.program_guard(main_p, startup):
            fluid.optimizer.Adam(learning_rate=0.02).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    for _ in range(150):
        src = np.stack([term_prompt(rng, int(rng.choice(
            [1, 2, 3, 4, 6, 8, 10, 10]))) for _ in range(8)])
        tgt_in = np.concatenate(
            [np.full((8, 1), 2, np.int64), src[:, :-1]], 1)
        exe.run(main_p, feed={"src_ids": src, "tgt_ids": tgt_in,
                              "label": src}, fetch_list=[loss],
                scope=scope)

    kwargs = dict(seq_len=S, max_out_len=maxT, d_model=D, n_heads=H,
                  n_layers=L, d_inner=64, vocab=V, start_id=2,
                  end_id=end_id)
    with unique_name.guard():
        inc_m, _, _, inc_buf = T.build_incremental_decode_program(
            **kwargs)
    # ONE admission bucket: every admission pads to n_slots (dustbin
    # lanes), so the warm round deterministically covers the whole
    # compile set — the zero-steady-compiles assert never rides on
    # which queue depths a throttle window happened to produce
    with unique_name.guard():
        paged = T.build_decode_step_program(
            n_slots=n_slots, state_prefix="@fdb/",
            admit_buckets=[n_slots],
            cache=CacheConfig(layout="paged", block_size=BS,
                              n_blocks=NB, n_prompt_entries=E),
            **kwargs)

    def oracle(srcs):
        ref, = exe.run(inc_m, feed={"src_ids": np.asarray(srcs)},
                       fetch_list=[inc_buf], scope=scope)
        return apply_eos_sentinel(np.asarray(ref), end_id=end_id)

    # pick prompts BY DECODE: the mixed pool is the SLO traffic, the
    # long generations (>= 16 tokens) feed the TTFT contrast and the
    # abandoners (a cancel must land mid-decode to return anything)
    mix_prompts = np.stack(
        [term_prompt(rng, p) for p in (1, 2, 3, 4, 6, 8, 10, 10)]
        + [rng.randint(3, V, (S,)).astype(np.int64)
           for _ in range(16)])
    mix_rows = oracle(mix_prompts)
    mix_lens = count_generated_tokens(mix_rows, end_id)
    long_idx = [i for i in range(len(mix_prompts))
                if mix_lens[i] >= 16][:6]
    assert long_idx, f"no long-decode prompt in the pool: {mix_lens}"
    long_prompts = mix_prompts[long_idx]
    long_rows = mix_rows[long_idx]

    def oracle_many(srcs, chunk=24):
        # oracle the per-leg prompt sets in fixed-size chunks during
        # SETUP (one compiled shape; padding rows decode + discard)
        srcs = np.asarray(srcs)
        pad = (-len(srcs)) % chunk
        if pad:
            srcs = np.concatenate(
                [srcs, np.repeat(srcs[-1:], pad, 0)])
        rows = np.concatenate([oracle(srcs[k:k + chunk])
                               for k in range(0, len(srcs), chunk)])
        return rows[:len(rows) - pad] if pad else rows

    def fresh_server(shed):
        srv = PagedContinuousGenerationServer(
            paged, executor=exe, scope=scope, steps_per_tick=2,
            drain_steps=2)
        if not shed:
            # the r20 contract verbatim: an uncalibrated estimator
            # must not shed anyone — disabling the estimator IS the
            # no-shed front door, not a parallel code path
            srv.expected_service_ms = lambda n_tokens=None: None
        return srv

    def assert_drained(srv, leg):
        # every reply resolved -> lanes freed at the resolving burst;
        # poll briefly for the scheduler's final bookkeeping, then
        # apply the radix-aware gauge contract: plain retirements
        # ADOPT full blocks into the tree, cancels adopt nothing
        for _ in range(400):
            with srv._cv:
                idle = all(l is None for l in srv._lanes) \
                    and not srv._queue
            if idle:
                break
            time.sleep(0.005)
        held = srv._blocks.in_use
        assert srv._prefix.in_use == 0, (
            f"{leg}: {srv._prefix.in_use} prompt-entry refs leaked")
        evicted = srv._radix.evict(NB)
        assert evicted == held, (
            f"{leg}: {held} blocks held but only {evicted} were "
            f"radix adoptions — a cancel/deadline teardown leaked")
        assert srv._blocks.free_count == NB, (
            f"{leg}: block pool not fully free after evict: "
            f"{srv._blocks.free_count}/{NB}")

    # --- TTFT legs: streamed vs whole-response delivery --------------
    def stream_leg():
        srv = fresh_server(shed=True)
        try:
            ttfts = []
            t0 = time.perf_counter()
            for k in range(len(long_prompts)):
                rep = srv.submit(long_prompts[k], stream=True)
                toks = np.array([t for _, t in rep], np.int64)
                row = np.asarray(rep.result(120.0))
                n = int(count_generated_tokens(row[None], end_id)[0])
                assert np.array_equal(toks, row[1:1 + n]), (
                    f"stream/whole parity broke on prompt {k}")
                assert np.array_equal(row, long_rows[k]), (
                    f"streamed decode diverged from oracle on {k}")
                ttfts.append(rep.ttft_s * 1e3)
            wall = time.perf_counter() - t0
            st = srv.stats()
            assert_drained(srv, "stream")
        finally:
            srv.close()
        return {"wall_s": wall, "ttft_ms": ttfts, "stats": st}

    def whole_leg():
        srv = fresh_server(shed=True)
        try:
            ttfts = []
            t0 = time.perf_counter()
            for k in range(len(long_prompts)):
                t1 = time.perf_counter()
                row = np.asarray(
                    srv.submit(long_prompts[k]).result(120.0))
                ttfts.append((time.perf_counter() - t1) * 1e3)
                assert np.array_equal(row, long_rows[k]), (
                    f"whole-response decode diverged from oracle on "
                    f"{k}")
            wall = time.perf_counter() - t0
            st = srv.stats()
            assert_drained(srv, "whole")
        finally:
            srv.close()
        return {"wall_s": wall, "ttft_ms": ttfts, "stats": st}

    # --- overload legs: shed vs noshed goodput -----------------------
    # capacity + idle latency + the per-mult DISTINCT prompt sets are
    # produced once after warmup (below); closed over via these
    load = {"n_base": 16, "window_s": 1.0, "deadline_ms": 100.0}
    traffic = {}  # mult -> (slo_prompts, slo_rows, abandoner_prompts)

    def overload_leg(mult, shed):
        srv = fresh_server(shed)
        if shed:
            assert srv.expected_service_ms() is not None, (
                "costmodel not calibrated — the shed leg would "
                "silently degrade to no-shed")
        registry = ModelRegistry()
        # max_inflight = lane count: a forwarded request is a lane
        # occupant, so "ahead of you" in the shed predicate counts
        # real contention, not a router-side buffer
        registry.load("gen", srv, warm=False, max_inflight=n_slots)
        router = Router(registry)
        router.add_tenant("fd", max_queue=4096)
        slo_p, slo_r, ab_p = traffic[mult]
        n_offered = int(round(mult * load["n_base"]))
        gap = load["window_s"] / n_offered
        ddl = load["deadline_ms"]
        pend, abandoners = [], []
        n_shed = n_qfull = n_cancelled = 0
        i_slo = i_ab = 0
        try:
            t0 = time.perf_counter()
            for i in range(n_offered):
                if i % 3 == 2:
                    # cancel-heavy slice: stream a (fresh) decode,
                    # the cancel fires below once its first burst
                    # lands
                    abandoners.append(srv.submit(
                        ab_p[i_ab], stream=True))
                    i_ab += 1
                else:
                    try:
                        pend.append((router.submit(
                            "fd", "gen", slo_p[i_slo],
                            deadline_ms=ddl), i_slo))
                    except DeadlineUnmeetable:
                        n_shed += 1
                    except AdmissionError:
                        n_qfull += 1
                    i_slo += 1
                live = []
                for rep in abandoners:
                    if rep.ttft_s is not None:
                        if rep.cancel():
                            n_cancelled += 1
                    else:
                        live.append(rep)
                abandoners = live
                # absolute schedule: offered rate stays mult x base
                # even when a submit/cancel pass runs long
                lag = t0 + (i + 1) * gap - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
            for rep in abandoners:  # still pre-first-burst: cancel
                if rep.cancel():    # queued (or just-live) teardown
                    n_cancelled += 1
                try:
                    rep.result(60.0)
                except Exception:
                    pass
            n_ok = n_deadline = 0
            for fut, pi in pend:
                try:
                    row = np.asarray(fut.result(120.0))
                except Exception:
                    n_deadline += 1
                    continue
                assert np.array_equal(row, slo_r[pi]), (
                    f"goodput leg decode diverged from oracle on "
                    f"prompt {pi}")
                n_ok += 1
            wall = time.perf_counter() - t0
            st = srv.stats()
            pst = srv.pool_stats()
            router.close()
            print(f"# frontdoor {'shed' if shed else 'noshed'}_"
                  f"{mult}x: ok={n_ok}/{n_offered} shed={n_shed} "
                  f"expired={n_deadline} cancelled={n_cancelled} "
                  f"wall={wall:.2f}s goodput={n_ok / wall:.1f} rps",
                  file=sys.stderr)
            assert_drained(srv, f"{'shed' if shed else 'noshed'}_"
                                f"{mult}x")
        finally:
            registry.close()
        return {"wall_s": wall, "goodput_rps": n_ok / wall,
                "ok": n_ok, "offered": n_offered, "shed": n_shed,
                "queue_full": n_qfull, "cancelled": n_cancelled,
                "expired": n_deadline, "stats": st, "pool": pst}

    legs = [("stream", stream_leg), ("whole", whole_leg)]
    for m in (1, 2, 4):
        legs.append((f"shed_{m}x",
                     lambda m=m: overload_leg(m, True)))
        legs.append((f"noshed_{m}x",
                     lambda m=m: overload_leg(m, False)))

    try:
        # warmup: one saturated burst compiles the serve tier and
        # calibrates the costmodel; repeating the pool hits the
        # radix admission tier (plain retirements adopted the
        # prefixes); idle capacity + latency scale the offered load
        warm = fresh_server(shed=True)
        try:
            for _pass in range(2):  # compiles: miss tier, then the
                #                     radix tier the adoptions feed
                reps = [warm.submit(p) for p in mix_prompts]
                rows = [np.asarray(r.result(120.0)) for r in reps]
            for k in range(len(mix_prompts)):
                assert np.array_equal(rows[k], mix_rows[k])
            # TRUE capacity: timed saturated passes over FRESH rows
            # once everything is warm — timing the compile passes
            # would understate capacity several-fold, and re-running
            # the warm pool would hit its radix adoptions and
            # OVERSTATE it just as badly
            cap_p = rng.randint(3, V, (48, S)).astype(np.int64)
            t0 = time.perf_counter()
            reps = [warm.submit(p) for p in cap_p]
            for r in reps:
                r.result(120.0)
            cap_wall = time.perf_counter() - t0
            lat = []
            for p in rng.randint(3, V, (8, S)).astype(np.int64):
                t1 = time.perf_counter()
                warm.submit(p).result(120.0)
                lat.append(time.perf_counter() - t1)
            svc = warm.expected_service_ms()
            assert svc is not None and svc > 0, (
                "costmodel did not calibrate from the warmup burst")
            assert_drained(warm, "warmup")
        finally:
            warm.close()
        cap_rps = len(cap_p) / cap_wall
        idle_lat_ms = 1e3 * float(np.median(lat))
        del rows, reps
        # the SLO in the CONTROLLER'S units: the shed predicate
        # compares svc_est x queue-depth against the deadline, so a
        # deadline of 5 x svc_est makes the threshold land at ~16
        # outstanding — reachable under real overload. (Stating it as
        # k x measured idle latency does not: the estimator omits
        # fixed host dispatch cost, runs ~2x low on this host, and
        # the implied depth drifts past what the router's cheap
        # expiry of queued requests lets the queue ever reach.)
        load["deadline_ms"] = 5.0 * svc
        # sustain the overload well past both the transient and the
        # deadline, or the no-shed leg drains its whole backlog
        # before the expiry regime ever sets in
        window_s = max(0.8, 15 * load["deadline_ms"] / 1e3)
        n_base = int(round(cap_rps * window_s))
        if n_base > 150:  # bound the 4x leg's request count
            n_base = 150
            window_s = n_base / cap_rps
        load["n_base"] = max(16, n_base)
        load["window_s"] = window_s
        print(f"# frontdoor: capacity {cap_rps:.1f} rps, idle "
              f"latency {idle_lat_ms:.1f} ms, svc_est {svc:.1f} ms, "
              f"deadline {load['deadline_ms']:.1f} ms, window "
              f"{window_s:.2f} s, n_base {load['n_base']}",
              file=sys.stderr)

        # per-mult DISTINCT traffic (fresh random rows decode long
        # with high probability — no planted EOS, no repeats, so no
        # radix-tier resumption inside a measured leg)
        for m in (1, 2, 4):
            n_off = int(round(m * load["n_base"]))
            n_ab = n_off // 3
            trng = np.random.RandomState(100 + m)
            slo_p = trng.randint(
                3, V, (n_off - n_ab, S)).astype(np.int64)
            ab_p = trng.randint(3, V, (n_ab, S)).astype(np.int64)
            traffic[m] = (slo_p, oracle_many(slo_p), ab_p)

        for _name, fn in legs:  # warm round: remaining compiles
            fn()                # (router path, radix admissions)
        compiles_before = exe.compile_count
        rounds = _harness.interleave_rounds(legs, rounds=3)
        steady_compiles = exe.compile_count - compiles_before
        assert steady_compiles == 0, (
            f"steady-state legs compiled {steady_compiles}")

        ratios = {m: _harness.paired_ratio_max(
            rounds, f"shed_{m}x", f"noshed_{m}x",
            value=lambda r: r["goodput_rps"]) for m in (1, 2, 4)}
        for m in (2, 4):
            assert ratios[m] > 1.0, (
                f"shedding did not beat no-shed at {m}x overload in "
                f"any paired round: {ratios[m]:.3f}")
        ttft_ratio = min(
            np.percentile(r["stream"]["ttft_ms"], 50)
            / np.percentile(r["whole"]["ttft_ms"], 50)
            for r in rounds)
        assert ttft_ratio < 1.0, (
            f"streamed first-burst TTFT p50 {ttft_ratio:.2f}x the "
            f"whole-response latency — streaming bought nothing")

        sbest = _harness.best_leg(rounds, "stream")
        wbest = _harness.best_leg(rounds, "whole")
        shed4 = _harness.best_leg(
            rounds, "shed_4x", key=lambda r: -r["goodput_rps"])
        noshed4 = _harness.best_leg(
            rounds, "noshed_4x", key=lambda r: -r["goodput_rps"])
        inc_rep = obs.incident_report()
        inc = inc_rep["incidents"]
        # the deque retains the LAST max_incidents timelines — by the
        # final leg's drain tail that window is deadline-heavy, so
        # carry the all-legs total beside the window histogram
        n_canc_inc = sum(1 for e in inc
                         if e.get("reason") == "cancelled")
        n_ddl_inc = sum(1 for e in inc
                        if e.get("reason") == "deadline")
        result = {
            "metric": "frontdoor_goodput_shed_over_noshed_4x",
            "value": round(ratios[4], 3),
            "unit": "x",
            "goodput_rps": {
                f"{m}x": {
                    "shed": round(_harness.best_leg(
                        rounds, f"shed_{m}x",
                        key=lambda r: -r["goodput_rps"])
                        ["goodput_rps"], 1),
                    "noshed": round(_harness.best_leg(
                        rounds, f"noshed_{m}x",
                        key=lambda r: -r["goodput_rps"])
                        ["goodput_rps"], 1),
                    "paired_ratio": round(ratios[m], 3),
                } for m in (1, 2, 4)},
            "ttft_ms": {
                "streamed_p50": round(float(np.percentile(
                    sbest["ttft_ms"], 50)), 2),
                "streamed_p99": round(float(np.percentile(
                    sbest["ttft_ms"], 99)), 2),
                "whole_p50": round(float(np.percentile(
                    wbest["ttft_ms"], 50)), 2),
                "whole_p99": round(float(np.percentile(
                    wbest["ttft_ms"], 99)), 2),
                "paired_p50_ratio": round(float(ttft_ratio), 3),
            },
            "token_parity_streamed_vs_whole": True,  # per leg
            "token_parity_vs_oracle": True,          # per leg
            "pools_drained_to_free_every_leg": True,  # asserted
            "steady_state_compiles": int(steady_compiles),
            "shed_4x": {k: shed4[k] for k in
                        ("ok", "offered", "shed", "cancelled",
                         "expired")},
            "noshed_4x": {k: noshed4[k] for k in
                          ("ok", "offered", "shed", "cancelled",
                           "expired")},
            "incidents": {"total": inc_rep["incidents_total"],
                          "retained": len(inc),
                          "retained_cancelled": n_canc_inc,
                          "retained_deadline": n_ddl_inc},
            "offered_load": {
                "capacity_rps": round(cap_rps, 1),
                "idle_latency_ms": round(idle_lat_ms, 2),
                "service_estimate_ms": round(svc, 2),
                "deadline_ms": round(load["deadline_ms"], 2),
                "n_base": load["n_base"],
                "window_s": round(load["window_s"], 3),
                "abandoner_fraction": 1 / 3},
            "workload": "cancel-heavy open loop at 1x/2x/4x offered "
                        "load, every prompt distinct; every 3rd "
                        "request streamed + cancelled after first "
                        "burst, rest carry deadline_ms = 5 x the "
                        "calibrated service estimate",
            "cache": {"block_size": BS, "n_blocks": NB,
                      "n_prompt_entries": E},
            "model": f"transformer d{D} L{L} S{S} maxT{maxT}, "
                     f"{n_slots} lanes, paged",
            "best_of": 3,
        }
        return _write_bench_self("BENCH_SELF_r20.json", result,
                                 stats_json_dict=shed4["stats"])
    finally:
        set_flags({"FLAGS_observability": prev_obs})


# opt-in configs (argv-selectable only; never in the driver's default
# window)
EXTRA_BENCHES = {"transformer_scan": bench_transformer_scan,
                 "moe_transformer": bench_moe_transformer,
                 "transformer_fused": bench_transformer_fused,
                 "transformer_scan_fused": bench_transformer_scan_fused,
                 "serving": bench_serving,
                 "coldstart": bench_coldstart,
                 "generation": bench_generation,
                 "paged": bench_paged,
                 "speculative": bench_speculative,
                 "speculative_adaptive": bench_speculative_adaptive,
                 "sharded": bench_sharded,
                 "multitenant": bench_multitenant,
                 "multiturn": bench_multiturn,
                 "prefill": bench_prefill,
                 "frontdoor": bench_frontdoor}


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "_coldstart_child":
        # internal: spawned by bench_coldstart
        _coldstart_child(sys.argv[2], sys.argv[3], int(sys.argv[4]))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "_sharded_child":
        # internal: spawned by bench_sharded with the 8-virtual-device
        # XLA_FLAGS (device count is fixed at backend init, so the
        # parent cannot host the mesh itself)
        print(json.dumps(_bench_sharded_impl(int(sys.argv[2]))),
              flush=True)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "trend":
        # perf-trend sentinel over the committed BENCH_SELF history
        # (benchmark/trend.py): pure file processing, never touches
        # JAX. Exit 2 on a regressed/stale store; --write-trend
        # refreshes intentionally.
        from benchmark import trend

        sys.exit(trend.main(sys.argv[2:]))
    # This one process is the only one that may use the chip; the
    # backend starts when the first config touches JAX (configs that
    # pin the CPU must do so before that). Every result names the
    # device it ran on.
    from paddle_tpu.core.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    only = sys.argv[1] if len(sys.argv) > 1 else None
    benches = list(BENCHES)
    if only in EXTRA_BENCHES:
        benches = [(only, EXTRA_BENCHES[only])]
    failed = []
    for name, fn in benches:
        if only and name != only:
            continue
        try:
            res = fn()
        except Exception:  # one config failing must not hide others
            traceback.print_exc()
            print(f"# {name} FAILED", file=sys.stderr)
            failed.append(name)
            continue
        import jax

        dev = jax.devices()[0]
        device = f"{dev.platform}/{dev.device_kind}x{jax.device_count()}"
        res.setdefault("device", {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": jax.device_count()})
        print(json.dumps(res), flush=True)
        if "loss0" in res:
            print(f"# {name}: device={device} loss {res['loss0']:.4f}"
                  f"->{res['loss1']:.4f} "
                  f"decreased={res['loss_decreased']}",
                  file=sys.stderr)
        else:
            print(f"# {name}: device={device} "
                  f"{res['value']} {res['unit']}", file=sys.stderr)
    if failed:
        sys.exit(f"bench: {len(failed)} config(s) failed: "
                 f"{', '.join(failed)}")


if __name__ == "__main__":
    main()
