"""Operations and bytes from shapes, each against a hand count at a
small size; the table of peaks refuses a device it does not know."""
import pytest

from benchmark.chip import peaks, shapes

# d=4, f=8, 1 layer, vocabulary 10; a multiply-add is two operations
C = {"d_model": 4, "d_inner": 8, "n_heads": 2, "n_layers": 1,
     "vocab": 10}


def test_encoder_flops_by_hand():
    # q, k, v, out: 4 matrices of 4x4 -> 2*4*16 = 128
    # scores and weighted sum over 3 positions: 2*2*3*4 = 48
    # feed-forward 4x8 and 8x4: 2*2*32 = 128
    assert shapes.encoder_flops_per_token(C, 3) == 128 + 48 + 128


def test_decoder_flops_by_hand():
    # self: 128 + attend over 2 positions 2*2*2*4 = 32
    # cross: q and out 2*2*16 = 64, attend over 3 source 48,
    #        with k, v of the source another 64
    # feed-forward 128; output table 2*4*10 = 80
    without = 128 + 32 + 64 + 48 + 128 + 80
    assert shapes.decoder_flops_per_token(C, 3, 2, False) == without
    assert shapes.decoder_flops_per_token(C, 3, 2, True) == without + 64


def test_train_flops_by_hand():
    # sentence pairs of 3 + 3 positions; causal mean length (3+1)/2 = 2
    fwd = (128 + 48 + 128) + (128 + 32 + 64 + 48 + 64 + 128 + 80)
    assert shapes.train_flops_per_target_token(C, 3) == 3 * fwd


def test_serve_flops_by_hand():
    # replies of 5 positions (4 output tokens), mean cache length 2.5
    dec = 128 + 2 * 2 * 2.5 * 4 + 64 + 48 + 128 + 80
    # a miss: 3 source tokens through the encoder and the cross k, v
    prefill = 3 * ((128 + 48 + 128) + 2 * 2 * 16)
    assert shapes.serve_flops_per_output_token(C, 3, 5, 0.0) == dec
    assert shapes.serve_flops_per_output_token(C, 3, 5, 1.0) \
        == pytest.approx(dec + prefill / 4)
    assert shapes.serve_flops_per_output_token(C, 3, 5, 0.5) \
        == pytest.approx(dec + prefill / 8)


def test_decode_tick_bytes_by_hand():
    # decoder layer: 8 d*d attention weights (self 4, cross 4) = 128,
    # feed-forward 2*4*8 = 64, biases 8 + 4, three norms 6*4 = 24
    # -> 228 floats; output table 40 floats; 4 bytes each
    weights = 4 * (228 + 40)
    assert shapes.decoder_weight_bytes(C) == weights
    # a lane reads keys and values: 2 * 1 layer * d=4 floats a position
    per_pos = 2 * 1 * 4 * 4
    assert shapes.decode_tick_min_bytes(C, 3, 5, 7) \
        == weights + 3 * per_pos * (5 + 7)


def test_kernel_call_bytes_by_hand():
    # result bf16[8,10] written, operands bf16[8,10] and s32[8,1]
    # read; f32[8,1] sits in another memory space (S(1)) and moves
    # nothing through HBM; the attributes repeat shapes and do not count
    text = ("%xent_backward.1 = bf16[8,10]{1,0:T(8,128)(2,1)} "
            "custom-call(bf16[8,10]{1,0} %a, s32[8,1]{1,0} %b, "
            "f32[8,1]{1,0:T(8,128)S(1)} %c), custom_call_target="
            "\"tpu_custom_call\", operand_layout_constraints="
            "{bf16[8,10]{1,0}, s32[8,1]{1,0}, f32[8,1]{1,0}}")
    assert shapes.hlo_call_bytes(text) == 160 + 160 + 32
    # a tuple of results, a scalar operand
    text = "%k.2 = (f32[4]{0}, bf16[2,2]{1,0}) custom-call(f32[]{} %s)"
    assert shapes.hlo_call_bytes(text) == 16 + 8 + 4
    with pytest.raises(ValueError):
        shapes.hlo_call_bytes("%k = q7[4]{0} custom-call(f32[4]{0} %a)")


def test_kernel_roofline_share_by_hand():
    chip = {"flops_per_s": 100.0, "hbm_bytes_per_s": 64.0}
    tr = {"devices": 1,
          "op_s": {"k_fwd_f32_4": 1.0, "k_bwd_f32_4": 3.0, "other": 9.0},
          "op_count": {"k_fwd_f32_4": 2, "k_bwd_f32_4": 2, "other": 1},
          "signatures": {
              "k_fwd_f32_4": "%k_fwd.1 = f32[4]{0} custom-call(f32[4]{0} %a)",
              "k_bwd_f32_4": "%k_bwd.1 = f32[4]{0} custom-call("
                             "f32[4]{0} %a, f32[4]{0} %b)",
              "other": "%o = f32[1]{0} fusion(f32[1]{0} %a)"}}
    # forward: 2 calls x 32 bytes, backward: 2 x 48 -> 160 bytes, 2.5 s
    assert shapes.kernel_roofline_share(tr, "k_", chip) \
        == pytest.approx(100 * 2.5 / 4.0)
    assert shapes.kernel_roofline_share(tr, "absent", chip) is None


def test_roofline_is_the_larger_bound():
    chip = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert shapes.roofline_seconds({"flops": 200, "bytes": 10}, chip) == 2
    assert shapes.roofline_seconds({"flops": 200, "bytes": 50}, chip) == 5


def test_published_peaks_and_unknown_device():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9}
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peak("cpu")
