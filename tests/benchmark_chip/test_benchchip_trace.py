"""The reduction from a trace to numbers: on hand-made planes, where
every answer can be worked out, and on a small trace recorded on the
chip (recorded_trace.json.gz: two steps of train_base_s256, as
`trace.dump` wrote them)."""
import os

import pytest

from benchmark.chip import shapes, trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def _planes():
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ["%fusion.1", 0 * MS, 4 * MS],
        ["%copy.7", 3 * MS, 3 * MS],            # 1 ms under the fusion
        ["%xent_forward.2", 10 * MS, 2 * MS],
        ["%fusion.9", 12 * MS, 1 * MS],
    ]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["bench:executor_run", 5 * MS, 6 * MS],
        ["bench:submit", 6 * MS, 1 * MS],
    ]}]}
    return [dev, host]


def test_busy_idle_kernel_and_gap_by_hand():
    r = trace.reduce(_planes(), window_ns=(0, 20 * MS))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.020)
    # busy: [0, 6) and [10, 13) -> 9 ms
    assert r["busy_s"] == pytest.approx(0.009)
    assert r["op_s"]["fusion"] == pytest.approx(0.005)
    assert r["op_s"]["xent_forward"] == pytest.approx(0.002)
    assert r["op_count"]["fusion"] == 2
    assert r["op_s"]["copy"] == pytest.approx(0.003)
    assert r["device_ops"][0] == ["fusion", pytest.approx(0.005)]
    # gaps: [13, 20) 7 ms no span; [6, 10) 4 ms under executor_run
    assert r["idle_gaps"][0] == ["no_span", pytest.approx(0.007)]
    assert r["idle_gaps"][1] == ["executor_run", pytest.approx(0.004)]
    assert r["host_span_s"] == {"executor_run": pytest.approx(0.006),
                                "submit": pytest.approx(0.001)}


def test_window_defaults_to_the_operations_extent_and_devices_average():
    planes = _planes()
    second = {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops",
              "events": [["%fusion.1", 0, 13 * MS]]}]}
    r = trace.reduce(planes + [second])
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(0.013)
    assert r["busy_s"] == pytest.approx((0.009 + 0.013) / 2)


def test_no_device_operation_reads_nothing():
    host_only = [p for p in _planes() if p["name"] == "/host:CPU"]
    assert trace.reduce(host_only)["devices"] == 0


def test_stable_names_drop_the_compilers_counter():
    assert trace.stable_name("%fusion.123") == "fusion"
    assert trace.stable_name("xent_backward.2") == "xent_backward"
    assert trace.stable_name("copy-done") == "copy-done"
    # the chip names an operation by its whole HLO text
    assert trace.stable_name(
        "%fusion.12 = bf16[128,8,256,64]{3,2,1,0:T(8,128)(2,1)} "
        "fusion(bf16[128,8,256,64]{3,2,1,0} %p), kind=kLoop") \
        == "fusion_bf16_128_8_256_64"
    assert trace.stable_name(
        "%divide_subtract_fusion.2 = (f32[512,32000]{1,0}, f32[4]{0}) "
        "fusion(f32[512,32000]{1,0} %w)") \
        == "divide_subtract_fusion_f32_512_32000"
    assert trace.stable_name("%all-reduce.7 = f32[]{:T(128)} "
                             "all-reduce(f32[] %x)") == "all-reduce_f32"
    assert len(trace.stable_name("%" + "x" * 90 + ".1")) <= 64


def test_a_loop_is_busy_time_but_its_children_are_the_operations():
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
               "events": [["while_s32_33", 0, 10 * MS],
                          ["fusion_f32_8", 1 * MS, 3 * MS],
                          ["copy_f32_8", 5 * MS, 4 * MS]]}]}]
    r = trace.reduce(planes)
    assert r["busy_s"] == pytest.approx(0.010)
    assert set(r["op_s"]) == {"fusion_f32_8", "copy_f32_8"}
    assert r["device_ops"][0] == ["copy_f32_8", pytest.approx(0.004)]


def test_a_span_names_a_gap_only_if_it_covers_half_of_it():
    planes = _planes()
    planes[1]["lines"][0]["events"] = [["bench:submit", 6 * MS, 1 * MS]]
    r = trace.reduce(planes, window_ns=(0, 13 * MS),
                     unattributed="server_cycle_unattributed")
    assert r["idle_gaps"] == [["server_cycle_unattributed",
                               pytest.approx(0.004)]]


def test_dump_and_load_round_trip(tmp_path):
    path = str(tmp_path / "t.json.gz")
    trace.dump(_planes(), path)
    assert trace.load(path) == _planes()


def test_recorded_trace_from_the_chip():
    path = os.path.join(HERE, "recorded_trace.json.gz")
    planes = trace.load(path)
    r = trace.reduce(planes)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0.5 < r["busy_s"] / r["window_s"] < 1.0
    # the routed Pallas kernels are there under their own names
    for kernel in ("xent_forward", "xent_backward", "layer_norm"):
        names = [k for k in r["op_s"] if k.startswith(kernel)]
        assert names and all(r["op_s"][k] > 0 for k in names), kernel
        assert all(k in r["signatures"] for k in names)
    share = shapes.kernel_roofline_share(
        r, "xent_", {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert 20 < share <= 100
    # the longest gaps between steps lie under the host's executor_run
    assert r["idle_gaps"][0][0] == "executor_run"
    assert r["host_span_s"]["executor_run"] > 0
