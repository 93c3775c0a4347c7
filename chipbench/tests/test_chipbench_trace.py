"""The trace reduction and the readers of device-trace metrics, on a
synthetic trace: busy union of overlapping ops, idle share, GEMM-class ops
by name, and idle gaps named by the host span open over them."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace_reduce  # noqa: E402
from chipbench.metrics import (alu_ms_per_image, device_idle_share,  # noqa: E402
                               gemm_roofline)

MS = 1_000_000          # ns
GEMM = ("%custom-call.4 = f32[8,256,16]{2,1,0:T(8,128)} custom-call(f32[8,256,"
        "1024]{2,1,0:T(8,128)} %a, f32[8,1024,16]{2,1,0} %b), "
        "custom_call_target=\"tpu_custom_call\"")


def _trace():
    """A 100 ms window. Device ops (ms): a GEMM 10-30, an ALU op 20-40
    overlapping it, another 60-70, one starting before the window and one
    after it. Host: a batch span over 5-95, segment spans 5-45 and 50-95."""
    device = {"/device:TPU:0": [
        (GEMM, 10 * MS, 20 * MS),
        ("%fusion.7 = s8[64]{0:T(1024)} fusion(s8[64]{0} %p), kind=kLoop, "
         "calls=%fused_computation", 20 * MS, 20 * MS),
        ("gather.3", 60 * MS, 10 * MS),
        ("copy.1", -10 * MS, 15 * MS),           # 0-5 inside the window
        ("copy.2", 150 * MS, 10 * MS),           # outside
    ]}
    host = [("chipbench.window", 0, 100 * MS),
            ("chipbench.batch:b8", 5 * MS, 90 * MS),
            ("chipbench.segment:a", 5 * MS, 40 * MS),
            ("chipbench.segment:b", 50 * MS, 45 * MS)]
    return device, host


def test_busy_is_the_union_of_overlapping_ops_inside_the_window():
    red = trace_reduce.reduce(*_trace())
    assert red["window_s"] == pytest.approx(0.100)
    # 0-5, 10-40 (two ops overlapping), 60-70
    assert red["busy_s"] == pytest.approx(0.045)
    assert red["ops"]["copy copy.1"] == pytest.approx(0.005)
    assert "copy.2" not in red["ops"]
    rec = {"trace": red}
    assert device_idle_share.read(rec) == pytest.approx(55.0)


def test_idle_gaps_go_to_the_host_span_open_over_them():
    idle = trace_reduce.reduce(*_trace())["idle"]
    # gaps: 5-10 (segment a), 40-60 (a to 45, batch between 45-50, b from
    # 50), 70-100 (b to 95, then outside a batch)
    assert idle["chipbench.segment:a"] == pytest.approx(0.010)
    assert idle["chipbench.batch"] == pytest.approx(0.005)
    assert idle["chipbench.segment:b"] == pytest.approx(0.035)
    assert idle[trace_reduce.OUTSIDE] == pytest.approx(0.005)
    assert sum(idle.values()) == pytest.approx(0.055)
    gaps = dict(trace_reduce.reduce(*_trace())["breakdown"]["idle_gaps"])
    assert max(gaps, key=gaps.get) == "chipbench.segment:b"


def test_breakdown_groups_ops_by_family_and_keeps_ten():
    device = {"/device:TPU:0": [(f"fusion.{i}", i * MS, MS // 2)
                                for i in range(50)]
              + [(f"op_{chr(97 + i)}.1", 60 * MS + i * MS, MS // 4)
                 for i in range(20)]}
    host = [("chipbench.window", 0, 100 * MS)]
    bd = trace_reduce.reduce(device, host)["breakdown"]
    assert bd["device_ops"][0] == ["fusion", pytest.approx(0.025)]
    assert len(bd["device_ops"]) == trace_reduce.TOP
    assert len(bd["idle_gaps"]) <= trace_reduce.TOP


def test_ops_are_keyed_by_hlo_family_and_name():
    assert trace_reduce.op_key(GEMM) == \
        "custom-call(tpu_custom_call) custom-call.4"
    assert trace_reduce.op_key(
        '%custom-call.62 = s32[8,16]{1,0} custom-call(s32[2,16]{1,0} %a), '
        'custom_call_target="ConcatBitcast"') == \
        "custom-call(ConcatBitcast) custom-call.62"
    assert trace_reduce.op_key(
        "%fusion.20 = s8[73728]{0:T(1024)(128)(4,1)S(1)} fusion(s8[2359296]"
        "{0:T(1024)(128)(4,1)S(1)} %custom-call.64), kind=kCustom, "
        "calls=%fused_computation") == "fusion(kCustom) fusion.20"
    assert trace_reduce.op_key(
        "%t = (s32[4]{0}, s8[2]{0}) convolution(s8[2]{0} %a)") == \
        "convolution t"
    assert trace_reduce.op_key("gather.3") == "gather gather.3"


def test_gemm_class_by_family_and_the_roofline_reader():
    assert gemm_roofline.is_gemm(trace_reduce.op_key(GEMM))
    assert gemm_roofline.is_gemm("convolution convolution.3")
    assert not gemm_roofline.is_gemm("custom-call(ConcatBitcast) c.1")
    assert gemm_roofline.is_gemm("dot dot.2")
    assert not gemm_roofline.is_gemm("fusion(kLoop) fusion.7")
    assert not gemm_roofline.is_gemm("gather gather.3")
    red = trace_reduce.reduce(*_trace())
    config = {"input_name": "x", "input_shape": [16, 8, 8], "layers": [
        {"name": "c", "kind": "conv", "inputs": ["x"], "shape": [16, 8, 8],
         "k": 3, "stride": 1, "pad": 1, "bias": False}]}
    # 8 images done inside the traced window, 8 after it
    reqs = ([{"done": 0.05, "due": 0.0, "submit": 0.0}] * 8
            + [{"done": 0.2, "due": 0.0, "submit": 0.0}] * 8)
    rec = {"trace": red, "config": config, "traffic": {"loop": "closed"},
           "requests": reqs, "t_close": 0.05, "traced": (0.0, 0.1),
           "peaks": {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}
    # 8 images x 2 x 147456 MACs = 2.36e6 ops -> 2.36 us at 1e12; bytes:
    # 2304 weights + 8 x 2048 activations = 18688 B -> 18.7 us at 1e9
    least = max(8 * 2 * 147456 / 1e12, (2304 + 8 * 2048) / 1e9)
    assert gemm_roofline.read(rec) == pytest.approx(100 * least / 0.020)
    # the other ops inside the window: copy 0-5, fusion 20-40, gather
    # 60-70: 5 + 20 + 10 ms over 8 images
    assert alu_ms_per_image.read(rec) == pytest.approx(35.0 / 8)


def test_no_device_ops_reads_nothing():
    host = [("chipbench.window", 0, 100 * MS)]
    red = trace_reduce.reduce({}, host)
    assert red["busy_s"] == 0.0
    rec = {"trace": red, "config": {}, "traffic": {"loop": "closed"},
           "requests": [], "t_close": 0.0, "traced": (0.0, 0.1), "peaks": {}}
    assert device_idle_share.read(rec) is None
    assert gemm_roofline.read(rec) is None
    assert alu_ms_per_image.read(rec) is None
    with pytest.raises(ValueError, match="chipbench.window"):
        trace_reduce.reduce({}, [])


def test_importing_the_reduction_loads_no_accelerator_library():
    import subprocess
    code = ("import sys; sys.path.insert(0, %r); "
            "import chipbench.trace_reduce, chipbench.metrics.gemm_roofline; "
            "bad = [m for m in sys.modules if m.startswith(('jax', 'libtpu'))]; "
            "print(bad); sys.exit(1 if bad else 0)"
            % str(Path(__file__).resolve().parents[2]))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr
