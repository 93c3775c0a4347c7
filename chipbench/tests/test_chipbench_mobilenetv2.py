"""The committed MobileNetV2 1.0 configuration: its layer list is the
program's full-width graph, the program accepts it with the benchmark's
weights, and on seeded images at 224 every layer keeps its spread through
the plain reference; the ``gemm_useful_share`` reader reads nothing where
the program has no GEMM MAC counter."""
import json

import numpy as np
import pytest

from _chipbench_fixtures import BENCH, ROOT

from chipbench import harness, reference, work

CONFIG = ROOT / "chipbench/configs/mobilenetv2-1.0-full.json"
# largest share seen 0.0044 (b2.add, the first residual add, where two
# spread-out tensors meet); a body layer that saturates loses the values a
# wrong result would show in
BODY_SATURATED = 0.01
# the fc has no shift, so its sums of 1280 taps saturate often: 0.44 seen,
# below the 0.68 of MobileNet-1.0's fc
FC_SATURATED = 0.5


@pytest.fixture(scope="module")
def config():
    return json.loads(CONFIG.read_text())


def test_the_layers_are_the_programs_full_width_graph(config):
    from repro.serve.model import device_graph
    from repro.vta.workloads import network_graph
    graph = device_graph(network_graph("mobilenetv2-1.0"))
    assert harness._without_weights(config["layers"]) == \
        harness.layers_from_graph(graph)
    assert (config["served"], config["input_shape"], config["num_classes"]) \
        == ({"network": "mobilenetv2-1.0", "scale": "full"}, [32, 112, 112],
            1008)
    assert work.macs_per_image(config) == 269_230_080


def test_the_program_accepts_the_configuration(config):
    specs = harness.weight_specs(config)
    rng = np.random.default_rng(0)
    weights = {name: rng.integers(lo, hi + 1, shape).astype(dt)
               for name, shape, lo, hi, dt in specs}
    model = harness.build_model(config, weights)
    # 53 layers and 10 adds; the adds fuse into their project convs, and
    # b13's depthwise, b16's project and the gap keep their outputs on chip
    assert len(model.segments) == 50
    assert sum(s.kinds.endswith("+add") for s in model.segments) == 10
    assert model.dram_shapes["mbv2.b2.add"] == (1, 32, 56, 56)
    assert model.weights["mbv2.b2.project.wgt"].shape == (24, 144, 1, 1)


def test_every_layer_keeps_its_spread_at_224(config, monkeypatch):
    rng = np.random.default_rng(2**31 + 19)
    weights = {name: rng.integers(lo, hi + 1, shape).astype(dt)
               for name, shape, lo, hi, dt in harness.weight_specs(config)}
    lo, hi = config["input_range"]
    images = rng.integers(lo, hi + 1, (2,) + tuple(config["input_shape"]))
    outs = []
    post = reference._post

    def keep(acc, post_op):                 # one call per layer, in order
        outs.append(post(acc, post_op))
        return outs[-1]
    monkeypatch.setattr(reference, "_post", keep)
    reference.forward(config, weights, images.astype(np.int8))
    assert len(outs) == len(config["layers"])
    for layer, out in zip(config["layers"], outs):
        saturated = np.mean(np.abs(out.astype(int)) >= 127)
        if layer["kind"] in ("conv", "depthwise", "dense"):
            assert out.any(), layer["name"]
        limit = FC_SATURATED if layer["kind"] == "dense" else BODY_SATURATED
        assert saturated <= limit, (layer["name"], saturated)


def _record(batches: int) -> dict:
    return {"config": json.loads(CONFIG.read_text()), "batches": batches,
            "traffic": json.loads((BENCH / "traffic/backlog.json")
                                  .read_text())}


def test_the_gemm_useful_share_reader_needs_the_counter(monkeypatch):
    from repro.vta import fsim_jax
    read = harness.load_reader(BENCH, "gemm_useful_share")
    monkeypatch.delattr(fsim_jax, "gemm_mac_log")
    assert read(_record(3)) is None


@pytest.mark.parametrize("executed,want", [(282_476_544, 95.31059683313033),
                                           (269_230_080, 100.0)])
def test_the_gemm_useful_share_reader_divides_published_by_executed(
        monkeypatch, executed, want):
    """Per image, the padded walk of the program's GEMMs and the
    published count (``tests/test_mobilenetv2_full_width.py`` counts
    them); three batches of eight images."""
    from repro.vta import fsim_jax
    read = harness.load_reader(BENCH, "gemm_useful_share")
    monkeypatch.setattr(fsim_jax, "gemm_mac_log", lambda: executed * 3 * 8)
    assert read(_record(3)) == pytest.approx(want)
    assert read(dict(_record(3), traffic={"buckets": [1, 8]})) is None
    monkeypatch.setattr(fsim_jax, "gemm_mac_log", lambda: 0)
    assert read(_record(3)) is None
