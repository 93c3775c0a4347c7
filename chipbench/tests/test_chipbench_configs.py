"""The committed MobileNet-1.0 configuration: its layer list is the
program's full-width graph, the program accepts it with the benchmark's
weights, and on seeded images at 224 every layer keeps its spread through
the plain reference: no weighted layer dies to zero and almost nothing
saturates."""
import json

import numpy as np
import pytest

from _chipbench_fixtures import ROOT

from chipbench import harness, reference

CONFIG = ROOT / "chipbench/configs/mobilenet1.0-full.json"
BODY_SATURATED = 0.005     # largest share seen 0.0016 (dw11)
FC_SATURATED = 0.75        # the fc has no shift; 0.68 seen, as in resnet18


@pytest.fixture(scope="module")
def config():
    return json.loads(CONFIG.read_text())


def test_the_layers_are_the_programs_full_width_graph(config):
    from repro.serve.model import device_graph
    from repro.vta.workloads import network_graph
    graph = device_graph(network_graph("mobilenet"))
    assert harness._without_weights(config["layers"]) == \
        harness.layers_from_graph(graph)
    assert (config["served"], config["input_shape"], config["num_classes"]) \
        == ({"network": "mobilenet", "scale": "full"}, [32, 112, 112], 1008)


def test_the_program_accepts_the_configuration(config):
    specs = harness.weight_specs(config)
    rng = np.random.default_rng(0)
    weights = {name: rng.integers(lo, hi + 1, shape).astype(dt)
               for name, shape, lo, hi, dt in specs}
    model = harness.build_model(config, weights)
    assert len(model.segments) == 26


def test_every_layer_keeps_its_spread_at_224(config, monkeypatch):
    rng = np.random.default_rng(2**31 + 15)
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
