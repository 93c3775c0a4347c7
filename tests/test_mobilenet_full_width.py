"""MobileNet-1.0 at published widths: the graph at the paper's default
resolution is the one the DSE, its fingerprints and the baselines were
built on, its layer table is the paper's Table 1, and the real graph at
resolution 32 (every channel width as published, feature maps 16 down to
1) served through the jax backend on the CPU is bit-exact against the
benchmark's plain numpy reference."""
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.serve.model import ServedModel, device_graph
from repro.vta.isa import DEFAULT_VTA
from repro.vta.workloads import mobilenet_graph, network_fingerprint

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness, make_config, reference  # noqa: E402

# Table 1 of Howard et al. 2017 after the stem: (depthwise stride, input
# size, channels in, pointwise channels out); the last depthwise at stride
# 1, as every public implementation has it (the table's s2 is a misprint)
TABLE_1 = [(1, 112, 32, 64), (2, 112, 64, 128), (1, 56, 128, 128),
           (2, 56, 128, 256), (1, 28, 256, 256), (2, 28, 256, 512),
           *[(1, 14, 512, 512)] * 5, (2, 14, 512, 1024), (1, 7, 1024, 1024)]


def test_the_default_graph_is_table_1_of_the_paper():
    nodes = {n.name: n for n in mobilenet_graph().topo()}
    assert nodes["mbn.conv1"].shape == (1, 32, 112, 112)
    for i, (s, size, ci, co) in enumerate(TABLE_1):
        dw, pw = nodes[f"mbn.dw{i}"], nodes[f"mbn.pw{i}"]
        assert (dw.layer.wl.sh, dw.layer.wl.h, dw.layer.wl.fi) == (s, size, ci)
        assert dw.shape == pw.shape[:1] + (ci,) + pw.shape[2:] == \
            (1, ci, size // s, size // s)
        assert pw.shape[1] == co and pw.layer.wl.kh == 1
        assert dw.layer.post_op == pw.layer.post_op == "relu_shift"
    assert nodes["mbn.gap"].layer.wl.kh == 7
    assert nodes["mbn.fc"].shape == (1, 1008, 1, 1)


@pytest.mark.parametrize("batch,fingerprint", [(1, "b3e93c7e29adf725"),
                                               (2, "c40e0c9e3e9124f8")])
def test_the_default_resolution_keeps_the_graph_it_was(batch, fingerprint):
    assert network_fingerprint("mobilenet", batch) == fingerprint
    assert repr(mobilenet_graph(batch).describe()) == \
        repr(mobilenet_graph(batch, resolution=224).describe())


def test_the_real_graph_at_resolution_32_matches_the_reference():
    """Stride-2 padded depthwise layers with relu_shift, dw->pw resident
    edges at stride 2, the 1024-channel layers, and the gap (window 1) and
    fc tail: three seeded images in a batch of 8 padded with zeros, and
    each alone through ``run_single``, bit-exact against the reference."""
    graph = device_graph(mobilenet_graph(resolution=32))
    inp = next(n for n in graph.topo() if n.kind == "input")
    config = make_config.calibrate(
        {"input_name": inp.name, "input_shape": list(inp.shape[1:]),
         "input_range": [-64, 64],
         "layers": harness.layers_from_graph(graph)}, 24)
    assert max(layer["shape"][0] for layer in config["layers"]) == 1024
    rng = np.random.default_rng(2**31 + 32)
    weights = {name: rng.integers(lo, hi + 1, shape).astype(dt)
               for name, shape, lo, hi, dt in harness.weight_specs(config)}
    images = rng.integers(-64, 65, (3, 1) + inp.shape[1:]).astype(np.int8)
    model = ServedModel.compile("mobilenet-r32", graph, DEFAULT_VTA)
    assert {k: (v.shape, v.dtype) for k, v in model.weights.items()} == \
        {k: (v.shape, v.dtype) for k, v in weights.items()}
    model.weights = weights
    ref = reference.forward(config, weights, images[:, 0])
    assert np.abs(ref.astype(int)).max() > 8            # the output has spread
    padded = np.concatenate([images, np.zeros((5,) + images.shape[1:],
                                              np.int8)])
    out = model.run_batch(padded, backend="jax")
    np.testing.assert_array_equal(out[:3, 0], ref)
    for i in range(3):
        np.testing.assert_array_equal(model.run_single(images[i]),
                                      ref[i:i + 1])
