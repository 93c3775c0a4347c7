"""MobileNetV2 1.0 at published widths: the default graph is Table 2 of
Sandler et al. 2018, adding it leaves every other network's graph and the
DSE's points as they were, and the served path pads channel counts that
are not a multiple of the VTA block inside the program only: a tiny
24-channel graph and the real graph at resolution 32 (every channel width
as published, feature maps 16 down to 1) are bit-exact against the
benchmark's plain numpy reference, with every pad channel zero."""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.serve.model import ServedModel, device_graph, served_model
from repro.vta import fsim_jax
from repro.vta.backend import get_backend
from repro.vta.graph import Graph
from repro.vta.isa import DEFAULT_VTA
from repro.vta.workloads import (MOBILENET_V2_BLOCKS, _inverted_residual,
                                 mobilenet_v2_graph, network_fingerprint,
                                 network_graph, pad_for_blocking,
                                 resolve_network)

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness, make_config, reference  # noqa: E402

# Table 2 of arXiv 1801.04381 after the stem: (t, c, n, s)
TABLE_2 = [(1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
           (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]


def test_the_default_graph_is_table_2_of_the_paper():
    assert list(MOBILENET_V2_BLOCKS) == TABLE_2
    g = mobilenet_v2_graph()
    nodes = g.nodes
    assert nodes["mbv2.conv1"].shape == (1, 32, 112, 112)
    assert nodes["mbv2.conv1"].on_cpu
    size, fi, i, adds = 112, 32, 0, 0
    for t, c, n, s in TABLE_2:
        for j in range(n):
            stride, name = (s if j == 0 else 1), f"mbv2.b{i}"
            dw, pj = nodes[f"{name}.dw"], nodes[f"{name}.project"]
            assert (f"{name}.expand" in nodes) == (t != 1)
            src = name.replace(f"b{i}", f"b{i - 1}") if i else "mbv2.conv1"
            if t != 1:
                ex = nodes[f"{name}.expand"]
                assert (ex.layer.wl.kh, ex.layer.wl.fi, ex.shape[1]) == \
                    (1, fi, fi * t)
                assert ex.layer.post_op == "relu_shift"
                assert dw.inputs == (ex.name,)
            wl = dw.layer.wl
            assert (wl.kh, wl.ph, wl.sh, wl.h, wl.fi) == \
                (3, 1, stride, size, fi * t)
            assert dw.layer.post_op == "relu_shift"
            assert dw.shape == (1, fi * t, size // stride, size // stride)
            assert (pj.layer.wl.kh, pj.shape[1]) == (1, c)
            assert pj.layer.post_op == "clip_shift"   # linear bottleneck
            residual = stride == 1 and fi == c
            assert (f"{name}.add" in nodes) == residual
            out = f"{name}.add" if residual else pj.name
            if residual:
                skip = [s_ for s_ in nodes[out].inputs if s_ != pj.name]
                assert nodes[out].layer.post_op == "clip"
                assert len(skip) == 1 and skip[0] in (
                    f"{src}.add", f"{src}.project", "mbv2.conv1")
                adds += 1
            size //= stride
            fi, i = c, i + 1
    assert (i, adds, size) == (17, 10, 7)
    assert nodes["mbv2.conv2"].shape == (1, 1280, 7, 7)
    assert nodes["mbv2.conv2"].layer.post_op == "relu_shift"
    assert nodes["mbv2.gap"].layer.wl.kh == 7
    fc = nodes["mbv2.fc"]
    assert fc.shape == (1, 1008, 1, 1) and fc.layer.bias
    assert fc.layer.post_op == "none"
    weighted = [n for n in g.topo()
                if n.kind in ("conv", "depthwise", "dense") and not n.on_cpu]
    assert len(weighted) == 52


@pytest.mark.parametrize("alias", ["mobilenetv2", "mobilenet_v2",
                                   "mobilenet-v2", "MobileNetV2",
                                   "mobilenetv2-1.0"])
def test_the_aliases_name_the_network(alias):
    assert resolve_network(alias) == "mobilenetv2-1.0"
    assert repr(network_graph(alias).describe()) == \
        repr(mobilenet_v2_graph().describe())


@pytest.mark.parametrize("batch", [1, 2])
def test_the_default_resolution_is_224(batch):
    assert repr(mobilenet_v2_graph(batch).describe()) == \
        repr(mobilenet_v2_graph(batch, resolution=224).describe())


# the fingerprints before MobileNetV2 was added
FINGERPRINTS = {
    ("resnet18", 1): "fcda9da70c856937", ("resnet18", 2): "937134f6afad2c8f",
    ("resnet34", 1): "0b580f03eeb262d0", ("resnet34", 2): "f197e219e0a11b39",
    ("resnet50", 1): "24b0e47d8c726784", ("resnet50", 2): "297a3488f3c63c9d",
    ("resnet101", 1): "f1b4fc1539f37a32",
    ("resnet101", 2): "1c5e7c7e013259da",
    ("mobilenet1.0", 1): "b3e93c7e29adf725",
    ("mobilenet1.0", 2): "c40e0c9e3e9124f8"}


@pytest.mark.parametrize("net,batch", sorted(FINGERPRINTS))
def test_every_other_network_keeps_its_fingerprint(net, batch):
    assert network_fingerprint(net, batch) == FINGERPRINTS[(net, batch)]


def test_the_dse_points_are_unchanged(tmp_path):
    """A small sweep of both DSE networks gives the points it gave before
    (``bench_pareto.points_digest`` over the cached records)."""
    from benchmarks.bench_pareto import _collect_records, points_digest
    from repro.core.dse import run_sweep
    run_sweep(["resnet18", "mobilenet"], out_dir=str(tmp_path),
              log_blocks=(4,), mem_widths=(8, 64), spad_scales=(1,),
              workers=1, tune="off")
    assert points_digest(_collect_records(str(tmp_path))) == DSE_DIGEST


DSE_DIGEST = "3716432d63b61e662956e99a4cb38e6feba48baf9fdad5ae65a8f4d94ae89d03"


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------
def _tiny_unaligned_graph() -> Graph:
    """Two inverted residual blocks at 24 channels on 8x8: expand to 48
    and 144, depthwise, project back to 24, each joined to its input by a
    residual add; the served input and output are 24 channels too."""
    g = Graph(name="mbv2-tiny24")
    prev = g.input("x", (1, 24, 8, 8)).name
    prev = _inverted_residual(g, "t0", prev, 1, 8, 24, 24, 2, 1)
    prev = _inverted_residual(g, "t1", prev, 1, 8, 24, 24, 6, 1)
    g.validate()
    return g


def _config_of(graph, target_rms: float) -> dict:
    inp = next(n for n in graph.topo() if n.kind == "input")
    return make_config.calibrate(
        {"input_name": inp.name, "input_shape": list(inp.shape[1:]),
         "input_range": [-64, 64],
         "layers": harness.layers_from_graph(graph)}, target_rms)


def _seeded(config: dict, seed: int, n_images: int) -> tuple:
    rng = np.random.default_rng(seed)
    weights = {name: rng.integers(lo, hi + 1, shape).astype(dt)
               for name, shape, lo, hi, dt in harness.weight_specs(config)}
    images = rng.integers(-64, 65, (n_images, 1)
                          + tuple(config["input_shape"])).astype(np.int8)
    return weights, images


class _Recorder:
    """A backend passed by instance: keeps every tensor each dispatch
    returns, at its DRAM shape."""

    def __init__(self, model, inner: str):
        self.model, self.inner = model, get_backend(inner)
        self.name = self.inner.name
        self.seen: dict = {}

    def run_batched(self, prog, hw, *, shared, batched):
        outs = self.inner.run_batched(prog, hw, shared=shared,
                                      batched=batched)
        for t, v in outs.items():
            v = np.asarray(v)
            self.seen[t] = v.reshape((v.shape[0],) + self.model.dram_shapes[t])
        return outs


def _padded_tensors(model, seen: dict) -> set:
    """The tensors of ``seen`` with pad channels; fails unless every pad
    channel is zero."""
    padded = set()
    for t, v in seen.items():
        c = model.shapes[t][1]
        assert not v[:, :, c:].any(), t
        if v.shape[2] > c:
            padded.add(t)
    return padded


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_a_24_channel_graph_is_padded_inside_the_program_only(backend):
    graph = _tiny_unaligned_graph()
    config = _config_of(graph, 12)
    weights, images = _seeded(config, 2**31 + 19, 3)
    model = ServedModel.compile("mbv2-tiny24", graph, DEFAULT_VTA)
    # published shapes outside, padded ones inside
    assert model.image_shape == model.output_shape == (1, 24, 8, 8)
    assert model.dram_shapes["x"] == model.dram_shapes["t1.add"] == \
        (1, 32, 8, 8)
    assert model.dram_shapes["t0.project.wgt"] == (32, 48, 1, 1)
    assert {k: (v.shape, v.dtype) for k, v in model.weights.items()} == \
        {k: (v.shape, v.dtype) for k, v in weights.items()}
    assert model.weights["t1.expand.wgt"].shape == (144, 24, 1, 1)
    model.weights = weights
    fused = [s for s in model.segments if s.kinds.endswith("+add")]
    assert len(fused) == 2 and all(s.padded == 16 for s in fused)
    ref = reference.forward(config, weights, images[:, 0])
    assert np.abs(ref.astype(int)).max() > 8            # the output has spread
    rec = _Recorder(model, backend)
    batch = np.concatenate([images, np.zeros((5,) + images.shape[1:],
                                             np.int8)])
    out = model.run_batch(batch, backend=rec)
    assert out.shape == (8,) + model.output_shape
    np.testing.assert_array_equal(out[:3, 0], ref)
    # the adds, and the images where the backend returns what it put
    assert _padded_tensors(model, rec.seen) - {"x"} == {"t0.add", "t1.add"}
    np.testing.assert_array_equal(model.run_single(images[0]), ref[:1])
    # the padded weights are made once per array, and anew for a new one
    w = model._padded_weight("t0.expand.wgt")
    assert model._padded_weight("t0.expand.wgt") is w
    assert w.shape == (48, 32, 1, 1) and not w[:, 24:].any()
    model.weights = dict(weights, **{"t0.expand.wgt":
                                     weights["t0.expand.wgt"].copy()})
    assert model._padded_weight("t0.expand.wgt") is not w


def test_an_aligned_model_serves_its_weights_unpadded():
    model = served_model("mobilenet", "tiny")
    assert model.dram_shapes == model.shapes | {
        k: v.shape for k, v in model.weights.items()}
    for seg in model.segments:
        assert seg.padded == 0
        for k, v in model._weights_of(seg).items():
            assert v is model.weights[k]


def _gemm_macs(graph, padded: bool) -> int:
    return sum((pad_for_blocking(n.layer.wl, DEFAULT_VTA) if padded
                else n.layer.wl).macs
               for n in graph.topo() if n.kind in ("conv", "dense"))


@pytest.mark.parametrize("net", ["unaligned", "aligned"])
def test_the_gemm_mac_counter_counts_the_padded_work(net):
    if net == "unaligned":
        model = ServedModel.compile("mbv2-tiny24", _tiny_unaligned_graph(),
                                    DEFAULT_VTA)
    else:
        model = served_model("resnet18", "tiny")
    n = 4
    images = model.random_images(n, seed=5)
    model.run_batch(images, backend="jax")            # traced, warm
    fsim_jax.reset_kernel_launch_log()
    model.run_batch(images, backend="jax")
    want = n * _gemm_macs(model.graph, padded=True)
    assert fsim_jax.gemm_mac_log() == want
    published = n * _gemm_macs(model.graph, padded=False)
    assert (want == published) == (net == "aligned")
    fsim_jax.reset_kernel_launch_log()
    assert fsim_jax.gemm_mac_log() == 0


def test_the_real_graph_at_resolution_32_matches_the_reference():
    """Every published width at 16x16 down to 1x1: the 24-channel stage
    padded to 32, its fused project-add after a resident depthwise ->
    project chain, the 960-channel layers, the 1280-channel conv and the
    gap and fc tail: three seeded images in a batch of 8 padded with
    zeros, bit-exact against the reference, every pad channel zero."""
    graph = device_graph(mobilenet_v2_graph(resolution=32))
    config = _config_of(graph, 24)
    assert max(layer["shape"][0] for layer in config["layers"]) == 1280
    weights, images = _seeded(config, 2**31 + 32, 3)
    model = ServedModel.compile("mobilenetv2-r32", graph, DEFAULT_VTA)
    assert {k: (v.shape, v.dtype) for k, v in model.weights.items()} == \
        {k: (v.shape, v.dtype) for k, v in weights.items()}
    model.weights = weights
    kinds = [s.kinds for s in model.segments]
    assert "depthwise+conv+add" in kinds
    assert kinds.count("conv+add") + kinds.count("depthwise+conv+add") == 10
    ref = reference.forward(config, weights, images[:, 0])
    assert np.abs(ref.astype(int)).max() > 8            # the output has spread
    rec = _Recorder(model, "jax")
    batch = np.concatenate([images, np.zeros((5,) + images.shape[1:],
                                             np.int8)])
    out = model.run_batch(batch, backend=rec)
    np.testing.assert_array_equal(out[:3, 0], ref)
    assert {"mbv2.b1.project", "mbv2.b2.add"} <= \
        _padded_tensors(model, rec.seen)
    assert math.prod(out.shape[2:]) == 1008
