"""The network's work from the configuration files (or, for a network with
no cell yet, from the program's graph), pinned, and the table of peaks."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import work  # noqa: E402


NO_CELL_YET = {"mobilenet1.0-full": "mobilenet"}      # name -> the program's graph


def _config(name):
    path = ROOT / "chipbench/configs" / f"{name}.json"
    if path.exists():
        return json.loads(path.read_text())
    from chipbench.harness import layers_from_graph
    from repro.serve.model import device_graph
    from repro.vta.workloads import network_graph
    graph = device_graph(network_graph(NO_CELL_YET[name]))
    inp = next(n for n in graph.topo() if n.kind == "input")
    return {"input_name": inp.name, "input_shape": list(inp.shape[1:]),
            "layers": layers_from_graph(graph)}


@pytest.mark.parametrize("name,gemm_m,dw_m", [
    ("resnet18-full", 1696.1, 0.0),
    ("mobilenet1.0-full", 540.5, 17.4),
])
def test_macs_of_the_served_bodies(name, gemm_m, dw_m):
    cfg = _config(name)
    assert work.macs_per_image(cfg) / 1e6 == pytest.approx(gemm_m, abs=0.05)
    assert work.macs_per_image(cfg, ("depthwise",)) / 1e6 == \
        pytest.approx(dw_m, abs=0.05)
    assert work.int8_ops_per_image(cfg) == \
        2 * (work.macs_per_image(cfg) + work.macs_per_image(cfg, ("depthwise",)))


def test_gemm_bytes_count_weights_per_batch_and_activations_per_image():
    cfg = _config("resnet18-full")
    one = work.gemm_min_bytes(cfg, images=1, batches=1)
    weights = work.gemm_min_bytes(cfg, images=8, batches=2) - \
        work.gemm_min_bytes(cfg, images=8, batches=1)
    # conv and dense int8 weights plus the fc's int32 bias: 11.67 MB
    assert weights == pytest.approx(11.67e6, rel=0.002)
    assert work.gemm_min_bytes(cfg, images=2, batches=1) - one == one - weights


def test_peaks_refuse_an_unknown_device_kind():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        work.peaks("TPU v9 imaginary")
