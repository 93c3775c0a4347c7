"""Shared pieces of the chipbench tests: a benchmark tree of tiny served
models in a temporary directory, and a stand-in for the device."""
from __future__ import annotations

import json
import shutil
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness, make_config  # noqa: E402

BENCH = ROOT / "chipbench"
CPU = types.SimpleNamespace(platform="cpu", device_kind="cpu", count=1)
PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def tiny_config(name: str, network: str) -> dict:
    """A configuration file for the program's ``tiny`` served ``network``,
    its weight ranges set as ``make_config`` sets them."""
    from repro.serve.model import SERVE_GRAPHS
    graph = SERVE_GRAPHS[network]("tiny")
    inp = next(n for n in graph.topo() if n.kind == "input")
    config = {"name": name, "served": {"network": network, "scale": "tiny"},
              "input_name": inp.name, "input_shape": list(inp.shape[1:]),
              "input_range": [-64, 64], "check_sample": 16,
              "layers": harness.layers_from_graph(graph)}
    return make_config.calibrate(config, 12)


def tiny_root(tmp: Path, cells: dict) -> Path:
    """A benchmark tree under ``tmp`` with the real traffic mixes and
    metric readers: ``cells`` maps a workload name to (network, traffic).
    Each network gets a tiny configuration named ``<network>-tiny``."""
    bench = tmp / "chipbench"
    (bench / "configs").mkdir(parents=True)
    for part in ("traffic", "generators", "metrics"):
        shutil.copytree(BENCH / part, bench / part)
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = {k: real[k] for k in ("command", "paths", "run_seconds",
                                 "end_to_end", "per_layer")}
    spec["configs"], spec["workloads"] = [], []
    for net in sorted({n for n, _ in cells.values()}):
        file = f"chipbench/configs/{net}-tiny.json"
        (tmp / file).write_text(json.dumps(tiny_config(f"{net}-tiny", net)))
        spec["configs"].append({"name": f"{net}-tiny", "source": "test",
                                "file": file, "reduced": [], "why": "test"})
    for name, (net, traffic) in cells.items():
        spec["workloads"].append({"name": name, "config": f"{net}-tiny",
                                  "traffic": traffic, "chips": 1,
                                  "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)        # every metric applies to the tiny cells
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: Path, workload: str, **kw) -> dict:
    kw.setdefault("seed", 2**31 + 11)
    kw.setdefault("seconds", 1.0)
    kw.setdefault("trace", False)
    return harness.run_cell(root, workload, device=CPU, peaks=PEAKS,
                            log=lambda s: None, **kw)
