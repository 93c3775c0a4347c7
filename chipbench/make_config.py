"""Write a configuration file from the program's graph of a network.

    PYTHONPATH=src python chipbench/make_config.py --network resnet18 \
        --scale full --name resnet18-full --target-rms 12 > out.json

The layer list is the program's graph (``harness.layers_from_graph``); each
weighted layer's integer weight range is then set, layer by layer on two
images, to the narrowest symmetric range whose output reaches
``--target-rms`` (widened upward to ``[lo, 127]`` where even +-127 does
not), so that activations keep their spread through the whole network and
a wrong value anywhere shows in the output. Dense layers take +-1.
The file's other keys (source, reduced, assumed, check_sample) are written
by hand.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:1] = [str(ROOT), str(ROOT / "src")]


def calibrate(config: dict, target_rms: float, n_images: int = 2,
              seed: int = 0) -> dict:
    import numpy as np

    from chipbench import reference
    rng = np.random.default_rng(seed)
    lo, hi = config["input_range"]
    acts = {config["input_name"]: rng.integers(
        lo, hi + 1, (n_images,) + tuple(config["input_shape"])).astype(np.int8)}
    chans = {config["input_name"]: config["input_shape"][0]}
    for layer in config["layers"]:
        name, kind = layer["name"], layer["kind"]
        chans[name] = layer["shape"][0]
        one = {"input_name": "x", "layers": [dict(layer, inputs=["x"])]}

        def out(w_lo, w_hi):
            k, fo = layer.get("k", 1), layer["shape"][0]
            shape = ((fo, k, k) if kind == "depthwise"
                     else (fo, chans[layer["inputs"][0]], k, k))
            w = {f"{name}.wgt": np.random.default_rng(1).integers(
                w_lo, w_hi + 1, shape).astype(np.int8),
                 f"{name}.bias": np.zeros(fo, np.int32)}
            return reference.forward(one, w, acts[layer["inputs"][0]])

        if kind == "dense":
            layer["weights"] = [-1, 1]
        elif kind in ("conv", "depthwise"):
            tries = [(-h, h) for h in range(1, 128)] + \
                    [(lo_, 127) for lo_ in range(-127, 1)]
            layer["weights"] = list(next(
                (t for t in tries
                 if math.sqrt(np.mean(out(*t).astype(float) ** 2)) >= target_rms),
                (0, 127)))
        if "weights" in layer:
            acts[name] = out(*layer["weights"])
        else:                                   # pools and adds
            names = {i: f"x{j}" for j, i in enumerate(layer["inputs"])}
            sub = {"input_name": "x0", "layers": [
                dict(layer, inputs=[names[i] for i in layer["inputs"]])]}
            acts[name] = _forward_from(sub, {names[i]: acts[i]
                                             for i in layer["inputs"]})
    return config


def _forward_from(config: dict, inputs: dict):
    """``reference.forward`` of a one-layer config with several inputs."""
    from chipbench import reference
    first, *rest = config["layers"][0]["inputs"]
    if not rest:
        return reference.forward(config, {}, inputs[first])
    import numpy as np
    a, b = (inputs[i].astype(np.int64) for i in config["layers"][0]["inputs"])
    return np.clip(a + b, -127, 127).astype(np.int8)


def main(argv=None) -> int:
    from chipbench.harness import layers_from_graph
    from repro.serve.model import SERVE_GRAPHS, device_graph
    from repro.vta.workloads import network_graph
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--network", required=True)
    ap.add_argument("--scale", default="full")
    ap.add_argument("--name", required=True)
    ap.add_argument("--target-rms", type=float, required=True)
    ap.add_argument("--input-range", type=int, nargs=2, default=(-64, 64))
    args = ap.parse_args(argv)
    graph = (device_graph(network_graph(args.network)) if args.scale == "full"
             else SERVE_GRAPHS[args.network](args.scale))
    inp = next(n for n in graph.topo() if n.kind == "input")
    config = {"name": args.name,
              "served": {"network": args.network, "scale": args.scale},
              "input_name": inp.name, "input_shape": list(inp.shape[1:]),
              "input_range": list(args.input_range),
              "layers": layers_from_graph(graph)}
    print(json.dumps(calibrate(config, args.target_rms), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
