"""The network's work, from the layer shapes of its configuration file, and
the chip's peaks, from ``peaks.json``.

Operations and bytes are what the network needs, whatever implements it:
an int8 multiply-accumulate is two operations, and the least traffic of a
layer is its weights once per batch plus its input and output activations
once per image. Nothing here reads the program.
"""
from __future__ import annotations

import json
from pathlib import Path

GEMM_KINDS = ("conv", "dense")
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def _shapes(config: dict) -> dict:
    shapes = {config["input_name"]: tuple(config["input_shape"])}
    for layer in config["layers"]:
        shapes[layer["name"]] = tuple(layer["shape"])
    return shapes


def layer_work(config: dict) -> list:
    """Per weighted or pooled layer: its kind, MACs per image, weight bytes,
    and input plus output activation bytes per image (int8 activations,
    int8 weights, int32 bias)."""
    shapes = _shapes(config)
    out = []
    for layer in config["layers"]:
        c, oh, ow = layer["shape"]
        src = shapes[layer["inputs"][0]]
        k = layer.get("k", 1)
        kind = layer["kind"]
        if kind in GEMM_KINDS:
            fi = src[0]
            macs = c * oh * ow * fi * k * k
            wbytes = c * fi * k * k + (4 * c if layer.get("bias") else 0)
        elif kind == "depthwise":
            macs = c * oh * ow * k * k
            wbytes = c * k * k
        else:
            macs, wbytes = 0, 0
        act = sum(shapes[i][0] * shapes[i][1] * shapes[i][2]
                  for i in layer["inputs"]) + c * oh * ow
        out.append({"name": layer["name"], "kind": kind, "macs": macs,
                    "weight_bytes": wbytes, "act_bytes": act})
    return out


def macs_per_image(config: dict, kinds=GEMM_KINDS) -> int:
    return sum(w["macs"] for w in layer_work(config) if w["kind"] in kinds)


def int8_ops_per_image(config: dict) -> int:
    """2 x the body's conv, dense and depthwise MACs."""
    return 2 * macs_per_image(config, GEMM_KINDS + ("depthwise",))


def gemm_min_bytes(config: dict, images: int, batches: int) -> int:
    """Least HBM traffic of the conv and dense layers for ``images`` served
    in ``batches`` dispatches: weights once per batch, activations once per
    image."""
    gemm = [w for w in layer_work(config) if w["kind"] in GEMM_KINDS]
    return (batches * sum(w["weight_bytes"] for w in gemm)
            + images * sum(w["act_bytes"] for w in gemm))


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
