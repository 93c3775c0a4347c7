"""Plain numpy reference of a served int8 network, read from its config file.

This is the oracle the benchmark judges served outputs against. It imports
nothing of the program: the layer list comes from the configuration file,
the weights from ``chipbench.harness.make_weights``, and the arithmetic is
the VTA int8 contract written out layer by layer:

* conv / dense: int8 x int8 products summed exactly (float64 carries every
  sum of this network exactly), plus an int32 bias where the layer has one;
* depthwise: the same per channel, over the kernel's taps;
* maxpool pads with -128, avgpool sums its taps and shifts right by
  round(log2(taps));
* add: a + b clipped to +-127;
* the post-op of the layer: ``clip_shift`` = clip(acc >> 8, +-127),
  ``relu_shift`` = max(acc >> 8, 0), ``relu`` = max(acc, 0), ``clip`` =
  clip(acc, +-127), ``none`` = acc; every stored tensor then saturates to
  int8 [-128, 127].

``quant="int4"`` is the benchmark's control: the same network held at the
next precision below int8. Every stored activation keeps only its top four
bits and each weight tensor is rounded to 16 levels of its own range. A
served output that matches it instead of the int8 reference fails the check.
"""
from __future__ import annotations

import numpy as np


def _post(acc: np.ndarray, post_op: str) -> np.ndarray:
    if post_op == "none":
        r = acc
    elif post_op == "relu":
        r = np.maximum(acc, 0)
    elif post_op == "relu_shift":
        r = np.maximum(acc >> 8, 0)
    elif post_op == "clip_shift":
        r = np.clip(acc >> 8, -127, 127)
    elif post_op == "clip":
        r = np.clip(acc, -127, 127)
    else:
        raise ValueError(f"unknown post_op {post_op!r}")
    return np.clip(r, -128, 127).astype(np.int8)


def _windows(x: np.ndarray, k: int, stride: int, pad: int,
             fill: int = 0) -> np.ndarray:
    """(N, C, OH, OW, k, k) view of the padded (N, C, H, W) input."""
    if pad:
        x = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)),
                   constant_values=fill)
    w = np.lib.stride_tricks.sliding_window_view(x, (k, k), axis=(2, 3))
    return w[:, :, ::stride, ::stride]


def conv(x: np.ndarray, w: np.ndarray, stride: int, pad: int,
         bias=None) -> np.ndarray:
    """int8 (N, FI, H, W) * int8 (FO, FI, k, k) -> int64 (N, FO, OH, OW)."""
    fo, fi, k, _ = w.shape
    win = _windows(x.astype(np.float64), k, stride, pad)
    n, _, oh, ow = win.shape[:4]
    cols = win.transpose(0, 2, 3, 1, 4, 5).reshape(n * oh * ow, fi * k * k)
    acc = cols @ w.reshape(fo, fi * k * k).T.astype(np.float64)
    acc = np.rint(acc).astype(np.int64).reshape(n, oh, ow, fo)
    acc = acc.transpose(0, 3, 1, 2)
    if bias is not None:
        acc = acc + bias.astype(np.int64)[None, :, None, None]
    return acc


def depthwise(x: np.ndarray, w: np.ndarray, stride: int,
              pad: int) -> np.ndarray:
    """int8 (N, C, H, W) * int8 (C, k, k) -> int64 (N, C, OH, OW)."""
    win = _windows(x.astype(np.int64), w.shape[1], stride, pad)
    return np.einsum("ncyxij,cij->ncyx", win, w.astype(np.int64))


def pool(x: np.ndarray, k: int, stride: int, pad: int,
         mode: str) -> np.ndarray:
    fill = -128 if mode == "max" else 0
    win = _windows(x.astype(np.int64), k, stride, pad, fill)
    if mode == "max":
        return win.max(axis=(4, 5))
    return win.sum(axis=(4, 5)) >> max(0, int(round(np.log2(k * k))))


def _int4_act(x: np.ndarray) -> np.ndarray:
    return ((x.astype(np.int16) >> 4) << 4).astype(np.int8)


def _int4_weight(w: np.ndarray) -> np.ndarray:
    top = float(np.abs(w).max())
    if top == 0:
        return w
    step = top / 7.0
    q = np.clip(np.rint(w / step), -8, 7) * step
    return np.rint(q).astype(w.dtype)


def forward(config: dict, weights: dict, images: np.ndarray,
            quant: str = "int8") -> np.ndarray:
    """Run ``images`` (N, C, H, W) int8 through the configuration's layers;
    returns the last layer's int8 output, (N, C, H, W)."""
    if quant not in ("int8", "int4"):
        raise ValueError(f"unknown precision {quant!r}")
    low = quant == "int4"
    wq = (lambda a: _int4_weight(a)) if low else (lambda a: a)
    acts = {config["input_name"]: images.astype(np.int8)}
    out = None
    for layer in config["layers"]:
        name, kind = layer["name"], layer["kind"]
        src = [acts[i] for i in layer["inputs"]]
        k, s, p = layer.get("k", 1), layer.get("stride", 1), layer.get("pad", 0)
        if kind in ("conv", "dense"):
            bias = weights.get(f"{name}.bias") if layer.get("bias") else None
            acc = conv(src[0], wq(weights[f"{name}.wgt"]), s, p, bias)
        elif kind == "depthwise":
            acc = depthwise(src[0], wq(weights[f"{name}.wgt"]), s, p)
        elif kind in ("maxpool", "avgpool"):
            acc = pool(src[0], k, s, p, kind[:3])
        elif kind == "add":
            acc = src[0].astype(np.int64) + src[1].astype(np.int64)
        else:
            raise ValueError(f"{name}: unknown layer kind {kind!r}")
        out = _post(acc, layer.get("post_op", "none") if kind not in
                    ("maxpool", "avgpool") else "none")
        if low:
            out = _int4_act(out)
        acts[name] = out
    return out
