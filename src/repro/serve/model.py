"""Served models: a (network graph, VTAConfig) pair compiled to programs.

A ``ServedModel`` is the unit the serving engine batches over: the graph
compiler's segment Programs (fused adds, resident chains and all) plus
deterministic int8 weights, executable on any registered backend through
``Backend.run_batched`` — the whole batch of a dispatch runs as one
vmap-batched XLA computation on the jax backend, or as the sequential
per-image reference on numpy. ``run_single`` is the batch-1 numpy oracle
the engine's outputs are bit-identical to by contract (property-tested in
tests/test_serve.py, re-verified by benchmarks/bench_serve.py).

The registry ships *serving-scale* variants of the paper's two workload
families — a resnet18-flavored residual stack (fused conv→add→clip
segments) and a mobilenet-flavored depthwise-separable chain (resident
dw→pw edges) — at ``tiny`` (unit tests / CI smoke) and ``small`` (default
benchmark) scales, and ``full``: the DSE's own 224×224 graph
(``vta/workloads``) at published widths, minus the CPU stem conv, through
exactly the same code path (``chip_smoke.py`` serves it on a TPU).
"""
from __future__ import annotations

import functools
import hashlib
import itertools
import math
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Optional, Union

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.tps import ConvWorkload, heuristic_conv_tiling
from repro.vta.backend import Backend, get_backend
from repro.vta.compiler import compile_graph
from repro.vta.graph import Graph
from repro.vta.isa import DEFAULT_VTA, VTAConfig
from repro.vta.lowering import lower_cached
from repro.vta.runtime import Program
from repro.vta.scheduler import (schedule_add, schedule_conv,
                                 schedule_depthwise, schedule_pool)
from repro.vta.workloads import (Layer, _add, _conv, network_graph,
                                 pad_for_blocking)


@dataclass
class SegmentExec:
    """One dispatchable Program + the DRAM tensor names it touches.
    ``label`` (the tensors it writes, joined by ``+``) names its
    ``vta.segment`` profiler span; ``kinds`` (its layers' kinds, joined by
    ``+``, such as ``depthwise+conv``) and ``padded`` (``_pad_channels`` of
    its layers) are the span's other attributes."""
    program: Program
    reads: tuple
    writes: tuple
    label: str
    kinds: str
    padded: int = 0


# the batch number of this thread's last ``run_batch`` (``take_batch``)
_LAST_BATCH = threading.local()


def take_batch() -> Optional[int]:
    """The ``batch`` number of the last ``run_batch`` on this thread, or
    None; cleared by the call. The engine stamps it on the ``serve.resolve``
    span, so one batch's spans join across the two layers."""
    seq, _LAST_BATCH.seq = getattr(_LAST_BATCH, "seq", None), None
    return seq


def _tensor_roles(node) -> dict:
    """The compiler's DRAM naming convention, applied to fallback nodes."""
    return {"inp": node.inputs[0], "wgt": f"{node.name}.wgt",
            "bias": f"{node.name}.bias", "out": node.name}


def _fallback_program(node, hw: VTAConfig) -> Program:
    """Lower a single-node segment with node-named tensors (the per-layer
    path names them inp/wgt/out, which cannot chain across a network), on
    its channel-padded workload, as ``compile_graph`` plans the others."""
    layer = node.layer
    wl = pad_for_blocking(layer.wl, hw)
    roles = _tensor_roles(node)
    if node.kind in ("conv", "dense"):
        tiling = heuristic_conv_tiling(wl, hw, prefer_db=True)
        return schedule_conv(wl, tiling, hw, post_op=layer.post_op,
                             bias=layer.bias, tensors=roles).program
    if node.kind == "depthwise":
        return schedule_depthwise(wl, hw, post_op=layer.post_op,
                                  tensors=roles).program
    if node.kind in ("maxpool", "avgpool"):
        return schedule_pool(wl, hw, mode=node.kind[:3],
                             tensors=roles).program
    if node.kind == "add":
        return schedule_add(wl, hw, tensors={
            "add_a": node.inputs[0], "add_b": node.inputs[1],
            "out": node.name}).program
    raise ValueError(f"cannot serve node kind {node.kind!r}")


def _pad_channels(node, hw: VTAConfig) -> int:
    """Channels ``pad_for_blocking`` adds to ``node``'s layer: to its
    output, and to its input where it is a GEMM (conv or dense)."""
    if node.layer is None:
        return 0
    wl = node.layer.wl
    pwl = pad_for_blocking(wl, hw)
    gemm_in = pwl.fi - wl.fi if node.kind in ("conv", "dense") else 0
    return pwl.fo - wl.fo + gemm_in


def _dram_shapes(graph: Graph, hw: VTAConfig) -> dict:
    """Per-image DRAM shape of every activation, weight and bias tensor,
    with channels padded as ``pad_for_blocking`` pads the layers that
    write and read them. Raises ValueError where two layers would pad one
    tensor differently."""
    shapes: dict = {}

    def put(name: str, shape: tuple) -> None:
        if shapes.setdefault(name, shape) != shape:
            raise ValueError(f"{name}: padded to {shapes[name]} and {shape}")

    for node in graph.topo():
        if node.kind == "input":         # padded as its first reader pads it
            continue
        if node.kind == "concat":        # block-aligned by the compiler
            put(node.name, tuple(node.shape))
            continue
        wl = pad_for_blocking(node.layer.wl, hw)
        _, _, h, w = node.shape
        put(node.name, (node.shape[0], wl.fo, h, w))
        if node.kind == "add":
            for src in node.inputs:
                put(src, (node.shape[0], wl.fo, h, w))
            continue
        src = graph.nodes[node.inputs[0]].shape
        put(node.inputs[0], (src[0], wl.fi, src[2], src[3]))
        if node.kind in ("conv", "dense"):
            put(f"{node.name}.wgt", (wl.fo, wl.fi, wl.kh, wl.kw))
            if node.layer.bias:
                put(f"{node.name}.bias", (wl.fo,))
        elif node.kind == "depthwise":
            put(f"{node.name}.wgt", (wl.fi, wl.kh, wl.kw))
    return shapes


def _pad_to(a: np.ndarray, shape: tuple, axis0: int = 0) -> np.ndarray:
    """``a`` with zeros appended to reach ``shape`` from its axis
    ``axis0`` on; ``a`` itself where it has that shape."""
    if a.shape[axis0:] == tuple(shape):
        return a
    return np.pad(a, [(0, 0)] * axis0 + [(0, t - d) for d, t in
                                         zip(a.shape[axis0:], shape)])


def _model_rng(name: str, hw: VTAConfig) -> np.random.Generator:
    seed = hashlib.sha256(f"{name}:{hw}".encode()).hexdigest()[:8]
    return np.random.default_rng(int(seed, 16))


@dataclass
class ServedModel:
    """Compiled, weight-initialized, backend-agnostic network.

    ``graph``, ``shapes`` and ``weights`` are at the network's published
    widths. Inside the program every tensor lives at ``dram_shapes``: a
    channel count that is not a multiple of the VTA block is padded to it
    with zeros, as the compiler pads the layers (``pad_for_blocking``).
    A weight is padded once per array (``_padded_weight``), a served
    input on its put and the output sliced on its fetch; the pad channels
    stay zero through every layer, since their weights and biases are
    zero. On a block-aligned network the two sets of shapes are one."""
    name: str
    hw: VTAConfig
    graph: Graph
    segments: list = field(default_factory=list)     # SegmentExec, topo order
    weights: dict = field(default_factory=dict)      # shared DRAM tensors
    shapes: dict = field(default_factory=dict)       # per-image tensor shapes
    dram_shapes: dict = field(default_factory=dict)  # the same, padded
    input_name: str = ""
    output_name: str = ""
    # numbers each ``run_batch`` (``next`` on a count is atomic)
    batch_numbers: itertools.count = field(default_factory=itertools.count,
                                           repr=False, compare=False)
    # weight name -> (the published array, its padded copy)
    padded_weights: dict = field(default_factory=dict, repr=False,
                                 compare=False)

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @classmethod
    def compile(cls, name: str, graph: Graph, hw: VTAConfig) -> "ServedModel":
        graph.validate()
        m = cls(name=name, hw=hw, graph=graph)
        rng = _model_rng(name, hw)
        consumed: set = set()
        for node in graph.topo():
            m.shapes[node.name] = tuple(node.shape)
            consumed.update(node.inputs)
            if node.kind == "input":
                m.input_name = node.name
                continue
            assert not node.on_cpu, \
                f"{node.name}: CPU layers cannot be served on the VTA path"
            wl = node.layer.wl if node.layer is not None else None
            if wl is not None and pad_for_blocking(wl, hw).b != wl.b:
                raise ValueError(
                    f"{node.name}: serve graphs must be batch-aligned for "
                    f"the target config (batch % {hw.batch})")
            if node.kind in ("conv", "dense"):
                m.weights[f"{node.name}.wgt"] = rng.integers(
                    -8, 8, (wl.fo, wl.fi, wl.kh, wl.kw), dtype=np.int8)
                if node.layer.bias:
                    m.weights[f"{node.name}.bias"] = rng.integers(
                        -100, 100, (wl.fo,), dtype=np.int32)
            elif node.kind == "depthwise":
                m.weights[f"{node.name}.wgt"] = rng.integers(
                    -8, 8, (wl.fi, wl.kh, wl.kw), dtype=np.int8)
        assert m.input_name, "serve graphs need exactly one input node"
        sinks = [n.name for n in graph.topo()
                 if n.is_compute and n.name not in consumed]
        assert len(sinks) == 1, f"need exactly one sink, got {sinks}"
        m.output_name = sinks[0]
        m.dram_shapes = _dram_shapes(graph, hw)

        for seg in compile_graph(graph, hw):
            prog = seg.program
            if prog is None:
                assert len(seg.nodes) == 1
                prog = _fallback_program(seg.nodes[0], hw)
            trace = lower_cached(prog, hw, m.dram_shapes)
            m.segments.append(SegmentExec(
                program=prog, reads=trace.tensors_read,
                writes=trace.tensors_written,
                label="+".join(trace.tensors_written),
                kinds="+".join(n.kind for n in seg.nodes),
                padded=sum(_pad_channels(n, hw) for n in seg.nodes)))
        return m

    # ------------------------------------------------------------------
    # shapes + synthetic inputs
    # ------------------------------------------------------------------
    @property
    def image_shape(self) -> tuple:
        """Per-request input shape (1, C, H, W) — b=1 per image."""
        return self.shapes[self.input_name]

    @property
    def output_shape(self) -> tuple:
        return self.shapes[self.output_name]

    def random_images(self, n: int, seed: int = 0) -> np.ndarray:
        """(n,) + image_shape int8 stack, deterministic per seed."""
        rng = np.random.default_rng(seed)
        return rng.integers(-32, 32, (n,) + self.image_shape, dtype=np.int8)

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def run_batch(self, images: np.ndarray,
                  backend: Union[str, Backend, None] = None) -> np.ndarray:
        """Execute a (N,) + image_shape stack; returns (N,) + output_shape.

        Segments chain through a per-image DRAM state dict; each dispatch
        passes only the tensors that segment touches, so the backend's
        lowering/compile caches key on stable small shape sets. The state
        holds what the backend returns: on the jax backend device arrays in
        its flat layout ``(N, size)``, so a batch's activations stay on the
        device from the images' put to the output's one fetch.
        Intermediates are born on the device as flat zeros.

        The batch runs in a ``vta.batch`` profiler span (``model``,
        ``bucket`` = N, ``batch`` = this model's batch number), each segment
        in a ``vta.segment`` span (``segment`` = its ``label``, ``kinds`` =
        its ``kinds``, ``padded`` = its ``padded``), and the output's fetch
        in a ``vta.fetch`` span. Images whose channels the program pads are
        padded with zeros before their put, and the output is cut back to
        its published channels after its fetch.
        """
        be = get_backend(backend)
        images = np.ascontiguousarray(images, dtype=np.int8)
        assert images.shape[1:] == self.image_shape, \
            (images.shape, self.image_shape)
        n = images.shape[0]
        seq = _LAST_BATCH.seq = next(self.batch_numbers)
        with TraceAnnotation("vta.batch", model=self.name, bucket=n,
                             batch=seq):
            state: dict = {self.input_name: _pad_to(
                images, self.dram_shapes[self.input_name], axis0=1)}
            for seg in self.segments:
                with TraceAnnotation("vta.segment", segment=seg.label,
                                     kinds=seg.kinds, padded=seg.padded):
                    batched = {}
                    for t in self._activations(seg):
                        if t not in state:  # intermediate first touched here
                            state[t] = jnp.zeros(
                                (n, math.prod(self.dram_shapes[t])), jnp.int8)
                        batched[t] = state[t]
                    outs = be.run_batched(seg.program, self.hw,
                                          shared=self._weights_of(seg),
                                          batched=batched)
                    state.update(outs)
            with TraceAnnotation("vta.fetch"):     # waits for the device
                return self._published(np.asarray(state[self.output_name]),
                                       n)

    def _published(self, out: np.ndarray, n: int) -> np.ndarray:
        """The output tensor of ``n`` images, fetched in any layout, at its
        published shape ``(n,) + output_shape``."""
        out = out.reshape((n,) + self.dram_shapes[self.output_name])
        return out[:, :, :self.output_shape[1]]

    def _activations(self, seg: SegmentExec) -> set:
        return (set(seg.reads) | set(seg.writes)) - set(self.weights)

    def _weights_of(self, seg: SegmentExec) -> dict:
        return {t: self._padded_weight(t) for t in seg.reads
                if t in self.weights}

    def _padded_weight(self, name: str) -> np.ndarray:
        """``weights[name]`` at its DRAM shape: the array itself where no
        channel is padded, else a zero-padded copy made once per array
        (``weights`` may be reassigned; a new array is padded anew)."""
        w = self.weights[name]
        if np.shape(w) == self.dram_shapes[name]:
            return w
        hit = self.padded_weights.get(name)
        if hit is None or hit[0] is not w:
            hit = self.padded_weights[name] = (
                w, _pad_to(np.asarray(w), self.dram_shapes[name]))
        return hit[1]

    def precompile(self, n: int, backend: Union[str, Backend] = "jax",
                   threads: Optional[int] = None) -> int:
        """Compile every program a batch of ``n`` launches on ``backend``
        before the first dispatch, ``threads`` at a time (default: the CPUs
        this process may use). A cold full-width model is dominated by
        compilation; compiled concurrently it starts several times sooner.
        Returns the number of distinct programs (0 for backends that
        compile nothing, such as numpy)."""
        be = get_backend(backend)
        if not hasattr(be, "chunk_compiles"):
            return 0
        jobs: dict = {}
        for seg in self.segments:
            batched = {t: np.broadcast_to(np.int8(0),
                                          (n,) + self.dram_shapes[t])
                       for t in self._activations(seg)}
            jobs.update(be.chunk_compiles(seg.program, self.hw,
                                          shared=self._weights_of(seg),
                                          batched=batched))
        threads = threads or len(os.sched_getaffinity(0))
        with ThreadPoolExecutor(max_workers=threads) as pool:
            for f in [pool.submit(job) for job in jobs.values()]:
                f.result()
        return len(jobs)

    def run_single(self, image: np.ndarray,
                   backend: Union[str, Backend, None] = None) -> np.ndarray:
        """Batch-1 execution of one image (numpy by default): the oracle
        batched serving must match bit for bit."""
        be = get_backend(backend)
        assert image.shape == self.image_shape, \
            (image.shape, self.image_shape)
        dram = {self.input_name: _pad_to(
            np.array(image, dtype=np.int8), self.dram_shapes[self.input_name])}
        for t in self.shapes:
            if t not in dram:
                dram[t] = np.zeros(self.dram_shapes[t], np.int8)
        dram.update({t: self._padded_weight(t) for t in self.weights})
        for seg in self.segments:
            be.run(seg.program, self.hw, dram)
        return self._published(dram[self.output_name], 1)[0].copy()


# ---------------------------------------------------------------------------
# Serving-scale graph builders
# ---------------------------------------------------------------------------
# (spatial size, channels) per scale — block-aligned for the default config
SERVE_SCALES = {"tiny": (8, 16), "small": (14, 32)}


def _resnet_serve_graph(scale: str) -> Graph:
    """Residual stack shaped like a resnet18 stage: two basic blocks whose
    adds fuse into the producing convs (conv→add→clip segments)."""
    size, c = SERVE_SCALES[scale]
    g = Graph(name=f"resnet18-{scale}")
    prev = g.input("image", (1, c, size, size)).name
    for blk in ("b0", "b1"):
        a = g.layer(_conv(f"{blk}.a", 1, size, c, c, 3, 1, 1), prev).name
        b = g.layer(_conv(f"{blk}.b", 1, size, c, c, 3, 1, 1), a).name
        prev = g.residual_add(f"{blk}.add", b, prev,
                              layer=_add(f"{blk}.add", 1, size, c)).name
    g.validate()
    return g


def _mobilenet_serve_graph(scale: str) -> Graph:
    """Depthwise-separable chain shaped like a mobilenet stage: dw→pw pairs
    with resident on-chip edges where the compiler finds them."""
    size, c = SERVE_SCALES[scale]
    g = Graph(name=f"mobilenet-{scale}")
    prev = g.input("image", (1, c, size, size)).name
    for i in range(2):
        dw = ConvWorkload(f"dw{i}", 1, size, size, 3, 3, c, c, 1, 1, 1, 1,
                          depthwise=True)
        # dw keeps full precision (relu only); pw is the requantization
        # point (relu_shift) — shifting at every layer collapses the small
        # serve-scale activations to all-zero by the second block
        prev = g.layer(Layer("depthwise", dw, post_op="relu"), prev).name
        prev = g.layer(_conv(f"pw{i}", 1, size, c, c, 1, 0, 1,
                             post="relu_shift"), prev).name
    g.validate()
    return g


SERVE_GRAPHS = {
    "resnet18": _resnet_serve_graph,
    "mobilenet": _mobilenet_serve_graph,
}


def device_graph(graph: Graph) -> Graph:
    """``graph`` with its CPU stem cut off: every ``on_cpu`` node becomes a
    served input of its output shape (upstream VTA runs the 3-channel stem
    conv on the host too), and the raw-image input it consumed is dropped.
    """
    out = Graph(name=graph.name)
    for node in graph.topo():
        if node.kind == "input":
            continue
        if node.on_cpu:
            out.input(node.name, node.shape)
        else:
            out.add(node)
    out.validate()
    return out


def list_served_models() -> list:
    return sorted(SERVE_GRAPHS)


def _serve_graph(name: str, scale: str) -> Graph:
    if scale == "full":          # the DSE's own graph at published widths
        return device_graph(network_graph(name))
    if scale not in SERVE_SCALES:
        raise KeyError(f"unknown scale {scale!r}; "
                       f"known: {sorted(SERVE_SCALES) + ['full']}")
    return SERVE_GRAPHS[name](scale)


@functools.lru_cache(maxsize=None)
def served_model(name: str, scale: str = "small",
                 hw: Optional[VTAConfig] = None) -> ServedModel:
    """Build (memoized) a registry model for ``hw`` (default config).
    ``scale="full"`` serves ``vta/workloads``' graph of the network whole,
    minus its CPU stem (input ``(1, 64, 112, 112)`` for resnet18)."""
    if name not in SERVE_GRAPHS:
        raise KeyError(f"unknown served model {name!r}; "
                       f"known: {list_served_models()}")
    hw = hw or DEFAULT_VTA
    return ServedModel.compile(f"{name}-{scale}", _serve_graph(name, scale),
                               hw)
