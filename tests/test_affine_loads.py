"""Loads read as strided blocks of the padded DRAM tensor
(``GatherLoad.affine``, the executor's ``gather_block`` entry): lowering
proves every load of the full-width served networks one, the proof gives
exactly the element gather's index map and mask and rejects a map that is
no block, which then runs as a gather, and the executor's slices agree bit
for bit with the numpy interpreter, under ``vmap`` and stepped."""
import collections
import dataclasses

import numpy as np
import pytest

from repro.core.tps import ConvWorkload, tps_search
from repro.serve.model import ServedModel, device_graph, served_model
from repro.vta import fsim_jax
from repro.vta.backend import get_backend
from repro.vta.compiler import compile_graph
from repro.vta.fsim import FSim
from repro.vta.graph import Graph
from repro.vta.isa import DEFAULT_VTA
from repro.vta.lowering import (GatherLoad, _affine_load, _block_matches,
                                _load_coords, lower, lower_cached)
from repro.vta.scheduler import schedule_conv
from repro.vta.trace import diff_backends
from repro.vta.workloads import _add, _conv, network_graph

RNG = np.random.default_rng(18)


def _served_loads(model) -> list:
    """(kind, load, tensor shape) of every load the executor runs for the
    model's segments: the feeder loads of DRAM-direct sweeps run inside
    the sweeps and are left out."""
    out = []
    for seg in model.segments:
        shapes = {t: model.shapes[t] for t in model._activations(seg)}
        shapes.update({t: w.shape for t, w in model._weights_of(seg).items()})
        trace = lower_cached(seg.program, model.hw, shapes)
        for i, op in enumerate(trace.ops):
            if isinstance(op, GatherLoad) and i not in trace.elided:
                out.append((trace.insns[i].meta["kind"], op,
                            shapes[op.tensor]))
    return out


@pytest.fixture(scope="module")
def full_width_loads() -> dict:
    return {net: _served_loads(ServedModel.compile(
        net, device_graph(network_graph(net)), DEFAULT_VTA))
        for net in ("resnet18", "mobilenet")}


@pytest.mark.parametrize("network,kinds,masked", [
    ("resnet18", {"inp": 606, "wgt": 609, "resid": 110, "bias": 3}, 552),
    ("mobilenet", {"inp": 346, "wgt": 355, "bias": 7}, 0),
])
def test_every_load_of_the_full_width_networks_is_a_strided_block(
        full_width_loads, network, kinds, masked):
    loads = full_width_loads[network]
    assert collections.Counter(k for k, _, _ in loads) == kinds
    assert sum(op.mask is not None for _, op, _ in loads) == masked
    assert [op for _, op, _ in loads if op.affine is None] == []


def _read_block(affine: tuple, flat: np.ndarray, fill: int,
                rows: tuple) -> np.ndarray:
    """What the executor's ``gather_block`` entry reads, in numpy."""
    view, perm, sizes, starts, pads = affine
    padded = np.pad(flat.reshape(view), pads, constant_values=fill)
    block = padded[tuple(slice(s, s + n) for s, n in zip(starts, sizes))]
    moving = [s for s in sizes if s > 1]
    return block.reshape(moving).transpose(
        np.argsort(perm[:len(moving)])).reshape(rows)


@pytest.mark.parametrize("kind,halo", [
    ("inp", True), ("inp", False), ("wgt", False), ("resid", False),
    ("bias", False)])
def test_the_block_reads_what_the_gather_reads(full_width_loads, kind,
                                               halo):
    loads = [(op, shape) for k, op, shape in full_width_loads["resnet18"]
             if k == kind and (op.mask is not None) == halo]
    assert loads
    for op, shape in loads:
        flat = RNG.integers(-128, 128, int(np.prod(shape))).astype(np.int8)
        want = flat[op.index]
        if op.mask is not None:
            want = np.where(op.mask, want, np.int8(op.fill))
        if halo:                         # an edge tile reads the padding
            assert any(lo or hi for lo, hi in op.affine[4])
        np.testing.assert_array_equal(
            _read_block(op.affine, flat, op.fill, op.index.shape), want)


def _conv_program(wl, *, bias=False):
    tiling = tps_search(wl, DEFAULT_VTA).tiling
    prog = schedule_conv(wl, tiling, DEFAULT_VTA, bias=bias).program
    shared = {"wgt": RNG.integers(-8, 8, (wl.fo, wl.fi, wl.kh, wl.kw),
                                  dtype=np.int8)}
    if bias:
        shared["bias"] = RNG.integers(-100, 100, (wl.fo,), dtype=np.int32)
    batched = {"inp": (wl.b, wl.fi, wl.h, wl.w),
               "out": (wl.b, wl.fo, wl.oh, wl.ow)}
    return prog, shared, batched


def _conv_add_program():
    g = Graph(name="t")
    g.input("image", (1, 16, 8, 8))
    g.layer(_conv("a", 1, 8, 16, 16, 3, 1, 1), "image")
    g.layer(_conv("b", 1, 8, 16, 16, 3, 1, 1), "a")
    g.residual_add("add", "b", "a", layer=_add("add", 1, 8, 16))
    seg = next(s for s in compile_graph(g, DEFAULT_VTA) if s.multi)
    shared = {"b.wgt": RNG.integers(-8, 8, (16, 16, 3, 3), dtype=np.int8)}
    batched = {"a": (1, 16, 8, 8), "add": (1, 16, 8, 8)}
    return seg.program, shared, batched


PROGRAMS = {
    "padded 3x3 conv": lambda: _conv_program(
        ConvWorkload("c3", 1, 14, 14, 3, 3, 32, 32, 1, 1, 1, 1)),
    "stride-2 conv": lambda: _conv_program(
        ConvWorkload("c3s2", 1, 14, 14, 3, 3, 16, 32, 1, 1, 2, 2)),
    "fused conv-add": _conv_add_program,
    "pointwise conv with bias": lambda: _conv_program(
        ConvWorkload("pw", 1, 7, 7, 1, 1, 32, 48, 0, 0, 1, 1), bias=True),
}


def _trace_of(prog, shared, batched):
    shapes = {k: v.shape for k, v in shared.items()} | batched
    return lower_cached(prog, DEFAULT_VTA, shapes)


@pytest.mark.parametrize("case", list(PROGRAMS))
def test_the_sliced_loads_match_numpy_batched(case):
    prog, shared, shapes = PROGRAMS[case]()
    trace = _trace_of(prog, shared, shapes)
    loads = [(trace.insns[i].meta["kind"], op)
             for i, op in enumerate(trace.ops) if isinstance(op, GatherLoad)]
    assert loads and all(op.affine is not None for _, op in loads)
    kinds = {k for k, _ in loads}
    if case == "padded 3x3 conv":
        assert any(op.mask is not None for _, op in loads)
    if case == "fused conv-add":
        assert "resid" in kinds
    if case == "pointwise conv with bias":
        assert "bias" in kinds
    n = 3
    batched = {k: RNG.integers(-64, 64, (n,) + s, dtype=np.int8)
               for k, s in shapes.items()}
    want = get_backend("numpy").run_batched(
        prog, DEFAULT_VTA, shared=shared,
        batched={k: v.copy() for k, v in batched.items()})
    fsim_jax.reset_kernel_launch_log()
    got = get_backend("jax").run_batched(
        prog, DEFAULT_VTA, shared=shared,
        batched={k: v.copy() for k, v in batched.items()})
    dispatched = [i for i, op in enumerate(trace.ops)
                  if isinstance(op, GatherLoad) and i not in trace.elided]
    assert fsim_jax.load_form_log() == {"slice": len(dispatched),
                                        "gather": 0}
    for t in trace.tensors_written:
        np.testing.assert_array_equal(
            np.asarray(got[t]).reshape(want[t].shape), want[t])


def test_the_stepped_path_slices_as_the_chunks_do():
    prog, shared, shapes = PROGRAMS["padded 3x3 conv"]()
    dram = {k: RNG.integers(-64, 64, s, dtype=np.int8)
            for k, s in shapes.items()} | shared
    diff = diff_backends(prog, DEFAULT_VTA, dram)
    assert diff.outputs_equal and diff.divergence is None


def test_a_map_that_is_no_block_is_rejected_and_gathers():
    prog, shared, shapes = PROGRAMS["padded 3x3 conv"]()
    hw = DEFAULT_VTA
    all_shapes = {k: v.shape for k, v in shared.items()} | shapes
    trace = lower(prog, hw, all_shapes)
    i, op = next((i, op) for i, op in enumerate(trace.ops)
                 if isinstance(op, GatherLoad) and op.mask is not None)
    coords, grid, halo_dims, _ = _load_coords(trace.insns[i], hw)
    shape = all_shapes[op.tensor]
    # columns read right to left: no slice reads them
    mirrored = coords[:3] + (coords[3][..., ::-1, :, :],)
    assert _affine_load(mirrored, grid, shape, op.affine[4]) is None
    # the tile's rows in reverse order: the block no longer matches
    index, mask = op.index[::-1].copy(), op.mask[::-1].copy()
    assert _block_matches(op.affine, grid, op.index, op.mask)
    assert not _block_matches(op.affine, grid, index, mask)
    trace.ops[i] = dataclasses.replace(op, index=index, mask=mask,
                                       affine=None)
    dram = {k: RNG.integers(-64, 64, s, dtype=np.int8)
            for k, s in shapes.items()} | shared
    want = {k: v.copy() for k, v in dram.items()}
    FSim(hw, want).run(prog, trace=trace)
    fsim_jax.reset_kernel_launch_log()
    got = fsim_jax.JaxBackend()._execute(
        trace, hw, {k: dram[k][None] for k in shapes}, shared)
    forms = fsim_jax.load_form_log()
    assert forms["gather"] == 1 and forms["slice"] > 0
    for t in trace.tensors_written:
        np.testing.assert_array_equal(
            np.asarray(got[t]).reshape(want[t].shape), want[t])


# the served models' chunk programs before loads were sliced: every load
# an element gather, one more index map per load
SPECS_BEFORE = {"resnet18": (2, 2), "mobilenet": (1, 1)}


@pytest.mark.parametrize("network", list(SPECS_BEFORE))
def test_slicing_adds_no_program_to_compile(network):
    model = served_model(network, "small")
    specs = set()
    for seg in model.segments:
        shapes = {t: model.shapes[t] for t in model._activations(seg)}
        shapes.update({t: w.shape for t, w in model._weights_of(seg).items()})
        trace = lower_cached(seg.program, model.hw, shapes)
        specs |= {cspec for cspec, _ in fsim_jax._spec_chunks(trace)}
    fsim_jax.reset_xla_trace_log()
    model.run_batch(model.random_images(8, seed=3), backend="jax")
    specs_before, traces_before = SPECS_BEFORE[network]
    assert len(specs) <= specs_before
    assert len(fsim_jax.xla_trace_log()) <= traces_before


@pytest.mark.parametrize("network", ["resnet18", "mobilenet"])
def test_every_served_load_is_a_slice_and_the_log_resets(network):
    model = served_model(network, "small")
    fsim_jax.reset_kernel_launch_log()
    model.run_batch(model.random_images(8, seed=5), backend="jax")
    forms = fsim_jax.load_form_log()
    assert forms["gather"] == 0 and forms["slice"] == len(
        _served_loads(model))
    assert fsim_jax.indexed_bytes_by_class()["load"] == 0
    fsim_jax.reset_kernel_launch_log()
    assert fsim_jax.load_form_log() == {"slice": 0, "gather": 0}


def test_the_benchmark_reads_the_share_of_sliced_loads():
    from chipbench import harness
    read = harness.load_reader(harness.BENCH, "load_slice_share")
    fsim_jax.reset_kernel_launch_log()
    assert read({}) is None                      # no load dispatched yet
    model = served_model("resnet18", "small")
    model.run_batch(model.random_images(8, seed=6), backend="jax")
    assert read({}) == 100
