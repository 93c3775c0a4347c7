"""GEMM operands read as windows of the scratchpads (``GemmOp.window``, the
executor's ``gemm_window`` entry): lowering proves every GEMM of the
full-width served networks windowed, and the window reads exactly the rows
the instruction's index vectors name; the windowed entry agrees bit for bit
with the numpy interpreter, batched and stepped; and a GEMM whose
accumulator rows are not one block, or whose accumulator groups read
different input rows, is refused, runs as the row gather and stays
exact."""
import dataclasses

import numpy as np
import pytest

from repro.core.tps import ConvWorkload, tps_search
from repro.serve.model import ServedModel, device_graph, served_model
from repro.vta import fsim_jax
from repro.vta.fsim import FSim
from repro.vta.graph import Graph
from repro.vta.isa import DEFAULT_VTA, GemmInsn, Op
from repro.vta.lowering import (GemmOp, _gemm_window, _window_matches,
                                lower, lower_cached)
from repro.vta.scheduler import schedule_conv
from repro.vta.trace import diff_backends
from repro.vta.workloads import _inverted_residual, network_graph

RNG = np.random.default_rng(20)


def _served_gemms(model) -> list:
    """Every GEMM op of the model's segments, as lowered for serving."""
    out = []
    for seg in model.segments:
        shapes = {t: model.dram_shapes[t] for t in model._activations(seg)}
        shapes.update({t: w.shape for t, w in model._weights_of(seg).items()})
        trace = lower_cached(seg.program, model.hw, shapes)
        out += [op for op in trace.ops if isinstance(op, GemmOp)]
    return out


def _window_rows(op: GemmOp, hw) -> tuple:
    """(accumulator, input, weight) rows a windowed GEMM addresses, each
    in the (l0, l1, uop) order of the op's index vectors: its input rows
    read as the executor reads them (a slice, viewed as planes of H x W,
    a strided view per shift), here from a scratchpad of row numbers."""
    geom, starts = op.window
    if op.reset:
        (n,), (a0,) = geom, starts
        return a0 + np.arange(n), None, None
    G, R, lp0, lp1, planes, pitch, H, W, sh, sw, shifts = geom
    a0, s0, w0 = starts
    rows = np.arange(hw.inp_depth)
    if planes > 1:
        view = rows[s0:s0 + planes * pitch].reshape(planes, pitch)[:, :H * W]
    else:
        view = rows[s0:s0 + H * W]
    view = view.reshape(planes, H, W)
    x = np.stack([view[:, dy:dy + sh * (lp0 - 1) + 1:sh,
                       dx:dx + sw * (lp1 - 1) + 1:sw] for dy, dx in shifts],
                 axis=1).reshape(R, lp0, lp1)
    inp = np.broadcast_to(x.transpose(1, 2, 0)[:, :, None], (lp0, lp1, G, R))
    acc = (a0 + np.arange(G * lp0 * lp1)).reshape(G, lp0, lp1)
    acc = np.broadcast_to(acc.transpose(1, 2, 0)[..., None], (lp0, lp1, G, R))
    wgt = np.broadcast_to((w0 + np.arange(G * R)).reshape(G, R),
                          (lp0, lp1, G, R))
    return acc.reshape(-1), inp.reshape(-1), wgt.reshape(-1)


@pytest.mark.parametrize("network,gemms,resets", [
    ("resnet18", 792, 183), ("mobilenet", 556, 201),
    ("mobilenetv2", 796, 335)])
def test_every_gemm_of_the_full_width_networks_reads_its_rows_as_windows(
        network, gemms, resets):
    model = ServedModel.compile(network, device_graph(network_graph(network)),
                                DEFAULT_VTA)
    ops = _served_gemms(model)
    assert (len(ops), sum(op.reset for op in ops)) == (gemms, resets)
    assert [op for op in ops if op.window is None] == []
    for op in ops:
        acc, inp, wgt = _window_rows(op, model.hw)
        if op.reset:
            np.testing.assert_array_equal(acc, np.sort(op.acc_idx))
            continue
        np.testing.assert_array_equal(acc, op.acc_idx)
        np.testing.assert_array_equal(inp, op.inp_idx)
        np.testing.assert_array_equal(wgt, op.wgt_idx)


def _conv_program(wl, *, bias=False):
    tiling = tps_search(wl, DEFAULT_VTA).tiling
    prog = schedule_conv(wl, tiling, DEFAULT_VTA, bias=bias).program
    shared = {"wgt": RNG.integers(-128, 128, (wl.fo, wl.fi, wl.kh, wl.kw),
                                  dtype=np.int8)}
    if bias:
        shared["bias"] = RNG.integers(-100, 100, (wl.fo,), dtype=np.int32)
    batched = {"inp": (wl.b, wl.fi, wl.h, wl.w),
               "out": (wl.b, wl.fo, wl.oh, wl.ow)}
    return prog, shared, batched


PROGRAMS = {
    "3x3 stride-1 conv": lambda: _conv_program(
        ConvWorkload("c3", 1, 14, 14, 3, 3, 32, 32, 1, 1, 1, 1)),
    "3x3 stride-2 conv": lambda: _conv_program(
        ConvWorkload("c3s2", 1, 14, 14, 3, 3, 16, 32, 1, 1, 2, 2)),
    "1x1 conv": lambda: _conv_program(
        ConvWorkload("pw", 1, 7, 7, 1, 1, 32, 48, 0, 0, 1, 1), bias=True),
    "dense layer": lambda: _conv_program(
        ConvWorkload("fc", 1, 1, 1, 1, 1, 512, 64, 0, 0, 1, 1), bias=True),
}


def _run_both(prog, shared, shapes, trace, n=3):
    """(numpy's outputs, jax's outputs) of one batched dispatch of ``n``
    images, and the jax dispatch's ``gemm_form_log``."""
    batched = {k: RNG.integers(-128, 128, (n,) + s, dtype=np.int8)
               for k, s in shapes.items()}
    want = {k: v.copy() for k, v in batched.items()}
    for i in range(n):
        dram = {k: v[i] for k, v in want.items()} | shared
        FSim(DEFAULT_VTA, dram).run(prog, trace=trace)
    fsim_jax.reset_kernel_launch_log()
    got = fsim_jax.JaxBackend()._execute(trace, DEFAULT_VTA, batched, shared)
    return want, got, fsim_jax.gemm_form_log()


@pytest.mark.parametrize("case", list(PROGRAMS))
def test_the_windowed_gemm_matches_numpy_batched(case):
    prog, shared, shapes = PROGRAMS[case]()
    trace = lower(prog, DEFAULT_VTA,
                  {k: v.shape for k, v in shared.items()} | shapes)
    gemms = [op for op in trace.ops if isinstance(op, GemmOp)]
    assert gemms and all(op.window is not None for op in gemms)
    want, got, forms = _run_both(prog, shared, shapes, trace)
    assert forms == {"window": len(gemms), "gather": 0}
    assert fsim_jax.indexed_bytes_by_class()["gemm"] == 0
    for t in trace.tensors_written:
        np.testing.assert_array_equal(
            np.asarray(got[t]).reshape(want[t].shape), want[t])


def test_a_padded_24_channel_mobilenetv2_block_matches_numpy():
    """An inverted residual block at 24 channels, padded to 32 inside the
    program: expand, depthwise, project and the fused residual add."""
    g = Graph(name="mbv2-block24")
    prev = g.input("x", (1, 24, 8, 8)).name
    _inverted_residual(g, "b", prev, 1, 8, 24, 24, 6, 1)
    g.validate()
    model = ServedModel.compile("mbv2-block24", g, DEFAULT_VTA)
    assert any(s.padded for s in model.segments)
    model.weights = {k: RNG.integers(-16, 16, v.shape).astype(v.dtype)
                     for k, v in model.weights.items()}
    images = RNG.integers(-64, 65, (4,) + model.image_shape).astype(np.int8)
    want = model.run_batch(images, backend="numpy")
    fsim_jax.reset_kernel_launch_log()
    got = model.run_batch(images, backend="jax")
    forms = fsim_jax.gemm_form_log()
    assert forms["gather"] == 0 and forms["window"] == len(
        _served_gemms(model))
    assert np.abs(want.astype(int)).max() > 8
    np.testing.assert_array_equal(got, want)


def test_the_stepped_path_reads_windows_as_the_chunks_do():
    prog, shared, shapes = PROGRAMS["3x3 stride-2 conv"]()
    dram = {k: RNG.integers(-64, 64, s, dtype=np.int8)
            for k, s in shapes.items()} | shared
    diff = diff_backends(prog, DEFAULT_VTA, dram)
    assert diff.outputs_equal and diff.divergence is None


def test_a_gemm_whose_accumulator_rows_are_no_block_gathers():
    hw = DEFAULT_VTA
    # two accumulator groups of one uop each, their rows 2 * lp1 apart
    uops = np.array([[0, 0, 0], [8, 0, 1]], np.int64)
    gapped = GemmInsn(op=Op.GEMM, uop_bgn=0, uop_end=2, lp0=2, lp1=2,
                      acc_f0=4, acc_f1=1, inp_f0=2, inp_f1=1)
    assert _gemm_window(gapped, uops, hw) is None
    assert _gemm_window(dataclasses.replace(gapped, acc_f0=2),
                        np.array([[0, 0, 0], [4, 0, 1]]), hw) is not None
    # a lowered GEMM whose accumulator groups land in reverse order
    prog, shared, shapes = PROGRAMS["3x3 stride-1 conv"]()
    trace = lower(prog, hw, {k: v.shape for k, v in shared.items()} | shapes)
    i, op = next((i, op) for i, op in enumerate(trace.ops)
                 if isinstance(op, GemmOp) and not op.reset)
    G, R, lp0, lp1 = op.window[0][:4]
    assert G > 1
    acc = op.acc_idx.reshape(lp0 * lp1, G, R)[:, ::-1]
    reversed_ = dataclasses.replace(op, acc_idx=acc.reshape(-1).copy())
    assert _window_matches(op.window, op)
    assert not _window_matches(op.window, reversed_)
    trace.ops[i] = dataclasses.replace(reversed_, window=None)
    want, got, forms = _run_both(prog, shared, shapes, trace)
    assert forms["gather"] == 1 and forms["window"] > 0
    assert fsim_jax.indexed_bytes_by_class()["gemm"] > 0
    for t in trace.tensors_written:
        np.testing.assert_array_equal(
            np.asarray(got[t]).reshape(want[t].shape), want[t])


def test_a_batch_tiled_gemm_gathers_and_stays_exact():
    """Two images per tile: the accumulator groups of each batch lane read
    different input rows, so the proof refuses the GEMM; its reset is
    still a block."""
    prog, shared, shapes = _conv_program(
        ConvWorkload("c3b2", 2, 8, 8, 3, 3, 16, 32, 1, 1, 1, 1))
    trace = lower(prog, DEFAULT_VTA,
                  {k: v.shape for k, v in shared.items()} | shapes)
    gemms = [op for op in trace.ops if isinstance(op, GemmOp)]
    assert [(op.reset, op.window is not None) for op in gemms] == \
        [(True, True), (False, False)]
    want, got, forms = _run_both(prog, shared, shapes, trace)
    assert forms == {"window": 1, "gather": 1}
    for t in trace.tensors_written:
        np.testing.assert_array_equal(
            np.asarray(got[t]).reshape(want[t].shape), want[t])


@pytest.mark.parametrize("network", ["resnet18", "mobilenet"])
def test_every_served_gemm_reads_windows_and_the_log_resets(network):
    model = served_model(network, "small")
    fsim_jax.reset_kernel_launch_log()
    model.run_batch(model.random_images(8, seed=7), backend="jax")
    assert fsim_jax.gemm_form_log() == {"window": len(_served_gemms(model)),
                                        "gather": 0}
    fsim_jax.reset_kernel_launch_log()
    assert fsim_jax.gemm_form_log() == {"window": 0, "gather": 0}


def test_the_benchmark_reads_the_share_of_windowed_gemms():
    from chipbench import harness
    read = harness.load_reader(harness.BENCH, "gemm_window_share")
    fsim_jax.reset_kernel_launch_log()
    assert read({}) is None                      # no GEMM dispatched yet
    model = served_model("resnet18", "small")
    model.run_batch(model.random_images(8, seed=8), backend="jax")
    assert read({}) == 100
