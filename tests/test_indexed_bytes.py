"""``fsim_jax.indexed_bytes_by_class()``: the bytes the executor moves
through index arrays, by VTA instruction class. On the tiny served models
it equals a count made independently from the programs the chunks trace
to: every gather, scatter and ``.at[idx]`` update left in each chunk's
vmapped jaxpr once dead code is gone, at its dtype's width; it grows by the
same amount on every batch, resets with the launch log, and at published
widths MobileNet's ALU part is larger per image than ResNet-18's."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.interpreters import partial_eval as pe

from repro.serve.model import ServedModel, device_graph, served_model
from repro.vta import fsim_jax
from repro.vta.isa import DEFAULT_VTA
from repro.vta.lowering import lower_cached
from repro.vta.workloads import network_graph

AXES = {"inp": 0, "wgt": 0, "acc": 0, "tensors": 0}


def _walk(jaxpr, out: dict, cls=None) -> None:
    """Add each indexed access of ``jaxpr`` to ``out[class]``. A gather or
    scatter whose indices carry no batch dimension addresses one window: it
    is a dynamic slice that ``vmap`` rewrote, and counts nothing."""
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        here = next((k for k in fsim_jax.INDEXED_CLASSES
                     if f"vta.{k}" in stack), cls)
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            if eqn.invars[1].aval.ndim >= 2:
                moved = (eqn.outvars[0] if name == "gather"
                         else eqn.invars[2]).aval
                out[here] = out.get(here, 0) + \
                    math.prod(moved.shape) * moved.dtype.itemsize
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _walk(inner, out, here)


def _jaxpr_count(model, n: int, gemm_impl: str) -> dict:
    """The bytes one batch of ``n`` moves through index arrays, read from
    the jaxpr of every chunk the backend dispatches, vmapped as
    ``_exec_chunk`` vmaps it (weights and biases unbatched)."""
    inp_depth, BV, BI, wgt_depth, BO, acc_depth = fsim_jax._geom_of(model.hw)
    sds = jax.ShapeDtypeStruct
    out = dict.fromkeys(fsim_jax.INDEXED_CLASSES, 0)
    for seg in model.segments:
        weights = model._weights_of(seg)
        acts = model._activations(seg)
        shapes = {t: model.shapes[t] for t in acts}
        shapes.update({t: w.shape for t, w in weights.items()})
        trace = lower_cached(seg.program, model.hw, shapes)
        names = fsim_jax._tensor_names(trace)
        state = {"inp": sds((n, inp_depth, BV, BI), jnp.int8),
                 "wgt": sds((n, wgt_depth, BO, BI), jnp.int8),
                 "acc": sds((n, acc_depth, BV, BO), jnp.int32),
                 "tensors": {names[t]: sds((n, math.prod(model.shapes[t])),
                                           jnp.int8) for t in acts},
                 "shared": {names[k]: sds((w.size,), w.dtype)
                            for k, w in weights.items()}}
        for spec, args in fsim_jax._spec_chunks(trace):

            def chunk(args, state, spec=spec):
                def body(st):
                    inner = {"inp": st["inp"], "wgt": st["wgt"],
                             "acc": st["acc"],
                             "tensors": {**st["tensors"], **st["shared"]}}
                    fsim_jax._exec_entries(spec, args, inner, gemm_impl)
                    return {k: inner[k] for k in ("inp", "wgt", "acc")} | {
                        "tensors": {k: inner["tensors"][k]
                                    for k in st["tensors"]}}
                return jax.vmap(body, in_axes=(AXES | {"shared": None},),
                                out_axes=AXES)(state)

            closed = jax.make_jaxpr(chunk)(
                tuple(sds(np.shape(a), np.asarray(a).dtype) for a in args),
                state)
            live, _ = pe.dce_jaxpr(closed.jaxpr,
                                   [True] * len(closed.jaxpr.outvars))
            _walk(live, out)
    return out


@pytest.mark.parametrize("n", [1, 8])
@pytest.mark.parametrize("network", ["resnet18", "mobilenet"])
def test_the_counter_equals_the_indexed_accesses_of_the_traced_chunks(
        network, n):
    model = served_model(network, "tiny")
    be = fsim_jax.JaxBackend()
    fsim_jax.reset_kernel_launch_log()
    model.run_batch(model.random_images(n, seed=4), backend=be)
    got = fsim_jax.indexed_bytes_by_class()
    assert list(got) == list(fsim_jax.INDEXED_CLASSES)
    assert got == _jaxpr_count(model, n, be.gemm_impl)
    # every GEMM of the served models reads windows of the scratchpads
    assert got["alu"] > 0 and got["gemm"] == 0


def test_the_counter_grows_by_one_batch_each_batch_and_resets():
    model = served_model("mobilenet", "tiny")
    images = model.random_images(8, seed=2)
    fsim_jax.reset_kernel_launch_log()
    seen = []
    for _ in range(3):
        model.run_batch(images, backend="jax")
        seen.append(fsim_jax.indexed_bytes_by_class())
    for cls in fsim_jax.INDEXED_CLASSES:
        assert [s[cls] for s in seen] == [(i + 1) * seen[0][cls]
                                          for i in range(3)]
    fsim_jax.reset_kernel_launch_log()
    assert fsim_jax.indexed_bytes_by_class() == dict.fromkeys(
        fsim_jax.INDEXED_CLASSES, 0)


def _full_width_bytes(network: str, n: int = 8) -> dict:
    """The bytes by class that one batch of ``n`` of the full-width network
    counts, from the lowered chunks alone (no program runs)."""
    model = ServedModel.compile(network, device_graph(network_graph(network)),
                                DEFAULT_VTA)
    total = dict.fromkeys(fsim_jax.INDEXED_CLASSES, 0)
    for seg in model.segments:
        weights = model._weights_of(seg)
        batched = {t: np.broadcast_to(np.int8(0), (n,) + model.shapes[t])
                   for t in model._activations(seg)}
        shapes = {t: model.shapes[t] for t in batched}
        shapes.update({t: w.shape for t, w in weights.items()})
        trace = lower_cached(seg.program, model.hw, shapes)
        chunks = fsim_jax._spec_chunks(trace)
        for cls, b in fsim_jax._indexed_bytes(
                trace, chunks, ("full width", n), model.hw, batched,
                weights).items():
            total[cls] += b
    return total


@pytest.fixture(scope="module")
def full_width() -> dict:
    return {net: _full_width_bytes(net) for net in ("resnet18", "mobilenet")}


def test_mobilenet_moves_more_alu_bytes_per_image_than_resnet18(full_width):
    """The depthwise taps: at published widths MobileNet-1.0's ALU part
    (521.67 MB per batch of 8) is above ResNet-18's (358.64 MB), though
    its GEMM work is a third of ResNet's."""
    assert (full_width["mobilenet"]["alu"], full_width["resnet18"]["alu"]) \
        == (521_666_432, 358_642_176)


def test_no_load_of_the_full_width_networks_goes_through_an_index(
        full_width):
    """Every load is a strided slice of its padded tensor: the ``load``
    part, 72,043,456 bytes (ResNet-18) and 39,096,256 (MobileNet-1.0) per
    batch of 8 while loads were element gathers, is 0."""
    assert (full_width["resnet18"]["load"],
            full_width["mobilenet"]["load"]) == (0, 0)


def test_no_gemm_of_the_full_width_networks_goes_through_an_index(
        full_width):
    """Every GEMM reads windows of the scratchpads: the ``gemm`` part,
    1,218,096,128 bytes (ResNet-18) and 763,517,952 (MobileNet-1.0) per
    batch of 8 while the GEMMs gathered their input and weight rows and
    scattered their accumulator rows, is 0."""
    assert (full_width["resnet18"]["gemm"],
            full_width["mobilenet"]["gemm"]) == (0, 0)


def _full_width_gemm_macs(network: str) -> tuple:
    """(the MACs per image ``gemm_mac_log`` adds, the MACs per image the
    chunks' windowed GEMM entries contract) at published widths, from the
    lowered chunks alone (no program runs)."""
    model = ServedModel.compile(network, device_graph(network_graph(network)),
                                DEFAULT_VTA)
    _, BV, BI, _, BO, _ = fsim_jax._geom_of(model.hw)
    logged = contracted = 0
    for seg in model.segments:
        shapes = {t: model.dram_shapes[t] for t in model._activations(seg)}
        shapes.update({t: w.shape for t, w in model._weights_of(seg).items()})
        trace = lower_cached(seg.program, model.hw, shapes)
        logged += fsim_jax._gemm_macs(trace)
        for spec, _ in fsim_jax._spec_chunks(trace):
            for e in spec:
                if e[0] == "gemm_window" and not e[1]:
                    G, R, lp0, lp1 = e[2][:4]
                    contracted += G * R * lp0 * lp1 * BV * BI * BO
    return logged, contracted


@pytest.mark.parametrize("network,macs", [
    ("resnet18", 1_696_063_488), ("mobilenet", 540_524_544),
    ("mobilenetv2", 282_476_544)])
def test_the_windowed_gemms_contract_the_macs_the_log_counts(network, macs):
    """``gemm_mac_log`` per image is what it was while the GEMMs gathered
    rows, and the windows contract exactly those MACs: no column a window
    reads past its rows enters the contraction."""
    assert _full_width_gemm_macs(network) == (macs, macs)
