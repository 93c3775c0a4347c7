"""Fused fast-path coverage: ALU-chain fusion, whole-segment launches, the
kernel registry, and per-kernel divergence localization.

Everything here guards one invariant: ``JaxBackend``'s fused execution
(fused ALU chains, whole-segment launches, the capped chunks of every other
program; with the einsum GEMM and with the chip's Pallas GEMM in interpret
mode) is bit-exact vs the sequential numpy ``FSim`` — on padded edges, int8
extremes, batched runs — while actually fusing (asserted via the
kernel-launch counter, not just by producing right answers)."""
import copy
import math

import numpy as np
import pytest

from repro.core.tps import ConvWorkload
from repro.kernels import available_impls, get_kernel, register_kernel
from repro.vta import fsim_jax
from repro.vta.backend import get_backend, register_backend, _jax_factory
from repro.vta.compiler import compile_graph
from repro.vta.fsim import depthwise_ref, post_op_ref
from repro.vta.graph import Graph
from repro.vta.isa import DEFAULT_VTA, PIPELINED_VTA, AluInsn, AluOp
from repro.vta.lowering import AluChain, enclosing_kernel, lower, lower_cached
from repro.vta.runtime import Program
from repro.vta.scheduler import schedule_depthwise, schedule_pool
from repro.vta.trace import diff_backends, first_divergence, record_trace
from repro.vta.workloads import _add, _conv

RNG = np.random.default_rng(23)


def _fused_segment_case():
    """conv -> residual add -> clip compiled as one multi-node segment."""
    hw = DEFAULT_VTA
    g = Graph(name="t")
    g.input("image", (1, 16, 8, 8))
    g.layer(_conv("a", 1, 8, 16, 16, 3, 1, 1), "image")
    g.layer(_conv("b", 1, 8, 16, 16, 3, 1, 1), "a")
    g.residual_add("add", "b", "a", layer=_add("add", 1, 8, 16))
    seg = [s for s in compile_graph(g, hw) if s.multi][0]
    dram = {"a": RNG.integers(-64, 64, (1, 16, 8, 8), dtype=np.int8),
            "b.wgt": RNG.integers(-8, 8, (16, 16, 3, 3), dtype=np.int8),
            "add": np.zeros((1, 16, 8, 8), np.int8)}
    return hw, seg.program, dram


def _depthwise_case(hw=PIPELINED_VTA, *, h=28, c=256, stride=1):
    """3x3 padded depthwise with full int8-range activations."""
    wl = ConvWorkload("dw", 1, h, h, 3, 3, c, c, 1, 1, stride, stride,
                      depthwise=True)
    prog = schedule_depthwise(wl, hw).program
    dram = {"inp": RNG.integers(-128, 128, (1, c, h, h), dtype=np.int8),
            "dw_wgt": RNG.integers(-8, 8, (c, 3, 3), dtype=np.int8),
            "out": np.zeros((1, wl.fo, wl.oh, wl.ow), np.int8)}
    return prog, dram


def _resident_spill_case():
    """A 3x3 conv whose output feeds a 1x1 conv through the scratchpad:
    one segment with a resident edge, compiler-marked fused."""
    hw = DEFAULT_VTA
    g = Graph(name="chain")
    g.input("image", (1, 16, 8, 8))
    g.layer(_conv("c1", 1, 8, 16, 16, 3, 1, 1), "image")
    g.layer(_conv("c2", 1, 8, 16, 32, 1, 0, 1), "c1")
    seg = compile_graph(g, hw)[0]
    assert seg.resident_edges == ("c1->c2",)
    dram = {"image": RNG.integers(-128, 128, (1, 16, 8, 8), dtype=np.int8),
            "c1.wgt": RNG.integers(-8, 8, (16, 16, 3, 3), dtype=np.int8),
            "c2.wgt": RNG.integers(-8, 8, (32, 16, 1, 1), dtype=np.int8),
            "c2": np.zeros((1, 32, 8, 8), np.int8)}
    return hw, seg.program, dram


def _run_fused_vs_numpy(prog, hw, dram, *, backend=None):
    """(jax dram, numpy dram, launch count) — asserts bit-exact outputs."""
    be = backend or get_backend("jax")
    d_jx = {k: v.copy() for k, v in dram.items()}
    fsim_jax.reset_kernel_launch_log()
    be.run(prog, hw, d_jx)
    launches = fsim_jax.kernel_launch_log()
    d_np = {k: v.copy() for k, v in dram.items()}
    get_backend("numpy").run(prog, hw, d_np)
    for k in dram:
        np.testing.assert_array_equal(d_jx[k], d_np[k])
    return d_jx, d_np, launches


# ---------------------------------------------------------------------------
# Whole-segment fusion: one kernel launch per segment program
# ---------------------------------------------------------------------------
def test_fused_conv_add_clip_segment_is_one_launch():
    hw, prog, dram = _fused_segment_case()
    assert getattr(prog, "fused_segment", False)
    out, _, launches = _run_fused_vs_numpy(prog, hw, dram)
    assert launches == 1
    assert np.any(out["add"])        # non-trivial result, not an all-zero tie


def test_resident_spill_chain_is_one_launch():
    hw, prog, dram = _resident_spill_case()
    assert getattr(prog, "fused_segment", False)
    out, _, launches = _run_fused_vs_numpy(prog, hw, dram)
    assert launches == 1
    assert np.any(out["c2"])


def test_fused_segment_falls_back_over_the_op_cap(monkeypatch):
    """Programs longer than SEGMENT_FUSION_MAX_OPS run chunked (compile-time
    guard) and stay bit-exact."""
    monkeypatch.setattr(fsim_jax, "SEGMENT_FUSION_MAX_OPS", 2)
    monkeypatch.setattr(fsim_jax, "CHUNK_CAP", 4)   # chunking visible
    hw, prog, dram = _fused_segment_case()      # fresh program: empty memos
    _, _, launches = _run_fused_vs_numpy(prog, hw, dram)
    assert launches > 1


def test_unfused_program_runs_in_capped_chunks():
    """A program the compiler did not mark fused launches one chunk per
    ``CHUNK_CAP`` entries (its fused ALU sweeps hold no store to close a
    chunk early) and stays bit-exact."""
    prog, dram = _depthwise_case()
    trace = lower(prog, PIPELINED_VTA, {k: v.shape for k, v in dram.items()})
    assert not trace.fused_segment
    entries = len(fsim_jax._spec_of(trace))
    assert entries > fsim_jax.CHUNK_CAP
    _, _, launches = _run_fused_vs_numpy(prog, PIPELINED_VTA, dram)
    assert launches == math.ceil(entries / fsim_jax.CHUNK_CAP)


def test_fused_segment_batched_run_matches_numpy():
    hw, prog, dram = _fused_segment_case()
    N = 3
    shared = {"b.wgt": dram["b.wgt"]}
    batched = {"a": np.stack([RNG.integers(-128, 128, dram["a"].shape,
                                           dtype=np.int8)
                              for _ in range(N)]),
               "add": np.zeros((N,) + dram["add"].shape, np.int8)}
    fsim_jax.reset_kernel_launch_log()
    o_jx = get_backend("jax").run_batched(
        prog, hw, shared=shared,
        batched={k: v.copy() for k, v in batched.items()})
    assert fsim_jax.kernel_launch_log() == 1    # one launch for the batch
    o_np = get_backend("numpy").run_batched(
        prog, hw, shared=shared,
        batched={k: v.copy() for k, v in batched.items()})
    np.testing.assert_array_equal(
        np.asarray(o_jx["add"]).reshape(o_np["add"].shape), o_np["add"])


# ---------------------------------------------------------------------------
# ALU-chain fusion: depthwise / pool sweeps as single kernels
# ---------------------------------------------------------------------------
def test_lowering_marks_depthwise_chains():
    prog, dram = _depthwise_case()
    trace = lower(prog, PIPELINED_VTA, {k: v.shape for k, v in dram.items()})
    assert trace.alu_chains, "depthwise program must produce fused chains"
    known = {"seed_imm", "seed_copy", "seed_mac", "read_dst", "mac", "red",
             "src", "imm"}
    for c in trace.alu_chains:
        assert isinstance(c, AluChain) and len(c.members) >= 2
        assert c.unique                          # fusion-legality invariant
        assert {s[0] for s in c.stages} <= known
        # the depthwise shape: MAC seed, tap sweep, then requant epilogue
        assert c.stages[0][0] in ("seed_mac", "read_dst", "seed_copy",
                                  "seed_imm")
        # attribution span covers the whole fused kernel: the chain
        # members plus any elided feeder gathers / absorbed store
        kern = enclosing_kernel(trace, c.members[0])
        assert kern is not None and kern[0] == "aluchain"
        lo, hi = kern[1], kern[2]
        assert lo <= c.members[0] and hi >= c.members[-1]
    # the depthwise sweeps go DRAM-direct: feeder gathers become in-kernel
    # slabs, the following store is absorbed, and since nothing re-reads
    # the chain's acc rows the scratchpad is bypassed entirely
    direct = [c for c in trace.alu_chains if c.slabs]
    assert direct, "depthwise chains must resolve to DRAM-direct sweeps"
    for c in direct:
        assert {s.tensor for s in c.slabs} <= set(dram)
        assert c.store is not None and c.store.tensor == "out"
        assert not c.write_acc
    assert trace.elided, "feeder gathers/stores must be elided"
    # chains lower to single alusweep/aluchain entries
    fused_kinds = {e[0] for e, _ in fsim_jax._spec_of(trace)}
    assert fused_kinds & {"aluchain", "alusweep"}


@pytest.mark.parametrize("stride", [1, 2])
def test_depthwise_fused_matches_numpy_and_reference(stride):
    """Padded edges + full int8-range activations, fused vs numpy vs the
    analytical depthwise reference."""
    hw = PIPELINED_VTA
    prog, dram = _depthwise_case(hw, h=28, c=128, stride=stride)
    out, _, _ = _run_fused_vs_numpy(prog, hw, dram)
    acc = depthwise_ref(dram["inp"], dram["dw_wgt"], (stride, stride), (1, 1))
    ref = post_op_ref(acc, "relu_shift")      # schedule_depthwise default
    np.testing.assert_array_equal(out["out"], ref)


@pytest.mark.parametrize("mode,wl", [
    ("max", ConvWorkload("pool", 1, 14, 14, 3, 3, 16, 16, 1, 1, 2, 2)),
    ("avg", ConvWorkload("gap", 1, 7, 7, 7, 7, 64, 64, 0, 0, 7, 7)),
])
def test_pool_fused_matches_numpy(mode, wl):
    hw = PIPELINED_VTA
    prog = schedule_pool(wl, hw, mode=mode).program
    dram = {"inp": RNG.integers(-128, 128, (1, wl.fi, wl.h, wl.w),
                                dtype=np.int8),
            "out": np.zeros((1, wl.fo, wl.oh, wl.ow), np.int8)}
    trace = lower(prog, hw, {k: v.shape for k, v in dram.items()})
    assert trace.alu_chains
    _run_fused_vs_numpy(prog, hw, dram)


def test_direct_store_affine_decomposition_is_exact():
    """``_affine_block``'s claim is elementwise: reshaping the flat tensor
    to the view and slicing at the block starts must select exactly the
    positions the scatter index map names, in the same order."""
    prog, dram = _depthwise_case()
    trace = lower(prog, PIPELINED_VTA, {k: v.shape for k, v in dram.items()})
    checked = 0
    for c in trace.alu_chains:
        st = c.store
        if st is None or st.affine is None:
            continue
        view_shape, perm, sizes, starts = st.affine
        n = int(np.prod(dram[st.tensor].shape))
        positions = np.arange(n).reshape(view_shape)
        block = positions[tuple(slice(s, s + z)
                                for s, z in zip(starts, sizes))]
        np.testing.assert_array_equal(
            block, st.index.transpose(perm).reshape(sizes))
        checked += 1
    assert checked, "depthwise stores must decompose to affine blocks"


def _call_sweep(fn, acc, c, dram, *, force_scatter=False):
    """Drive an alu_sweep impl with a real chain's full descriptor set."""
    import jax.numpy as jnp
    slabs = []
    for s in c.slabs:
        flat = jnp.asarray(dram[s.tensor].reshape(-1))
        mask = jnp.asarray(s.mask) if s.mask is not None else None
        slabs.append((flat, jnp.asarray(s.index), mask, s.fill))
    oa = []
    for src, a in zip(c.arg_src, c.args):
        if isinstance(src, str):
            oa.append(("acc", jnp.asarray(a)))
        else:
            oa.append((src[0], jnp.asarray(src[1])))
    kw = {}
    st = c.store
    if st is not None:
        kw["out_flat"] = jnp.asarray(dram[st.tensor].reshape(-1))
        kw["store_unique"], kw["store_sorted"] = st.unique, st.sorted
        if st.affine is not None and not force_scatter:
            view_shape, perm, sizes, starts = st.affine
            kw["store_affine"] = (view_shape, perm, sizes)
            kw["store_idx"] = jnp.asarray(np.asarray(starts, np.int32))
        else:
            kw["store_idx"] = jnp.asarray(st.index)
            if st.mask is not None:
                kw["store_mask"] = jnp.asarray(st.mask)
    acc2, out2 = fn(jnp.asarray(acc), jnp.asarray(c.dst), c.stages, oa,
                    slabs=slabs, write_acc=c.write_acc, unique=c.unique,
                    sorted_=c.sorted, **kw)
    return (np.asarray(acc2), None if out2 is None else np.asarray(out2))


def test_direct_sweep_lax_pallas_and_scatter_agree():
    """One DRAM-direct chain, two ways: the lax sweep with the affine
    store and the lax sweep forced onto the scatter fallback —
    byte-identical."""
    hw = PIPELINED_VTA
    prog, dram = _depthwise_case(hw, h=14, c=64)
    trace = lower(prog, hw, {k: v.shape for k, v in dram.items()})
    direct = [c for c in trace.alu_chains
              if c.slabs and c.store is not None
              and c.store.affine is not None]
    assert direct, "expected affine-store direct sweeps"
    acc = RNG.integers(-2**24, 2**24,
                       (hw.acc_depth, hw.batch, hw.block_out),
                       dtype=np.int32)
    lax_fn = get_kernel("alu_sweep", "lax")
    for c in direct[:2]:
        a_aff, o_aff = _call_sweep(lax_fn, acc, c, dram)
        a_sc, o_sc = _call_sweep(lax_fn, acc, c, dram, force_scatter=True)
        np.testing.assert_array_equal(o_aff, o_sc)
        np.testing.assert_array_equal(a_aff, a_sc)
        assert np.any(o_aff != dram[c.store.tensor].reshape(-1))


@pytest.mark.parametrize("case", ["depthwise", "fused-segment",
                                  "resident-spill"])
def test_jax_pallas_backend_bit_exact(case):
    """The executor with the GEMM kernel a TPU runs (the Pallas
    ``blocked_gemm``, interpret mode on the CPU) agrees with numpy: a
    depthwise program, a fused conv -> add -> clip segment and a resident
    spill chain."""
    if case == "depthwise":
        hw = DEFAULT_VTA
        prog, dram = _depthwise_case(hw, h=8, c=16)
    elif case == "fused-segment":
        hw, prog, dram = _fused_segment_case()
    else:
        hw, prog, dram = _resident_spill_case()
    be = fsim_jax.JaxBackend()
    be.gemm_impl = "pallas_interpret"
    out, _, _ = _run_fused_vs_numpy(prog, hw, dram, backend=be)
    assert all(np.any(v) for v in out.values())


# ---------------------------------------------------------------------------
# Kernel registry
# ---------------------------------------------------------------------------
def test_kernel_registry_contracts():
    assert {"einsum", "pallas", "pallas_interpret"} <= \
        set(available_impls("gemm"))
    assert available_impls("alu_chain") == ["lax"]
    assert available_impls("alu_sweep") == ["lax"]
    with pytest.raises(KeyError, match="einsum"):
        get_kernel("gemm", "not-an-impl")
    with pytest.raises(KeyError, match="gemm"):
        get_kernel("not-a-kernel", "einsum")
    with pytest.raises(ValueError):
        register_kernel("gemm", "einsum", lambda x, w: x)
    register_kernel("gemm", "einsum", get_kernel("gemm", "einsum"),
                    replace=True)                # replace is explicit opt-in


# ---------------------------------------------------------------------------
# Divergence localization to fused kernels
# ---------------------------------------------------------------------------
def test_diff_backends_localizes_into_fused_segment_kernel():
    """A backend bug inside a fused segment must be reported as living in
    that fused kernel, not just at a bare instruction index."""
    import jax.numpy as jnp
    hw, prog, dram = _fused_segment_case()
    register_kernel(
        "gemm", "broken-for-test",
        lambda x, w: jnp.dot(x, w, preferred_element_type=jnp.float32) + 1.0,
        replace=True)

    def broken():
        be = fsim_jax.JaxBackend()
        be.gemm_impl = "broken-for-test"
        return be
    register_backend("jax", broken, replace=True)
    try:
        diff = diff_backends(prog, hw, dram)
    finally:
        register_backend("jax", _jax_factory, replace=True)
    div = diff.divergence
    assert div is not None and not diff.outputs_equal
    assert div.kernel == ("segment", 0, len(prog.order) - 1)
    assert div.kernel[1] <= div.step <= div.kernel[2]
    assert "fused segment kernel" in div.describe()


def test_divergence_attributes_to_single_alu_chain():
    """An imm corruption inside a fused sweep localizes to exactly one
    chain kernel (the per-kernel attribution diff_backends attaches)."""
    hw = DEFAULT_VTA
    prog, dram = _depthwise_case(hw, h=8, c=16)
    a = record_trace(prog, hw, {k: v.copy() for k, v in dram.items()})
    bad = Program(hw=prog.hw, order=[copy.copy(i) for i in prog.order],
                  uop_mem=prog.uop_mem, n_ctx=prog.n_ctx)
    step = next(i for i, insn in enumerate(bad.order)
                if isinstance(insn, AluInsn) and insn.alu_op == AluOp.SHR)
    bad.order[step] = copy.copy(bad.order[step])
    bad.order[step].imm = 7
    c = record_trace(bad, hw, {k: v.copy() for k, v in dram.items()})
    div = first_divergence(a, c)
    assert div is not None and div.step == step
    trace = lower_cached(bad, hw, {k: v.shape for k, v in dram.items()})
    div.kernel = enclosing_kernel(trace, div.step)
    assert div.kernel is not None and div.kernel[0] == "aluchain"
    lo, hi = div.kernel[1], div.kernel[2]
    assert lo <= step <= hi
    # exactly ONE chain claims this step
    owners = [ch for ch in trace.alu_chains
              if ch.members[0] <= step <= ch.members[-1]]
    assert len(owners) == 1
    assert "fused aluchain kernel" in div.describe()
