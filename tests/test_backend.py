"""Execution-backend layer: lowering, registry, numpy-vs-jax bit-exactness
on resnet18/mobilenet layer programs (incl. fused conv→add→clip segments,
resident chains, on-chip spills, padded depthwise/pool edges), batched
verification, the hazard checker, and the trace divergence tooling."""
import numpy as np
import pytest

from repro.core.tps import ConvWorkload, tps_search
from repro.vta.backend import (NumpyBackend, available_backends, get_backend,
                               register_backend)
from repro.vta.compiler import compile_graph
from repro.vta.fsim import conv2d_ref, post_op_ref
from repro.vta.graph import Graph
from repro.vta.isa import (DEFAULT_VTA, PIPELINED_VTA, AluInsn, AluOp,
                           Buffer, LoadInsn, Op, StoreInsn)
from repro.vta.lowering import insn_dram_bytes, lower, lower_ranges
from repro.vta.runtime import Program, Task, UopAllocator, finalize
from repro.vta.scheduler import (program_dram_bytes, schedule_conv,
                                 schedule_depthwise, schedule_pool)
from repro.vta.trace import diff_backends, first_divergence, record_trace
from repro.vta.tsim import HazardError, run_tsim
from repro.vta.workloads import _add, _conv

RNG = np.random.default_rng(11)


def _conv_case(wl, hw, *, post_op="clip_shift", bias=False, dedup=False):
    res = tps_search(wl, hw, require_db=True)
    if not res.feasible:
        res = tps_search(wl, hw)
    assert res.feasible
    sched = schedule_conv(wl, res.tiling, hw, post_op=post_op,
                          dedup_loads=dedup, bias=bias)
    dram = {"inp": RNG.integers(-32, 32, (wl.b, wl.fi, wl.h, wl.w),
                                dtype=np.int8),
            "wgt": RNG.integers(-8, 8, (wl.fo, wl.fi, wl.kh, wl.kw),
                                dtype=np.int8),
            "out": np.zeros((wl.b, wl.fo, wl.oh, wl.ow), np.int8)}
    if bias:
        dram["bias"] = RNG.integers(-100, 100, (wl.fo,), dtype=np.int32)
    return sched.program, dram


def _run_both(prog, hw, dram):
    """Execute on both backends; assert byte-identical outputs, localizing
    the first diverging instruction on failure (vta/trace.py)."""
    d_np = {k: v.copy() for k, v in dram.items()}
    d_jx = {k: v.copy() for k, v in dram.items()}
    get_backend("numpy").run(prog, hw, d_np)
    get_backend("jax").run(prog, hw, d_jx)
    for k in dram:
        if not np.array_equal(d_np[k], d_jx[k]):
            diff = diff_backends(prog, hw, dram)
            where = diff.divergence.describe() if diff.divergence \
                else "outputs differ but per-insn digests agree"
            raise AssertionError(f"backend mismatch on {k!r}: {where}")
    return d_np


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
def test_registry_resolves_and_rejects():
    assert "numpy" in available_backends()
    assert "jax" in available_backends()
    be = get_backend("numpy")
    assert be.name == "numpy" and get_backend(None) is be
    assert get_backend(be) is be                 # instances pass through
    with pytest.raises(KeyError):
        get_backend("verilog")
    with pytest.raises(ValueError):
        register_backend("numpy", NumpyBackend)  # duplicate name


# ---------------------------------------------------------------------------
# Lowering invariants
# ---------------------------------------------------------------------------
def test_lowering_dram_bytes_match_program_accounting():
    wl = ConvWorkload("c8", 1, 14, 14, 3, 3, 256, 256, 1, 1, 1, 1)
    prog, dram = _conv_case(wl, PIPELINED_VTA, dedup=True)
    trace = lower(prog, PIPELINED_VTA, {k: v.shape for k, v in dram.items()})
    by_insn = sum(insn_dram_bytes(i, PIPELINED_VTA) for i in prog.order)
    by_ops = sum(getattr(op, "dram_bytes", 0) for op in trace.ops
                 if op is not None)
    # uop loads carry bytes at insn level but no trace-op accounting
    uop_bytes = sum(insn_dram_bytes(i, PIPELINED_VTA) for i in prog.order
                    if isinstance(i, LoadInsn) and i.buffer == Buffer.UOP)
    assert by_ops == by_insn - uop_bytes
    assert program_dram_bytes(prog, PIPELINED_VTA)["total"] == by_insn
    assert trace.tensors_written == ("out",)
    assert set(trace.tensors_read) == {"inp", "wgt"}


def test_lower_ranges_covers_every_insn():
    wl = ConvWorkload("c8", 1, 14, 14, 3, 3, 256, 256, 1, 1, 1, 1)
    prog, _ = _conv_case(wl, PIPELINED_VTA)
    touches = lower_ranges(prog, PIPELINED_VTA)
    assert len(touches) == len(prog.order)
    # every GEMM both reads and writes acc (accumulate), loads only write
    for insn, t in zip(prog.order, touches):
        for b, lo, hi in t.reads + t.writes:
            assert 0 <= lo < hi
        if isinstance(insn, LoadInsn):
            assert not t.reads and len(t.writes) == 1


# ---------------------------------------------------------------------------
# Backend equivalence: resnet18 layer programs
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("wl,kw", [
    # resnet18 C8 (3x3 pad s1, double-buffered + dedup)
    (ConvWorkload("r18.C8", 1, 14, 14, 3, 3, 256, 256, 1, 1, 1, 1),
     dict(dedup=True)),
    # resnet18 C10 (1x1 stride 2 downsample)
    (ConvWorkload("r18.C10", 1, 14, 14, 1, 1, 256, 512, 0, 0, 2, 2), {}),
    # resnet18 fc (dense + bias, no post-op)
    (ConvWorkload("r18.fc", 1, 1, 1, 1, 1, 512, 1008, 0, 0, 1, 1),
     dict(post_op="none", bias=True)),
    # mobilenet pw3 (1x1 pointwise, relu_shift)
    (ConvWorkload("mbn.pw3", 1, 28, 28, 1, 1, 256, 256, 0, 0, 1, 1),
     dict(post_op="relu_shift")),
])
def test_backend_equivalence_conv(wl, kw):
    prog, dram = _conv_case(wl, PIPELINED_VTA, **kw)
    out = _run_both(prog, PIPELINED_VTA, dram)
    b = dram.get("bias")
    ref = post_op_ref(conv2d_ref(dram["inp"], dram["wgt"], (wl.sh, wl.sw),
                                 (wl.ph, wl.pw), b),
                      kw.get("post_op", "clip_shift"))
    np.testing.assert_array_equal(out["out"], ref)


@pytest.mark.parametrize("wl,mode", [
    # mobilenet dw4 (3x3 s1, padded edges)
    (ConvWorkload("mbn.dw4", 1, 28, 28, 3, 3, 256, 256, 1, 1, 1, 1,
                  depthwise=True), "dw"),
    # mobilenet dw1 (3x3 stride 2, padded)
    (ConvWorkload("mbn.dw1", 1, 56, 56, 3, 3, 128, 128, 1, 1, 2, 2,
                  depthwise=True), "dw"),
    # resnet18 pool1 (3x3 s2 maxpool, INT8_MIN pad + clamped edge tiles)
    (ConvWorkload("r18.pool1", 1, 112, 112, 3, 3, 64, 64, 1, 1, 2, 2),
     "max"),
    # resnet/mobilenet gap (7x7 avgpool)
    (ConvWorkload("gap", 1, 7, 7, 7, 7, 512, 512, 0, 0, 7, 7), "avg"),
])
def test_backend_equivalence_alu(wl, mode):
    hw = PIPELINED_VTA
    if mode == "dw":
        prog = schedule_depthwise(wl, hw).program
        dram = {"inp": RNG.integers(-64, 64, (1, wl.fi, wl.h, wl.w),
                                    dtype=np.int8),
                "dw_wgt": RNG.integers(-8, 8, (wl.fi, wl.kh, wl.kw),
                                       dtype=np.int8),
                "out": np.zeros((1, wl.fo, wl.oh, wl.ow), np.int8)}
    else:
        prog = schedule_pool(wl, hw, mode=mode).program
        dram = {"inp": RNG.integers(-128, 127, (1, wl.fi, wl.h, wl.w),
                                    dtype=np.int8),
                "out": np.zeros((1, wl.fo, wl.oh, wl.ow), np.int8)}
    _run_both(prog, hw, dram)


def test_backend_equivalence_fused_segment():
    """conv→add→clip fused segment program (multi-tensor DRAM)."""
    hw = DEFAULT_VTA
    g = Graph(name="t")
    g.input("image", (1, 16, 8, 8))
    g.layer(_conv("a", 1, 8, 16, 16, 3, 1, 1), "image")
    g.layer(_conv("b", 1, 8, 16, 16, 3, 1, 1), "a")
    g.residual_add("add", "b", "a", layer=_add("add", 1, 8, 16))
    fused = [s for s in compile_graph(g, hw) if s.multi]
    assert fused and fused[0].fused_adds == ("add",)
    prog = fused[0].program
    dram = {"a": RNG.integers(-64, 64, (1, 16, 8, 8), dtype=np.int8),
            "b.wgt": RNG.integers(-8, 8, (16, 16, 3, 3), dtype=np.int8),
            "add": np.zeros((1, 16, 8, 8), np.int8)}
    out = _run_both(prog, hw, dram)
    b8 = post_op_ref(conv2d_ref(dram["a"], dram["b.wgt"], (1, 1), (1, 1)),
                     "clip_shift")
    ref = np.clip(b8.astype(np.int32) + dram["a"].astype(np.int32),
                  -127, 127).astype(np.int8)
    np.testing.assert_array_equal(out["add"], ref)


def test_backend_equivalence_resident_chain_spill():
    """Resident two-conv chain: on-chip spill stores + loadless consumer."""
    hw = DEFAULT_VTA
    g = Graph(name="chain")
    g.input("image", (1, 16, 8, 8))
    g.layer(_conv("c1", 1, 8, 16, 16, 3, 1, 1), "image")
    g.layer(_conv("c2", 1, 8, 16, 32, 1, 0, 1), "c1")
    segs = compile_graph(g, hw)
    assert len(segs) == 1 and segs[0].resident_edges == ("c1->c2",)
    prog = segs[0].program
    assert any(getattr(i, "on_chip", False) for i in prog.order)
    dram = {"image": RNG.integers(-32, 32, (1, 16, 8, 8), dtype=np.int8),
            "c1.wgt": RNG.integers(-8, 8, (16, 16, 3, 3), dtype=np.int8),
            "c2.wgt": RNG.integers(-8, 8, (32, 16, 1, 1), dtype=np.int8),
            "c2": np.zeros((1, 32, 8, 8), np.int8)}
    out = _run_both(prog, hw, dram)
    c1 = post_op_ref(conv2d_ref(dram["image"], dram["c1.wgt"], (1, 1),
                                (1, 1)), "clip_shift")
    ref = post_op_ref(conv2d_ref(c1, dram["c2.wgt"]), "clip_shift")
    np.testing.assert_array_equal(out["c2"], ref)


def test_run_batched_matches_sequential():
    wl = ConvWorkload("c", 1, 14, 14, 3, 3, 32, 32, 1, 1, 1, 1)
    prog, dram = _conv_case(wl, DEFAULT_VTA)
    N = 4
    shared = {"wgt": dram["wgt"]}
    batched = {"inp": np.stack([RNG.integers(-32, 32, dram["inp"].shape,
                                             dtype=np.int8)
                                for _ in range(N)]),
               "out": np.zeros((N,) + dram["out"].shape, np.int8)}
    o_np = get_backend("numpy").run_batched(
        prog, DEFAULT_VTA, shared=shared,
        batched={k: v.copy() for k, v in batched.items()})
    o_jx = get_backend("jax").run_batched(
        prog, DEFAULT_VTA, shared=shared,
        batched={k: v.copy() for k, v in batched.items()})
    np.testing.assert_array_equal(
        o_np["out"], np.asarray(o_jx["out"]).reshape((N,) + dram["out"].shape))
    for i in range(N):
        ref = post_op_ref(conv2d_ref(batched["inp"][i], dram["wgt"],
                                     (1, 1), (1, 1)), "clip_shift")
        np.testing.assert_array_equal(o_np["out"][i], ref)


def test_tuner_verifies_on_jax_backend():
    """A LayerTuner bound to the jax backend commits the same tile as the
    numpy one (results are backend-invariant) and verifies batched."""
    from repro.vta.autotune import LayerTuner
    from repro.vta.workloads import pad_for_blocking
    hw = PIPELINED_VTA
    wl = pad_for_blocking(
        ConvWorkload("c", 1, 14, 14, 3, 3, 64, 128, 1, 1, 1, 1), hw)
    t_np = LayerTuner(mode="full").tune_conv(wl, hw)
    tuner = LayerTuner(mode="full").with_backend("jax", verify_batch=3)
    t_jx = tuner.tune_conv(wl, hw)
    assert t_np.tile == t_jx.tile and t_np.cycles == t_jx.cycles
    assert t_jx.verified and tuner.verify_seconds > 0


# ---------------------------------------------------------------------------
# Pallas GEMM kernel (interpret mode on CPU)
# ---------------------------------------------------------------------------
def test_blocked_gemm_interpret_matches_einsum():
    import jax.numpy as jnp
    from repro.kernels.vta_gemm import blocked_gemm
    x = RNG.integers(-128, 128, (24, 48)).astype(np.int8)
    w = RNG.integers(-128, 128, (48, 16)).astype(np.int8)
    got = np.asarray(blocked_gemm(jnp.asarray(x, jnp.float32),
                                  jnp.asarray(w, jnp.float32),
                                  interpret=True))
    ref = x.astype(np.float32) @ w.astype(np.float32)
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("lead,m,k,n", [((), 97, 130, 37), ((), 1, 16, 1),
                                        ((), 3, 5, 7), ((3,), 49, 288, 16)])
def test_blocked_gemm_odd_shapes_exact(lead, m, k, n):
    """Prime/odd dims exercise the padded + masked tail path — the shapes
    that used to collapse the grid to one degenerate block; ``lead`` is the
    batch of independent matmuls one launch runs."""
    import jax.numpy as jnp
    from repro.kernels.vta_gemm import blocked_gemm, gemm_blocking
    x = RNG.integers(-128, 128, lead + (m, k)).astype(np.int8)
    w = RNG.integers(-128, 128, lead + (k, n)).astype(np.int8)
    got = np.asarray(blocked_gemm(jnp.asarray(x, jnp.float32),
                                  jnp.asarray(w, jnp.float32),
                                  interpret=True))
    ref = x.astype(np.float32) @ w.astype(np.float32)
    np.testing.assert_array_equal(got, ref)
    bm, bn, bk = gemm_blocking(m, n, k)
    assert bm >= 1 and bn >= 8 and bk >= 8      # no degenerate 1-wide grid
    assert bm % 8 == 0 or bm >= m               # sublane-aligned or covers M


# ---------------------------------------------------------------------------
# run_tsim(check_hazards=True)
# ---------------------------------------------------------------------------
def test_hazard_checker_passes_real_programs():
    hw = PIPELINED_VTA
    wl = ConvWorkload("c", 1, 28, 28, 3, 3, 64, 128, 1, 1, 1, 1)
    res = tps_search(wl, hw, require_db=True)
    s = schedule_conv(wl, res.tiling, hw, dedup_loads=True)
    run_tsim(s.program, hw, check_hazards=True)
    dw = ConvWorkload("dw", 1, 28, 28, 3, 3, 128, 128, 1, 1, 1, 1,
                      depthwise=True)
    run_tsim(schedule_depthwise(dw, hw).program, hw, check_hazards=True)


def test_hazard_checker_flags_unsynchronized_clobber():
    """A compute that overwrites the acc region a concurrent (still
    draining) store reads, with no dependency token ordering them, must
    raise — this is exactly the reduction-step acc clobber the ctx-aware
    release tokens in runtime.finalize now close."""
    hw = DEFAULT_VTA
    from repro.vta.isa import Uop
    alloc = UopAllocator(hw)
    bgn, uld = alloc.place((Uop(0, 0, 0),))

    def alu(lp0):
        return AluInsn(op=Op.ALU, alu_op=AluOp.MUL, uop_bgn=bgn,
                       uop_end=bgn + 1, lp0=lp0, lp1=1, dst_f0=1,
                       use_imm=True, imm=0)
    t0 = Task()
    t0.computes.extend([uld, alu(64)])       # writes acc [0, 64)
    st = StoreInsn(op=Op.STORE, sram_base=0, y_size=1, x_size=64,
                   x_stride=64)
    st.meta = {"kind": "dw_out", "b0": 0, "c0": 0, "y0": 0, "th": 1,
               "x0": 0, "tw": 64}
    t0.stores.append(st)                     # reads acc [0, 64), slow DMA
    t1 = Task()
    t1.computes.append(alu(64))              # clobbers acc [0, 64)
    prog = finalize([t0, t1], hw, n_ctx=1)
    prog.uop_mem = alloc.mem
    assert t1.computes[0].pop_next           # the protecting release token
    # strip it to model the pre-fix fixed-distance protocol
    t1.computes[0].pop_next = False
    with pytest.raises(HazardError):
        run_tsim(prog, hw, check_hazards=True)
    # with the same-ctx store release in place the schedule is clean
    t1.computes[0].pop_next = True
    run_tsim(prog, hw, check_hazards=True)


def test_hazard_checker_ignores_identical_reload():
    """Re-fetching exactly the bytes that already back a region is not a
    value hazard (merged dedup units re-load identical weight chunks)."""
    hw = DEFAULT_VTA
    wl = ConvWorkload("c2", 1, 56, 56, 3, 3, 64, 64, 1, 1, 1, 1)
    res = tps_search(wl, PIPELINED_VTA, require_db=True)
    s = schedule_conv(wl, res.tiling, PIPELINED_VTA, dedup_loads=True)
    run_tsim(s.program, PIPELINED_VTA, check_hazards=True)   # must not raise


# ---------------------------------------------------------------------------
# vta/trace.py: digest recorder + first-divergence differ
# ---------------------------------------------------------------------------
def test_trace_records_and_localizes_divergence():
    hw = DEFAULT_VTA
    wl = ConvWorkload("c", 1, 8, 8, 3, 3, 16, 16, 1, 1, 1, 1)
    prog, dram = _conv_case(wl, hw)
    a = record_trace(prog, hw, {k: v.copy() for k, v in dram.items()})
    b = record_trace(prog, hw, {k: v.copy() for k, v in dram.items()})
    assert len(a) == len(prog.order)
    assert first_divergence(a, b) is None

    # corrupt one ALU immediate: the differ must name that instruction
    import copy
    bad = Program(hw=prog.hw, order=[copy.copy(i) for i in prog.order],
                  uop_mem=prog.uop_mem, n_ctx=prog.n_ctx)
    step = next(i for i, insn in enumerate(bad.order)
                if isinstance(insn, AluInsn) and insn.alu_op == AluOp.SHR)
    bad.order[step] = copy.copy(bad.order[step])
    bad.order[step].imm = 7
    c = record_trace(bad, hw, {k: v.copy() for k, v in dram.items()})
    div = first_divergence(a, c)
    assert div is not None and div.step == step
    assert div.insn == "AluInsn" and "acc" in div.buffers


def test_trace_diff_backends_agree():
    hw = DEFAULT_VTA
    wl = ConvWorkload("c", 1, 8, 8, 3, 3, 16, 16, 1, 1, 1, 1)
    prog, dram = _conv_case(wl, hw)
    diff = diff_backends(prog, hw, dram)
    assert diff.outputs_equal and diff.divergence is None
    assert diff.steps == len(prog.order)


# ---------------------------------------------------------------------------
# Stores through lowering: masked dw_out edges write only in-bounds lanes
# ---------------------------------------------------------------------------
def test_masked_edge_store_clamps():
    hw = DEFAULT_VTA
    # 14x14 pool s2 -> 7x7 output with shrink-tiled edges
    wl = ConvWorkload("p", 1, 14, 14, 3, 3, 16, 16, 1, 1, 2, 2)
    prog = schedule_pool(wl, hw, mode="max").program
    dram = {"inp": RNG.integers(-128, 127, (1, 16, 14, 14), dtype=np.int8),
            "out": np.full((1, 16, wl.oh, wl.ow), 77, np.int8)}
    out = _run_both(prog, hw, dram)
    from repro.vta.fsim import pool_ref
    ref = np.clip(pool_ref(dram["inp"], (3, 3), (2, 2), (1, 1), "max"),
                  -128, 127).astype(np.int8)
    np.testing.assert_array_equal(out["out"], ref)
