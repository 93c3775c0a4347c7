"""JIT-compiled JAX execution backend for the lowered tensor-op trace.

Executes exactly the trace ``vta/lowering.py`` produces — the same one the
numpy ``FSim`` consumes — under ``jax.jit``, ``vmap``-batched over N input
images, so one compiled program verifies a whole calibration batch. The
numpy backend runs a batch as N sequential per-image interpreter passes;
this backend runs it as one XLA computation whose gathers, GEMMs and ALU
sweeps are vectorized over the batch axis.

Compile-cost control: a trace is split into a *static spec* (hashable op
structure: kinds, tensor names, imms) and *dynamic arguments* (index maps,
masks, block starts, scratchpad bases — traced, never embedded constants;
put on the device once per (trace, device) and reused by every dispatch).
``jax.jit`` keys its cache on the spec plus array shapes, so autotune
candidates of the same layer — and repeat layers across a network — reuse
one compilation instead of paying XLA per program, and a persistent on-disk
XLA cache (``enable_persistent_cache``) carries executables across
processes.
``JaxBackend.chunk_compiles`` hands out a chunk's compile ahead of its
first dispatch, so a cold model compiles on a thread pool.

Compute ops resolve through the kernel registry (repro.kernels). The GEMM
kernel is picked by platform (``kernel_impls``): ``"einsum"`` (jnp.dot) on
the CPU, ``"pallas"`` (the TPS-blocked kernel in kernels/vta_gemm.py,
compiled) on a TPU; ``gemm_impl`` is a plain attribute a test may set to
``"pallas_interpret"`` (the same kernel interpreted on the CPU). The ALU
sweeps run the ``lax`` composites (kernels/alu_sweep.py) on every platform.

Fusion (bit-exact by the lowering-time legality proofs): every ALU-sweep run
lowering proves fusable (``Trace.alu_chains``) executes as ONE gather ->
reduce -> scatter instead of a per-op scatter sequence, and a
compiler-marked segment program (``Program.fused_segment``: one conv -> add
-> clip pipeline, resident spill chains) of up to ``SEGMENT_FUSION_MAX_OPS``
entries executes as a single kernel launch instead of a chunk sequence of
up to ``CHUNK_CAP`` entries each, keeping scratchpads out of HBM between
ops. ``kernel_launch_log()`` counts dispatches for tests/benchmarks.

Residency: a dispatch takes its tensors as host or device arrays and
returns its results on the device, unfetched, so a caller that chains
dispatches (``ServedModel.run_batch``) keeps a batch's activations there
and fetches once. A host batched tensor is put at every dispatch; a shared
one (a weight) once per (array, device) while the array lives; a device
array is copied on the device, because the chunk chain donates its state.

Profiler spans (``jax.profiler.TraceAnnotation``; half a microsecond when
no trace is running): ``vta.upload`` builds a dispatch's state on the
device (puts its host tensors, and on a trace's first dispatch to a device
its index maps), ``vta.launch`` dispatches its chunks. The caller fetches
(``ServedModel.run_batch``'s ``vta.fetch``). Device ops are named by VTA
instruction class (``ENTRY_SCOPES``).

Integer semantics match numpy bit for bit: int32 wraparound, arithmetic
right shift, scatter-add with duplicate indices.
"""
from __future__ import annotations

import collections
import functools
import math
import os
import threading
import warnings
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels.registry import get_kernel
from repro.vta.isa import AluOp, Buffer, VTAConfig
from repro.vta.lowering import (F32_EXACT_TERMS, AluSweep, GatherLoad,
                                GemmOp, ScatterStore, SpillStore, Trace,
                                UopLoad, dispatch_shapes, lower_cached,
                                scatter_hints)
from repro.vta.runtime import Program

_scatter_hints = scatter_hints       # lowering owns the static index proofs


def _matmul(x, w, gemm_impl: str):
    return get_kernel("gemm", gemm_impl)(x, w)


def _exact_matmul(xf, wf, gemm_impl: str):
    """``xf (..., M, K) @ wf (..., K, N)`` in int32 for int8-valued f32
    operands: one kernel call per exact-f32 block of the contraction
    (``F32_EXACT_TERMS``), the blocks summed in int32."""
    out = None
    for k0 in range(0, xf.shape[-1], F32_EXACT_TERMS):
        part = _matmul(xf[..., k0:k0 + F32_EXACT_TERMS],
                       wf[..., k0:k0 + F32_EXACT_TERMS, :], gemm_impl)
        part = part.astype(jnp.int32)
        out = part if out is None else out + part
    return out


def _gemm_product(x, w, g: int, R: int, w_d: int, gemm_impl: str):
    """One GEMM instruction's products, contracted per accumulator target.

    x (g*R, BV, BI) int8 — gathered input tiles, statically permuted so the
    g accumulator groups are contiguous per weight block; w (w_d*R, BO, BI)
    int8 — the instruction's w_d distinct weight blocks (the wgt sweep
    factors are zero, so the sweep grid shares them). Returns (g, BV, BO)
    int32, bit-exact: the int8 operands are widened to f32 and contracted
    as a batch of w_d real (gb*BV, R*BI) @ (R*BI, BO) matmuls
    (``_exact_matmul``).
    """
    BV, BI = x.shape[1], x.shape[2]
    BO = w.shape[1]
    K = R * BI
    gb = g // w_d
    xf = x.reshape(w_d, gb, R, BV, BI).transpose(0, 1, 3, 2, 4) \
        .reshape(w_d, gb * BV, K).astype(jnp.float32)
    wf = w.reshape(w_d, R, BO, BI).transpose(0, 1, 3, 2) \
        .reshape(w_d, K, BO).astype(jnp.float32)
    return _exact_matmul(xf, wf, gemm_impl).reshape(g, BV, BO)


def _window_product(inp, wgt, starts, geom: tuple, gemm_impl: str):
    """A windowed GEMM instruction's products (``GemmOp.window``), (G *
    lp0 * lp1, BV, BO) int32 in the order of its accumulator block.

    Each of the R input windows is read once, as a strided view of one
    slice of ``inp`` at the traced ``starts[1]``, and contracted against
    all G weight blocks, one slice of ``wgt`` at ``starts[2]``: one
    (lp0*lp1*BV, R*BI) @ (R*BI, G*BO) ``_exact_matmul``."""
    G, R, lp0, lp1, planes, pitch, H, W, sh, sw, shifts = geom
    BV, BI = inp.shape[1:]
    BO = wgt.shape[1]
    if planes > 1:
        view = jax.lax.dynamic_slice_in_dim(inp, starts[1], planes * pitch)
        view = view.reshape(planes, pitch, BV, BI)[:, :H * W]
    else:
        view = jax.lax.dynamic_slice_in_dim(inp, starts[1], H * W)
    # the shifts side by side on the minor axis: on a TPU the strided
    # views then need fewer relayouts than stacked on a major one
    view = view.reshape(planes, H, W, BV, BI).astype(jnp.float32)
    x = jnp.concatenate([view[:, dy:dy + sh * (lp0 - 1) + 1:sh,
                              dx:dx + sw * (lp1 - 1) + 1:sw]
                         for dy, dx in shifts], axis=-1)
    K = R * BI                      # x: (planes, lp0, lp1, BV, S * BI)
    xf = x.transpose(1, 2, 3, 0, 4).reshape(lp0 * lp1 * BV, K)
    wf = jax.lax.dynamic_slice_in_dim(wgt, starts[2], G * R) \
        .reshape(G, R, BO, BI).transpose(1, 3, 0, 2) \
        .reshape(K, G * BO).astype(jnp.float32)
    return _exact_matmul(xf, wf, gemm_impl) \
        .reshape(lp0 * lp1, BV, G, BO).transpose(2, 0, 1, 3) \
        .reshape(G * lp0 * lp1, BV, BO)


def kernel_impls(platform: str) -> dict:
    """The ``{"gemm": impl, "alu": impl}`` registry choice for a JAX
    platform name — the one place kernels are picked by platform.

    On a TPU the Pallas ``blocked_gemm`` runs compiled; elsewhere the XLA
    ``einsum``. The ALU sweeps run the ``lax`` composites everywhere: the
    TPU compiler refuses them as Pallas kernels ("Only 2D gather is
    supported"; docs/pallas.md)."""
    return {"gemm": "pallas" if platform == "tpu" else "einsum",
            "alu": "lax"}


_CACHE_READY = False
# fixed, inside the checkout: the cache directory is part of JAX's cache
# key, so a path that moved between runs would never hit
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_persistent_cache() -> None:
    """Point jax at a persistent XLA-compilation cache so trace-chunk
    executables survive process boundaries — DSE pool workers, repeated
    sweeps, serving restarts and CI runs skip straight to the steady state
    instead of paying XLA again for every structurally known chunk.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    the directory is left to JAX; otherwise the cache lives at the fixed
    ``<repo>/.jax_cache``."""
    global _CACHE_READY
    if _CACHE_READY:
        return
    _CACHE_READY = True
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    try:
        if path is None:
            path = DEFAULT_CACHE_DIR
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    except Exception as e:                           # pragma: no cover
        # the cache is an optimization, never a requirement — but a silent
        # failure here makes degraded cold-start perf undiagnosable, so
        # name the path and error once (_CACHE_READY gates re-entry)
        warnings.warn(
            f"persistent XLA compile cache disabled: setup failed for "
            f"{path!r} ({e!r}); every process will re-pay XLA compilation "
            f"on cold start", RuntimeWarning, stacklevel=2)


# ---------------------------------------------------------------------------
# Trace -> (static spec, dynamic index arrays)
# ---------------------------------------------------------------------------
def _tensor_names(trace: Trace) -> dict:
    """{DRAM tensor name: positional name} in the trace's order of first
    use. Specs and executor state key tensors by the positional names, so
    two segments that differ only in what their tensors are called — the
    repeated blocks of a network stage — are one program to compile."""
    names = trace.__dict__.get("_tensor_names")
    if names is None:
        order = dict.fromkeys(trace.tensors_read + trace.tensors_written)
        names = {t: f"t{i}" for i, t in enumerate(order)}
        trace.__dict__["_tensor_names"] = names
    return names


def _spec_of(trace: Trace, names: Optional[dict] = None):
    """Per-op (hashable entry, dynamic arrays) pairs.

    The entry captures only execution-relevant structure (no step numbers,
    tensors by positional name — ``names``, default ``_tensor_names``), so
    structurally identical ops — repeated tiles within a program, repeat
    layers across programs — hash equal and share XLA compilations. Bool
    masks and int32 index maps ride as traced arguments, never as embedded
    constants.

    Every fusable AluSweep run lowering marked (``Trace.alu_chains``)
    collapses to one ``"aluchain"`` (or DRAM-direct ``"alusweep"``) entry at
    its head op — the members it covers emit nothing.
    """
    names = names or _tensor_names(trace)
    heads = {c.members[0]: c for c in trace.alu_chains}
    members = {i for c in trace.alu_chains for i in c.members}
    pairs: list = []
    for i, op in enumerate(trace.ops):
        if op is None or isinstance(op, UopLoad):
            continue                      # uops are resolved at lowering
        if i in trace.elided:
            continue     # feeder gather / absorbed store of a direct sweep
        if i in members:
            c = heads.get(i)
            if c is None:
                continue              # executed by the head's chain kernel
            if c.store is not None or c.slabs:
                # DRAM-direct sweep: the feeder gathers replay inside the
                # kernel as local slabs, optional absorbed store
                sldesc = tuple((names[t.tensor], t.mask is not None, t.fill)
                               for t in c.slabs)
                a: list = [c.dst]
                for t in c.slabs:
                    a.append(t.index)
                    if t.mask is not None:
                        a.append(t.mask)
                kinds = []
                for src, arr in zip(c.arg_src, c.args):
                    if isinstance(src, str):
                        kinds.append("acc")
                        a.append(arr)
                    else:
                        kinds.append("local")
                        a.append(src[1])
                sdesc = None
                if c.store is not None:
                    st = c.store
                    aff = None
                    if st.affine is not None:
                        view_shape, perm, sizes, starts = st.affine
                        aff = (view_shape, perm, sizes)
                        a.append(np.asarray(starts, np.int32))
                    else:
                        a.append(st.index)
                        if st.mask is not None:
                            a.append(st.mask)
                    sdesc = (names[st.tensor], st.mask is not None,
                             st.unique, st.sorted, aff)
                e = ("alusweep", c.stages, sldesc, tuple(kinds), sdesc,
                     c.write_acc, c.unique, c.sorted)
                pairs.append((e, tuple(a)))
                continue
            e = ("aluchain", c.stages, len(c.args), c.unique, c.sorted)
            pairs.append((e, (c.dst,) + c.args))
            continue
        if isinstance(op, GatherLoad) and op.affine is not None:
            # a strided block of the padded tensor: one slice, its starts
            # traced so that every tile of a layer shares the entry
            view_shape, perm, sizes, starts, pads = op.affine
            e = ("gather_block", int(op.buffer), names[op.tensor],
                 view_shape, perm, sizes, pads, op.fill)
            a = (np.int32(op.base), np.asarray(starts, np.int32))
        elif isinstance(op, GatherLoad):
            e = ("gather", int(op.buffer), names[op.tensor],
                 op.mask is not None, op.fill)
            a = (np.int32(op.base), op.index) if op.mask is None \
                else (np.int32(op.base), op.index, op.mask)
        elif isinstance(op, GemmOp) and op.window is not None:
            # windows of the scratchpads: slices at traced starts, so that
            # every tile of a layer shares the entry
            geom, starts = op.window
            e = ("gemm_window", op.reset, geom)
            a = (np.asarray(starts, np.int32),)
        elif isinstance(op, GemmOp):
            if op.reset:
                e = ("gemm", True, 1, 0, *_scatter_hints(op.acc_idx))
                a = (op.acc_idx,)
            else:
                # Group iterations by accumulator target: consecutive runs
                # of R reduction uops (ci, dy, dx) hit the same acc entry,
                # so the contraction folds into real matmuls and the
                # scatter-add sees only unique indices — XLA's CPU scatter
                # serializes on duplicates, and this is what makes the JIT
                # path beat the interpreter on GEMM-heavy programs. The
                # wgt sweep factors are zero in every emitted schedule, so
                # the instruction has only w_d = tb_i*tco_i distinct weight
                # blocks; a static permutation makes same-weight groups
                # contiguous, one real matmul each (einsum fallback for
                # hypothetical schedules that break the pattern).
                R = _reduction_run(op.acc_idx)
                uidx = op.acc_idx[::R]
                g = len(uidx)
                rows = op.wgt_idx.reshape(g, R)
                grouped = _weight_blocks(rows)
                if grouped is not None:
                    wrows, perm = grouped
                    uidx = uidx[perm]
                    e = ("gemm", False, R, len(wrows),
                         *_scatter_hints(uidx))
                    a = (uidx.astype(np.int32),
                         op.inp_idx.reshape(g, R)[perm].reshape(-1),
                         wrows.reshape(-1).astype(np.int32))
                else:
                    e = ("gemm", False, R, 0, *_scatter_hints(uidx))
                    a = (uidx, op.inp_idx, op.wgt_idx)
        elif isinstance(op, AluSweep):
            fused = _fuse_sweep(op)
            if fused is not None:
                e, a = fused
            else:
                steps = tuple((s.src is not None, s.src2 >= 0,
                               *_scatter_hints(s.dst)) for s in op.steps)
                e = ("alu", int(op.alu_op), op.use_imm, op.imm, op.overwrite,
                     steps)
                a = tuple(x for s in op.steps for x in
                          ((np.int32(max(s.src2, 0)),)
                           + ((s.dst,) if s.src is None
                              else (s.dst, s.src))))
        elif isinstance(op, ScatterStore):
            hints = (False, False) if op.mask is not None \
                else _scatter_hints(op.index.reshape(-1))
            e = ("store", names[op.tensor], len(op.index),
                 op.mask is not None, *hints)
            a = (np.int32(op.base), op.index) if op.mask is None \
                else (np.int32(op.base), op.index, op.mask)
        elif isinstance(op, SpillStore):
            e = ("spill", *_scatter_hints(op.dst))
            a = (op.src, op.dst)
        else:
            raise TypeError(type(op))
        pairs.append((e, a))
    return pairs


def _fuse_sweep(op: AluSweep):
    """Fuse a multi-step ADD/MAX/MIN/MAC macro sweep whose steps all write
    the SAME destination grid from sources disjoint with it (the depthwise
    tap accumulation, the pool tap reduce) into one gather -> reduce ->
    scatter op. Sequential step semantics are preserved exactly: with a
    shared destination and non-overlapping sources, chaining T commutative
    updates equals one reduction. Returns (entry, args) or None.
    """
    if op.use_imm or op.overwrite or len(op.steps) < 2:
        return None
    if op.alu_op not in (AluOp.MAC, AluOp.ADD, AluOp.MAX, AluOp.MIN):
        return None
    s0 = op.steps[0]
    for s in op.steps:
        if s.src is None or not np.array_equal(s.dst, s0.dst):
            return None
    dset = set(s0.dst.tolist())
    for s in op.steps:
        if dset.intersection(s.src.tolist()):
            return None
        if op.alu_op == AluOp.MAC and s.src2 in dset:
            return None
    srcs = np.stack([s.src for s in op.steps])          # (T, g)
    src2 = np.array([max(s.src2, 0) for s in op.steps], np.int32)
    e = ("alufused", int(op.alu_op), len(op.steps), *_scatter_hints(s0.dst))
    return e, (s0.dst, srcs, src2)


def _weight_blocks(rows: np.ndarray):
    """(distinct weight-index blocks, group permutation) for a GEMM whose
    per-group weight rows repeat — periodically in every emitted schedule
    (period = tb_i*tco_i; checked cheaply), with an np.unique fallback for
    other repeat structures. None when grouping would not pay."""
    g = len(rows)
    same0 = (rows == rows[0]).all(axis=1)
    p = int(np.argmax(same0[1:])) + 1 if same0[1:].any() else g
    if p <= 16 and g % p == 0 and \
            bool((rows.reshape(g // p, p, -1) == rows[:p]).all()):
        perm = np.arange(g).reshape(g // p, p).T.reshape(-1)
        return rows[:p], perm
    wrows, inv = np.unique(rows, axis=0, return_inverse=True)
    counts = np.bincount(inv)
    if len(wrows) <= 16 and bool((counts == counts[0]).all()):
        return wrows, np.argsort(inv, kind="stable")
    return None


def _reduction_run(acc_idx: np.ndarray) -> int:
    """Largest R with ``acc_idx.reshape(-1, R)`` constant per row (the
    reduction-uop run length of a GEMM's index vector)."""
    n = len(acc_idx)
    changes = np.flatnonzero(np.diff(acc_idx))
    R = int(changes[0]) + 1 if len(changes) else n
    if R <= 1 or n % R:
        return 1
    rows = acc_idx.reshape(-1, R)
    return R if bool((rows == rows[:, :1]).all()) else 1


# Whole-segment fusion emits the entire trace as ONE jit chunk. XLA compile
# time grows superlinearly in entry count, so very long segment programs
# (large real-net tilings) fall back to the capped chunk sequence; the bound
# comfortably covers the fused conv->add->clip and resident-spill segments
# the graph compiler actually builds at test/serve scales.
SEGMENT_FUSION_MAX_OPS = 256
# Entries per chunk of a program that is not run as one fused segment.
CHUNK_CAP = 24


def _spec_chunks(trace: Trace) -> list:
    """Chunked (spec, args) blocks for a trace, memoized on the Trace.

    Serving replays one lowered trace per dispatch; spec construction is
    pure numpy bookkeeping but shows up at high request rates, so cache the
    chunk list alongside the trace.

    A compiler-marked fused segment of up to ``SEGMENT_FUSION_MAX_OPS``
    entries is one chunk (one kernel launch); any other trace splits into
    chunks of up to ``CHUNK_CAP`` entries (``_chunks``).
    """
    hit = trace.__dict__.get("_spec_chunks")
    if hit is None:
        pairs = _spec_of(trace)
        if trace.fused_segment and len(pairs) <= SEGMENT_FUSION_MAX_OPS:
            spec = tuple(e for e, _ in pairs)
            args = tuple(x for _, a in pairs for x in a)
            hit = [(spec, args)] if pairs else []
        else:
            hit = list(_chunks(pairs))
        trace.__dict__["_spec_chunks"] = hit
    return hit


def _resident_chunks(trace: Trace, chunks: list, device) -> tuple:
    """``chunks`` with their arguments on ``device``, and the bytes this
    call put there: all of them on the first call for the device, None
    after.

    The index maps never change once lowered, so they cross the host link
    once per (trace, device) instead of at every dispatch, where jit would
    copy each of a chunk's arrays anew. The memo lives on the Trace, beside
    the chunk list, and dies with the Program. The copies are placed as
    uncommitted arrays, as the state is, so a dispatch keeps the signature
    ``chunk_compiles`` compiled for. Threads that share a Program and miss
    together each put a copy; the first one stored is kept.
    """
    memo = trace.__dict__.setdefault("_resident_chunks", {})
    key = str(device)
    hit = memo.get(key)
    if hit is not None:
        return hit, None
    with jax.default_device(device):
        args = jax.device_put([cargs for _, cargs in chunks])
    put = [(cspec, tuple(a)) for (cspec, _), a in zip(chunks, args)]
    return memo.setdefault(key, put), sum(
        np.asarray(a).nbytes for _, cargs in chunks for a in cargs)


def _resident_host(memo: dict, v, device) -> tuple:
    """(a flat copy of the host array ``v`` on ``device``, the bytes this
    call put there: all of them on the first call for the pair, 0 after).

    ``memo`` maps (id(array), str(device)) to (a weak reference to the
    array, its device copy): keyed on the array's identity and guarded by
    the reference, so a new array (``model.weights`` reassigned) is put
    anew and an entry dies with its array. The copy is uncommitted, as the
    index maps are, and is never donated: ``_execute`` copies it into each
    dispatch's state. Threads that miss together each put a copy."""
    v = np.asarray(v)
    key = (id(v), str(device))
    hit = memo.get(key)
    if hit is not None and hit[0]() is v:
        return hit[1], 0
    with jax.default_device(device):
        put = jax.device_put(np.reshape(v, -1))

    def drop(ref, key=key):
        if memo.get(key, (None,))[0] is ref:
            memo.pop(key, None)
    memo[key] = (weakref.ref(v, drop), put)
    return put, v.nbytes


def _indexed_bytes(trace: Trace, chunks: list, key: tuple, hw: VTAConfig,
                   batched: dict, shared: dict) -> dict:
    """{VTA instruction class: bytes} that one dispatch of ``chunks`` on
    ``batched`` (leading axis N) and ``shared`` reads or writes through
    index arrays, memoized on the Trace under ``key``.

    Every gather, scatter and ``.at[idx]`` update of ``_exec_entry`` (and
    of the ``lax`` ALU kernels it calls) counts the elements it
    addresses once, each at its dtype's width: a gather its reads, an
    update its writes. Moves proved affine (a scalar index, which JAX turns
    into a dynamic slice; ``dynamic_slice``, ``dynamic_update_slice``,
    ``gather_block``, ``store_affine``) count nothing. Under ``vmap`` a
    gather from a shared tensor runs once per dispatch, every other access
    once per image. The index arrays are walked once per key, never per
    dispatch."""
    memo = trace.__dict__.setdefault("_indexed_bytes", {})
    hit = memo.get(key)
    if hit is not None:
        return hit
    names = _tensor_names(trace)
    tensors = {names[k]: (np.dtype(v.dtype).itemsize, k not in shared)
               for d in (batched, shared) for k, v in d.items() if k in names}
    n = next(iter(batched.values())).shape[0]
    _, BV, BI, _, BO, _ = _geom_of(hw)
    row = {"inp": BV * BI, "wgt": BO * BI, "acc": 4 * BV * BO}
    per_image: collections.Counter = collections.Counter()
    per_dispatch: collections.Counter = collections.Counter()

    for cspec, cargs in chunks:
        it = iter(cargs)
        for e in cspec:
            kind = e[0]
            cls = ENTRY_SCOPES[kind].split(".")[1]

            def count(idx, nbytes, each_image=True, cls=cls):
                if np.ndim(idx):            # a scalar is a dynamic slice
                    into = per_image if each_image else per_dispatch
                    into[cls] += np.size(idx) * nbytes

            if kind == "gather_block":                      # a slice
                next(it)
                next(it)
            elif kind == "gemm_window":                     # slices
                next(it)
            elif kind in ("gather", "store"):
                tensor, has_mask = e[2 if kind == "gather" else 1], e[3]
                next(it)                                    # base
                count(next(it), *tensors[tensor])
                if has_mask:
                    next(it)
            elif kind == "gemm":
                acc_idx = next(it)
                if not e[1]:                                # not a reset
                    count(next(it), row["inp"])
                    count(next(it), row["wgt"])
                count(acc_idx, row["acc"])
            elif kind == "alu":
                overwrite, steps = e[4], e[5]
                for has_src, *_ in steps:
                    next(it)                                # src2: scalar
                    dst = next(it)
                    if has_src:
                        count(next(it), row["acc"])
                    count(dst, row["acc"] * (1 if overwrite else 2))
            elif kind == "aluchain":
                stages, n_args = e[1], e[2]
                dst = next(it)
                for _ in range(n_args):
                    count(next(it), row["acc"])
                reads = sum(st[0] == "read_dst" for st in stages)
                count(dst, row["acc"] * (1 + reads))
            elif kind == "alusweep":
                _, stages, sldesc, kinds, sdesc, write_acc = e[:6]
                dst = next(it)
                # the slabs concatenate into one local buffer, widened to
                # int32 where their dtypes differ
                local_elems, local_each, widths = 0, False, set()
                for tname, has_mask, _ in sldesc:
                    idx = next(it)
                    width, each_image = tensors[tname]
                    count(idx, width, each_image)
                    local_elems = math.prod(np.shape(idx)[1:])
                    local_each |= each_image
                    widths.add(width)
                    if has_mask:
                        next(it)
                local_row = local_elems * (widths.pop() if len(widths) == 1
                                           else 4)
                for k in kinds:
                    if k == "acc":
                        count(next(it), row["acc"])
                    else:
                        count(next(it), local_row, local_each)
                reads = sum(st[0] == "read_dst" for st in stages)
                count(dst, row["acc"] * (reads + bool(write_acc)))
                if sdesc is not None:
                    tname, s_has_mask, _, _, s_aff = sdesc
                    sidx = next(it)
                    if s_aff is None:
                        count(sidx, tensors[tname][0])
                        if s_has_mask:
                            next(it)
            elif kind == "alufused":
                alu_op = e[1]
                dst, srcs, src2 = next(it), next(it), next(it)
                count(srcs, row["acc"])
                if alu_op == int(AluOp.MAC):
                    count(src2, row["acc"])
                count(dst, 2 * row["acc"])
            elif kind == "spill":
                count(next(it), row["acc"])
                count(next(it), row["inp"])
            else:
                raise TypeError(kind)
    return memo.setdefault(key, {cls: n * per_image[cls] + per_dispatch[cls]
                                 for cls in INDEXED_CLASSES})


def _chunks(pairs: list):
    """Split the op stream into jit-able blocks of up to ``CHUNK_CAP`` ops.

    Because entries carry neither step numbers nor scratchpad bases (those
    ride as traced arguments), the repeated tile blocks that dominate real
    programs produce *identical* (spec, shapes) keys, so a whole program
    compiles only its handful of distinct block structures — this is what
    keeps XLA compile time flat in program length.
    """
    block: list = []
    bargs: list = []
    for e, a in pairs:
        block.append(e)
        bargs.extend(a)
        # close on task boundaries (stores) once half-full — big tasks stay
        # aligned for cache reuse, small ALU tasks coalesce up to the cap
        if len(block) >= CHUNK_CAP or (e[0] == "store"
                                       and len(block) >= CHUNK_CAP // 2):
            yield tuple(block), tuple(bargs)
            block, bargs = [], []
    if block:
        yield tuple(block), tuple(bargs)


def _geom_of(hw: VTAConfig) -> tuple:
    return (hw.inp_depth, hw.batch, hw.block_in, hw.wgt_depth, hw.block_out,
            hw.acc_depth)


_BUF_KEY = {int(Buffer.INP): "inp", int(Buffer.WGT): "wgt",
            int(Buffer.ACC): "acc"}
_BUF_DTYPE = {int(Buffer.INP): jnp.int8, int(Buffer.WGT): jnp.int8,
              int(Buffer.ACC): jnp.int32}

# The VTA instruction class of each spec entry kind. Every op an entry emits
# runs under ``jax.named_scope`` of its class, so the HLO's ``op_name`` (and a
# profiler trace of the device) names the class. The names are the same in
# every chunk: they change no program, only its metadata.
ENTRY_SCOPES = {"gather": "vta.load", "gather_block": "vta.load",
                "gemm": "vta.gemm", "gemm_window": "vta.gemm",
                "alu": "vta.alu", "aluchain": "vta.alu",
                "alusweep": "vta.alu", "alufused": "vta.alu",
                "store": "vta.store", "spill": "vta.store"}


def _exec_entries(spec: tuple, args: tuple, state: dict,
                  gemm_impl: str) -> None:
    """Apply spec entries to ``state`` (scratchpads + tensors), consuming
    ``args`` positionally. Runs traced (inside the chunk jit, vmapped over
    the batch) and eagerly (the stepped divergence-debug path)."""
    ai = 0

    def nxt():
        nonlocal ai
        a = args[ai]
        ai += 1
        return a

    for e in spec:
        with jax.named_scope(ENTRY_SCOPES[e[0]]):
            _exec_entry(e, nxt, state, gemm_impl)
    assert ai == len(args), (ai, len(args))


def _exec_entry(e: tuple, nxt, state: dict, gemm_impl: str) -> None:
    """Apply one spec entry to ``state``, taking its arguments in order
    from ``nxt()``."""
    kind = e[0]
    if kind == "gather":
        _, buf, tensor, has_mask, fill = e
        base = nxt()
        idx = nxt()
        flat = state["tensors"][tensor].reshape(-1)
        src = flat[idx]
        if has_mask:
            src = jnp.where(nxt(), src, jnp.asarray(fill, src.dtype))
        key = _BUF_KEY[buf]
        state[key] = jax.lax.dynamic_update_slice_in_dim(
            state[key], src.astype(_BUF_DTYPE[buf]), base, axis=0)
    elif kind == "gather_block":
        _, buf, tensor, view_shape, perm, sizes, pads, fill = e
        base = nxt()
        starts = nxt()
        src = state["tensors"][tensor].reshape(view_shape)
        if any(lo or hi for lo, hi in pads):
            src = jax.lax.pad(src, jnp.asarray(fill, src.dtype),
                              [(lo, hi, 0) for lo, hi in pads])
        block = jax.lax.dynamic_slice(
            src, [starts[i] for i in range(len(sizes))], sizes)
        # the sliced axes that move, in view order, back to grid order
        moving = [s for s in sizes if s > 1]
        block = block.reshape(moving).transpose(
            tuple(int(a) for a in np.argsort(perm[:len(moving)])))
        key = _BUF_KEY[buf]
        state[key] = jax.lax.dynamic_update_slice_in_dim(
            state[key], block.reshape(-1, *state[key].shape[1:])
            .astype(_BUF_DTYPE[buf]), base, axis=0)
    elif kind == "gemm_window":
        _, reset, geom = e
        starts = nxt()
        acc = state["acc"]
        if reset:
            block = jnp.zeros((geom[0],) + acc.shape[1:], acc.dtype)
        else:
            prod = _window_product(state["inp"], state["wgt"], starts, geom,
                                   gemm_impl)
            block = jax.lax.dynamic_slice_in_dim(
                acc, starts[0], prod.shape[0]) + prod
        state["acc"] = jax.lax.dynamic_update_slice_in_dim(
            acc, block, starts[0], axis=0)
    elif kind == "gemm":
        _, reset, R, w_d, uniq, srt = e
        acc_idx = nxt()
        if reset:
            state["acc"] = state["acc"].at[acc_idx].set(
                0, unique_indices=uniq, indices_are_sorted=srt)
        else:
            x = state["inp"][nxt()]
            w = state["wgt"][nxt()]
            g = x.shape[0] // R
            if w_d:
                prod = _gemm_product(x, w, g, R, w_d, gemm_impl)
            else:       # per-group weights (no emitted schedule today)
                prod = jnp.einsum(
                    "grbi,groi->gbo",
                    x.reshape(g, R, *x.shape[1:]).astype(jnp.int32),
                    w.reshape(g, R, *w.shape[1:]).astype(jnp.int32))
            state["acc"] = state["acc"].at[acc_idx].add(
                prod, unique_indices=uniq, indices_are_sorted=srt)
    elif kind == "alu":
        _, alu_op, use_imm, imm, overwrite, steps = e
        acc = state["acc"]
        for has_src, _has_src2, uniq, srt in steps:
            src2 = nxt()
            dst_i = nxt()

            def put(val):
                return acc.at[dst_i].set(val, unique_indices=uniq,
                                         indices_are_sorted=srt)
            if alu_op == int(AluOp.MAC):
                prod = acc[nxt()] * acc[src2][None]
                acc = put(prod if overwrite else acc[dst_i] + prod)
                continue
            src = jnp.int32(imm) if use_imm else acc[nxt()]
            if overwrite:
                acc = put(jnp.broadcast_to(src, acc[dst_i].shape))
                continue
            dst = acc[dst_i]
            if alu_op == int(AluOp.ADD):
                r = dst + src
            elif alu_op == int(AluOp.MAX):
                r = jnp.maximum(dst, src)
            elif alu_op == int(AluOp.MIN):
                r = jnp.minimum(dst, src)
            elif alu_op == int(AluOp.SHR):
                r = jnp.right_shift(dst, src)
            elif alu_op == int(AluOp.MUL):
                r = dst * src
            elif alu_op == int(AluOp.CLIP):
                bound = abs(int(imm))
                r = jnp.clip(dst, -bound, bound)
            else:
                raise ValueError(alu_op)
            acc = put(r)
        state["acc"] = acc
    elif kind == "aluchain":
        _, stages, n_args, uniq, srt = e
        dst = nxt()
        cargs = [nxt() for _ in range(n_args)]
        state["acc"] = get_kernel("alu_chain", "lax")(
            state["acc"], dst, stages, cargs,
            unique=uniq, sorted_=srt)
    elif kind == "alusweep":
        _, stages, sldesc, kinds, sdesc, write_acc, uniq, srt = e
        dst = nxt()
        slabs = []
        for tname, has_mask, fill in sldesc:
            flat = state["tensors"][tname].reshape(-1)
            idx = nxt()
            mask = nxt() if has_mask else None
            slabs.append((flat, idx, mask, fill))
        oa = [(k, nxt()) for k in kinds]
        of = sidx = smask = s_aff = None
        s_uniq = s_srt = False
        if sdesc is not None:
            stname, s_has_mask, s_uniq, s_srt, s_aff = sdesc
            of = state["tensors"][stname].reshape(-1)
            sidx = nxt()                 # block starts when affine
            smask = nxt() if s_has_mask and s_aff is None else None
        acc2, out2 = get_kernel("alu_sweep", "lax")(
            state["acc"], dst, stages, oa, slabs=slabs,
            write_acc=write_acc,
            unique=uniq, sorted_=srt, out_flat=of, store_idx=sidx,
            store_mask=smask, store_unique=s_uniq, store_sorted=s_srt,
            store_affine=s_aff)
        if write_acc:
            state["acc"] = acc2
        if sdesc is not None:
            arr = state["tensors"][sdesc[0]]
            state["tensors"][sdesc[0]] = out2.reshape(arr.shape)
    elif kind == "alufused":
        _, alu_op, T, uniq, srt = e
        dst = nxt()
        srcs = nxt()
        src2 = nxt()
        acc = state["acc"]
        src = acc[srcs]                      # (T, g, BV, BO)
        if alu_op == int(AluOp.MAC):
            r = acc[dst] + (src * acc[src2][:, None]).sum(0)
        elif alu_op == int(AluOp.ADD):
            r = acc[dst] + src.sum(0)
        elif alu_op == int(AluOp.MAX):
            r = jnp.maximum(acc[dst], src.max(0))
        else:
            r = jnp.minimum(acc[dst], src.min(0))
        state["acc"] = acc.at[dst].set(r, unique_indices=uniq,
                                       indices_are_sorted=srt)
    elif kind == "store":
        _, tensor, n, has_mask, uniq, srt = e
        base = nxt()
        idx = nxt()
        vals = jnp.clip(jax.lax.dynamic_slice_in_dim(
            state["acc"], base, n, axis=0), -128, 127).astype(jnp.int8)
        arr = state["tensors"][tensor]
        flat = arr.reshape(-1)
        if has_mask:
            idx = jnp.where(nxt(), idx, flat.shape[0])   # OOB -> drop
        state["tensors"][tensor] = flat.at[idx].set(
            vals, mode="drop", unique_indices=uniq,
            indices_are_sorted=srt).reshape(arr.shape)
    elif kind == "spill":
        _, uniq, srt = e
        src = nxt()
        dst = nxt()
        vals = jnp.clip(state["acc"][src], -128, 127).astype(jnp.int8)
        state["inp"] = state["inp"].at[dst].set(
            vals, unique_indices=uniq, indices_are_sorted=srt)


# ---------------------------------------------------------------------------
# XLA trace accounting. The Python body of ``_exec_chunk`` executes only when
# ``jax.jit`` misses its cache — i.e. exactly once per XLA trace/compile — so
# a plain counter keyed on the true cache identity (chunk spec, traced arg
# shapes, batch size) is an exact compile-reuse regression hook: serving any
# number of batches at a bucket size must leave every key at 1
# (tests/test_serve.py). Wall-clock-free, persistent-cache-independent.
# ---------------------------------------------------------------------------
_XLA_TRACES: collections.Counter = collections.Counter()
_LOG_LOCK = threading.Lock()      # chunks trace on many threads at once

# Trace *scope*: a thread-local label stamped into every trace signature so
# multi-worker serving (serve/workers.py) can attribute each compile to the
# worker that paid it. Each pool worker brackets its dispatches with
# ``set_xla_trace_scope(f"worker{id}")`` — jit tracing runs synchronously on
# the dispatching thread, so the label is exact. With sticky (model, bucket)
# -> worker affinity, every trace-log key must carry the scope of the key's
# *owning* worker and appear exactly once per owner (tests/test_workers.py);
# a key traced under two scopes means placement broke affinity.
_TRACE_TLS = threading.local()


def set_xla_trace_scope(label: Optional[str]) -> Optional[str]:
    """Set this thread's trace-scope label; returns the previous label so
    callers can restore it (``None`` = unscoped, the default)."""
    prev = getattr(_TRACE_TLS, "scope", None)
    _TRACE_TLS.scope = label
    return prev


def xla_trace_scope() -> Optional[str]:
    return getattr(_TRACE_TLS, "scope", None)


def _note_trace(spec, args, state) -> None:
    n = state["acc"].shape[0]
    sig = (hash(spec), tuple(np.shape(a) for a in args), int(n),
           xla_trace_scope())
    with _LOG_LOCK:
        _XLA_TRACES[sig] += 1


def reset_xla_trace_log() -> None:
    _XLA_TRACES.clear()


def xla_trace_log() -> dict:
    """{(chunk-spec hash, arg shapes, batch, scope): traces} since the last
    ``reset_xla_trace_log``. Any value above 1 means a structurally known
    chunk was re-traced — a compile-cache regression. ``scope`` is the
    dispatching thread's trace-scope label (the owning worker id under the
    serving pool, ``None`` everywhere else)."""
    return dict(_XLA_TRACES)


# Kernel-launch accounting: every ``_exec_chunk`` dispatch is one launch
# (one jit'd XLA computation hitting the device queue). Unlike _XLA_TRACES
# this counts *dispatches*, not compiles — the hook the segment-fusion tests
# use to assert a fused conv->add->clip segment really is ONE launch. Each
# dispatch is also attributed to the device its state lives on, and the
# host->device bytes it uploads are summed by kind: the batched activation
# tensors, the shared weights and biases, and the chunks' index maps.
_LAUNCHES: collections.Counter = collections.Counter()   # device -> launches
UPLOAD_KINDS = ("activations", "weights", "index_maps")
_UPLOAD_BYTES: collections.Counter = collections.Counter()   # kind -> bytes
_RESIDENCY: collections.Counter = collections.Counter()   # hit/put -> launches
_RESIDENT_BYTES: collections.Counter = collections.Counter()  # device -> bytes
_TENSORS: collections.Counter = collections.Counter()   # hit/put -> inputs
INDEXED_CLASSES = ("load", "gemm", "alu", "store")
_INDEXED_BYTES: collections.Counter = collections.Counter()   # class -> bytes
LOAD_FORMS = {"gather_block": "slice", "gather": "gather"}
GEMM_FORMS = {"gemm_window": "window", "gemm": "gather"}
_FORMS: collections.Counter = collections.Counter()   # entry kind -> entries
_GEMM_MACS: collections.Counter = collections.Counter()    # "macs" -> MACs


def reset_kernel_launch_log() -> None:
    with _LOG_LOCK:
        _LAUNCHES.clear()
        _UPLOAD_BYTES.clear()
        _RESIDENCY.clear()
        _RESIDENT_BYTES.clear()
        _TENSORS.clear()
        _INDEXED_BYTES.clear()
        _FORMS.clear()
        _GEMM_MACS.clear()


def kernel_launch_log() -> int:
    """Chunk dispatches since the last ``reset_kernel_launch_log``."""
    return sum(_LAUNCHES.values())


def kernel_launches_by_device() -> dict:
    """{str(device): chunk dispatches} since the last reset."""
    return dict(_LAUNCHES)


def upload_bytes_log() -> int:
    """Host->device bytes the dispatches uploaded since the last reset."""
    return sum(_UPLOAD_BYTES.values())


def upload_bytes_by_kind() -> dict:
    """``upload_bytes_log`` split into ``UPLOAD_KINDS``: ``activations``
    (the batched DRAM tensors), ``weights`` (the shared ones) and
    ``index_maps`` (the chunks' index, mask and base arguments)."""
    return {k: _UPLOAD_BYTES[k] for k in UPLOAD_KINDS}


def index_map_residency_log() -> dict:
    """How the dispatches since the last reset found their index maps:
    ``resident_dispatches`` launched chunks whose arguments were already on
    the device, ``uploaded_dispatches`` chunks whose arguments their own
    ``_execute`` put there first (the two sum to ``kernel_launch_log``),
    and ``resident_bytes`` {str(device): bytes put}, the ``index_maps``
    part of ``upload_bytes_by_kind`` by device."""
    with _LOG_LOCK:
        return {"resident_dispatches": _RESIDENCY["resident"],
                "uploaded_dispatches": _RESIDENCY["uploaded"],
                "resident_bytes": dict(_RESIDENT_BYTES)}


def tensor_residency_log() -> dict:
    """How the dispatches since the last reset found their batched and
    shared input tensors: ``resident`` already on the device (a device
    array, or a weight put by an earlier dispatch), ``uploaded`` put from
    the host by their own ``_execute`` (the ``activations`` and ``weights``
    parts of ``upload_bytes_by_kind``). A served batch in steady state
    puts one, its images."""
    with _LOG_LOCK:
        return {"resident": _TENSORS["resident"],
                "uploaded": _TENSORS["uploaded"]}


def indexed_bytes_by_class() -> dict:
    """{VTA instruction class of ``INDEXED_CLASSES``: bytes} the dispatches
    since the last reset read or wrote through index arrays (gathers,
    scatters, ``.at[idx]`` updates; see ``_indexed_bytes``), summed over
    the images of each batch."""
    with _LOG_LOCK:
        return {k: _INDEXED_BYTES[k] for k in INDEXED_CLASSES}


def load_form_log() -> dict:
    """{"slice": n, "gather": n}: the load entries the dispatches since
    the last reset ran as a strided slice of the padded tensor (a load
    lowering proved affine, ``GatherLoad.affine``) or as an element gather,
    each counted once per dispatch."""
    with _LOG_LOCK:
        return {form: _FORMS[kind] for kind, form in LOAD_FORMS.items()}


def gemm_form_log() -> dict:
    """{"window": n, "gather": n}: the GEMM entries the dispatches since
    the last reset ran on windows of the scratchpads (lowering proved the
    instruction windowed, ``GemmOp.window``) or on gathered rows, each
    counted once per dispatch."""
    with _LOG_LOCK:
        return {form: _FORMS[kind] for kind, form in GEMM_FORMS.items()}


def gemm_mac_log() -> int:
    """Multiply-accumulates the GEMM entries of the dispatches since the
    last reset executed, over every image of each batch: per GEMM
    instruction its uops times its loop extents times BV * BI * BO. Where
    the program pads channels to the VTA block (``ServedModel``), the pad
    channels' MACs are counted too."""
    with _LOG_LOCK:
        return _GEMM_MACS["macs"]


def _gemm_macs(trace: Trace) -> int:
    """MACs of one image's pass through ``trace``'s GEMM instructions,
    memoized on the Trace."""
    hit = trace.__dict__.get("_gemm_macs")
    if hit is None:
        _, BV, BI, _, BO, _ = _geom_of(trace.hw)
        hit = trace.__dict__["_gemm_macs"] = BV * BI * BO * sum(
            len(op.acc_idx) for op in trace.ops
            if isinstance(op, GemmOp) and not op.reset)
    return hit


def _entry_forms(trace: Trace, chunks: list) -> dict:
    """{entry kind: entries} of one dispatch of ``chunks``, for the kinds
    of ``LOAD_FORMS`` and ``GEMM_FORMS``, memoized on the Trace."""
    hit = trace.__dict__.get("_entry_forms")
    if hit is None:
        kinds = collections.Counter(e[0] for cspec, _ in chunks
                                    for e in cspec)
        hit = trace.__dict__["_entry_forms"] = {
            kind: kinds[kind] for kind in (*LOAD_FORMS, *GEMM_FORMS)}
    return hit


@functools.partial(jax.jit, static_argnums=(0,))
def _fresh(scratch: tuple, tensors: dict) -> tuple:
    """New device buffers for a dispatch's state, in one launch: ({name:
    zeros} for each (name, shape, dtype) of ``scratch``, {name: a copy of
    each of ``tensors``}); the call puts a host array on the device. The
    chunk chain donates its state, so nothing a caller holds (a zero-copy
    view of host memory, a resident weight, an activation a later dispatch
    reads again) may enter it itself."""
    return ({name: jnp.zeros(shape, dtype) for name, shape, dtype in scratch},
            {k: jnp.copy(v) for k, v in tensors.items()})


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3,))
def _exec_chunk(spec, gemm_impl, args, state):
    """One jit-compiled block, vmapped over the leading batch axis of the
    scratchpads and per-image tensors. ``state["shared"]`` (weights/biases)
    rides through with ``in_axes=None`` — vmap keeps gathers from unmapped
    tensors unbatched, so weight loads run once per batch instead of once
    per image. The shared/batched split is part of the jit cache key via
    the state pytree structure. Donating ``state`` lets XLA update the
    scratchpads and DRAM tensors in place across the chunk chain.

    JAX's persistent-cache key holds this function's name but no op
    metadata: rename it whenever ``ENTRY_SCOPES`` changes, or a cache
    written before serves executables whose ops carry the old names."""
    _note_trace(spec, args, state)
    axes = {"inp": 0, "wgt": 0, "acc": 0, "tensors": 0, "shared": None}

    def body(st):
        inner = {"inp": st["inp"], "wgt": st["wgt"], "acc": st["acc"],
                 "tensors": {**st["tensors"], **st["shared"]}}
        _exec_entries(spec, args, inner, gemm_impl)
        return {"inp": inner["inp"], "wgt": inner["wgt"],
                "acc": inner["acc"], "shared": st["shared"],
                "tensors": {k: inner["tensors"][k] for k in st["tensors"]}}

    return jax.vmap(body, in_axes=(axes,), out_axes=axes)(state)



# ---------------------------------------------------------------------------
# The backend object
# ---------------------------------------------------------------------------
class JaxBackend:
    """``jax.jit``-compiled, ``vmap``-batched executor of the lowered trace.

    ``gemm_impl`` is ``kernel_impls`` of the platform JAX runs on (einsum on
    the CPU, the Pallas GEMM on a TPU).
    """

    name = "jax"

    def __init__(self):
        self.gemm_impl = kernel_impls(jax.default_backend())["gemm"]
        # the shared host arrays this backend keeps on devices
        # (``_resident_host``)
        self.resident_host: dict = {}
        enable_persistent_cache()

    # -- core loop ---------------------------------------------------------
    def _execute(self, trace: Trace, hw: VTAConfig, batched: dict,
                 shared: dict = None) -> dict:
        """``batched``: DRAM tensors with a leading batch axis N; ``shared``:
        single arrays every image reads (never stores into). Either may be
        host or device arrays. Returns, as device arrays in the flat layout
        ``(N, size)``, every tensor the trace stores and every batched one
        this call put from the host. The state is built, and host tensors
        and the chunks' arguments made resident, in a ``vta.upload``
        profiler span; the chunks are launched in a ``vta.launch`` span (its
        ``chunks`` the number launched)."""
        shared = shared or {}
        assert not (set(trace.tensors_written) & set(shared)), \
            "programs must not store into shared tensors"
        n = next(iter(batched.values())).shape[0]
        inp_depth, BV, BI, wgt_depth, BO, acc_depth = _geom_of(hw)
        names = _tensor_names(trace)
        chunks = _spec_chunks(trace)
        # the tensors' dtypes are the program's: which are shared, and N,
        # are all a dispatch can change
        indexed = _indexed_bytes(trace, chunks, (tuple(shared), n), hw,
                                 batched, shared)
        forms = _entry_forms(trace, chunks)
        macs = n * _gemm_macs(trace)
        up = dict.fromkeys(UPLOAD_KINDS, 0)
        found = collections.Counter()
        put_host = []
        with TraceAnnotation("vta.upload"):
            # DRAM tensors ride flat, as the trace's index maps address
            # them: an NCHW weight's 3x3 minor dims would pad to a full TPU
            # tile, and the relayout to flat cost minutes of compile per
            # weight gather
            tensors = {}
            for k, v in batched.items():
                if k not in names:
                    continue
                if isinstance(v, jax.Array):
                    tensors[names[k]] = jnp.reshape(v, (n, -1))
                    found["resident"] += 1
                else:
                    tensors[names[k]] = np.reshape(v, (n, -1))
                    up["activations"] += tensors[names[k]].nbytes
                    found["uploaded"] += 1
                    put_host.append(k)
            state, tensors = _fresh(
                (("inp", (n, inp_depth, BV, BI), jnp.int8),
                 ("wgt", (n, wgt_depth, BO, BI), jnp.int8),
                 ("acc", (n, acc_depth, BV, BO), jnp.int32)), tensors)
            device = next(iter(state["acc"].devices()))
            weights = {}
            for k, v in shared.items():
                if k not in names:
                    continue
                if isinstance(v, jax.Array):
                    weights[names[k]], put = jnp.reshape(v, -1), 0
                else:
                    weights[names[k]], put = _resident_host(
                        self.resident_host, v, device)
                    up["weights"] += put
                found["uploaded" if put else "resident"] += 1
            state["tensors"] = tensors
            _, state["shared"] = _fresh((), weights)
            chunks, maps = _resident_chunks(trace, chunks, device)
        up["index_maps"] = maps or 0
        with _LOG_LOCK:
            _INDEXED_BYTES.update(indexed)
            _FORMS.update(forms)
            _GEMM_MACS["macs"] += macs
            _LAUNCHES[str(device)] += len(chunks)
            _UPLOAD_BYTES.update(up)
            _TENSORS.update(found)
            _RESIDENCY["resident" if maps is None else "uploaded"] += \
                len(chunks)
            if maps is not None:
                _RESIDENT_BYTES[str(device)] += maps
        with TraceAnnotation("vta.launch", chunks=len(chunks)):
            for cspec, cargs in chunks:
                state = _exec_chunk(cspec, self.gemm_impl, cargs, state)
        return {t: state["tensors"][names[t]]
                for t in (*trace.tensors_written, *put_host)}

    # -- Backend protocol --------------------------------------------------
    def run(self, prog: Program, hw: VTAConfig, dram: dict) -> None:
        shapes = {k: np.asarray(v).shape for k, v in dram.items()}
        trace = lower_cached(prog, hw, shapes)
        outs = self._execute(trace, hw,
                             {k: np.asarray(v)[None] for k, v in dram.items()})
        for name in trace.tensors_written:
            dram[name][...] = np.asarray(outs[name]).reshape(shapes[name])

    def run_batched(self, prog: Program, hw: VTAConfig, *, shared: dict,
                    batched: dict) -> dict:
        """The Backend protocol's batched run; the results stay on the
        device, unfetched, in the flat layout (``_execute``)."""
        trace = lower_cached(prog, hw, dispatch_shapes(prog, shared, batched))
        return self._execute(trace, hw, batched, shared)

    def chunk_compiles(self, prog: Program, hw: VTAConfig, *, shared: dict,
                       batched: dict, sharding=None) -> dict:
        """``{key: thunk}`` for every chunk ``run_batched`` would launch
        with arrays of these shapes and dtypes (``shared``/``batched`` hold
        anything with ``.shape``/``.dtype``). Each thunk lowers and compiles
        its chunk ahead of time for the current default device and returns
        the executable, so a pool of threads can compile a whole model at
        once (XLA compiles outside the GIL); the first dispatch then finds
        every executable built and traces nothing. Equal keys are the same
        program. ``sharding`` places the shapes instead (a device of a
        described topology, to compile for a chip that is not attached)."""
        shapes = {k: tuple(v.shape) for k, v in shared.items()}
        shapes.update({k: tuple(v.shape[1:]) for k, v in batched.items()})
        trace = lower_cached(prog, hw, shapes)
        names = _tensor_names(trace)
        n = next(iter(batched.values())).shape[0]
        inp_depth, BV, BI, wgt_depth, BO, acc_depth = _geom_of(hw)
        sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
        state = {"inp": sds((n, inp_depth, BV, BI), jnp.int8),
                 "wgt": sds((n, wgt_depth, BO, BI), jnp.int8),
                 "acc": sds((n, acc_depth, BV, BO), jnp.int32),
                 "tensors": {names[k]: sds((n, math.prod(shapes[k])), v.dtype)
                             for k, v in batched.items() if k in names},
                 "shared": {names[k]: sds((math.prod(v.shape),), v.dtype)
                            for k, v in shared.items() if k in names}}
        device = jax.config.jax_default_device
        out = {}
        for cspec, cargs in _spec_chunks(trace):
            args = tuple(sds(np.shape(a), np.asarray(a).dtype)
                         for a in cargs)
            leaves, tree = jax.tree_util.tree_flatten((args, state))
            key = (cspec, self.gemm_impl, tree, str(device),
                   tuple((x.shape, str(x.dtype)) for x in leaves))

            def thunk(cspec=cspec, args=args, gemm_impl=self.gemm_impl):
                with jax.default_device(device):
                    return _exec_chunk.lower(cspec, gemm_impl, args,
                                             state).compile()
            out[key] = thunk
        return out

    # -- divergence debugging (vta/trace.py) -------------------------------
    def run_stepped(self, prog: Program, hw: VTAConfig, dram: dict,
                    hook) -> None:
        """Execute one instruction at a time (each op is its own singleton
        chunk — cached like any other), calling ``hook(step, insn, state)``
        after each; ``state`` exposes numpy ``inp``/``wgt``/``acc``/``uop``
        snapshots shaped like the numpy FSim's, so vta/trace.py can digest
        both backends identically."""
        shapes = {k: np.asarray(v).shape for k, v in dram.items()}
        trace = lower_cached(prog, hw, shapes)
        names = _tensor_names(trace)
        inp_depth, BV, BI, wgt_depth, BO, acc_depth = _geom_of(hw)
        state = {"inp": jnp.zeros((1, inp_depth, BV, BI), jnp.int8),
                 "wgt": jnp.zeros((1, wgt_depth, BO, BI), jnp.int8),
                 "acc": jnp.zeros((1, acc_depth, BV, BO), jnp.int32),
                 "tensors": {names[k]: jnp.array(np.reshape(v, (1, -1)))
                             for k, v in dram.items() if k in names},
                 "shared": {}}
        uop = np.zeros((hw.uop_depth, 3), np.int64)

        class _View:
            pass

        for step, (insn, op) in enumerate(zip(trace.insns, trace.ops)):
            if isinstance(op, UopLoad):
                uop[op.base:op.base + len(op.values)] = op.values
            elif op is not None:
                mini = Trace(hw=hw, insns=[insn], ops=[op], touches=[])
                for cspec, cargs in _chunks(_spec_of(mini, names=names)):
                    state = _exec_chunk(cspec, self.gemm_impl, cargs, state)
            if hook is not None:
                view = _View()
                view.inp = np.asarray(state["inp"])[0]
                view.wgt = np.asarray(state["wgt"])[0]
                view.acc = np.asarray(state["acc"])[0]
                view.uop = uop
                hook(step, insn, view)
        for name in trace.tensors_written:
            dram[name][...] = np.asarray(
                state["tensors"][names[name]]).reshape(dram[name].shape)
