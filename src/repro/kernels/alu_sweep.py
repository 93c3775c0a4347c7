"""Fused ALU-sweep chain kernel: gather -> reduce -> scatter in one pass.

The depthwise and pooling schedules dominate their layers with runs of ALU
macro-ops against the int32 acc scratchpad — a seed (overwrite/copy/MAC), a
tap-accumulation sweep (MAC or MAX/ADD/MIN over the kh*kw taps), then a
requant epilogue (SHR, MAX/MIN, CLIP). Executed op by op (PR 5's path), each
op is its own gather + lax reduce + scatter against a (depth, BV, BO) acc
array — all through HBM. ``vta/lowering.py`` proves which runs are legal to
fuse (every op writes the same unique-indexed destination rows from sources
disjoint with them — see ``AluChain``) and flattens them into a *stage
program*; this module evaluates a whole stage program as ONE kernel: gather
the operand rows, reduce through the stages in registers, scatter the
destination rows once.

The stage encoding is plain data (string-keyed tuples + numpy index
vectors), so this module needs no vta imports and the stage tuple can sit in
a ``jax.jit`` static spec:

  ``("seed_imm", imm)``          v = imm                      (args: -)
  ``("seed_copy",)``             v = acc[src]                 (args: src)
  ``("seed_mac",)``              v = acc[src] * acc[src2]     (args: src, src2)
  ``("read_dst",)``              v = acc[dst]                 (args: -)
  ``("mac", T)``                 v += sum_t acc[srcs_t] * acc[src2_t]
                                                              (args: srcs, src2)
  ``("red", name, T)``           v = name(v, reduce_t acc[srcs_t])
                                                              (args: srcs)
  ``("src", name)``              v = name(v, acc[src])        (args: src)
  ``("imm", name, imm)``         v = name(v, imm)             (args: -)

Exactness vs the sequential numpy FSim is by construction: the chain
legality rules guarantee every stage reads rows the chain never writes, so
deferring the single scatter to the end is observationally identical to the
per-op scatters; int32 arithmetic wraps, SHR is an arithmetic shift, and
CLIP clamps to ``abs(imm)`` exactly as the interpreter does.

Implementations (registry names ``"alu_chain"`` and ``"alu_sweep"``):
``lax`` — the jnp composites, on every platform. The TPU compiler refuses
them as Pallas kernels ("Only 2D gather is supported"; docs/pallas.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.registry import register_kernel


def _binop(name: str, v, s):
    if name == "add":
        return v + s
    if name == "max":
        return jnp.maximum(v, s)
    if name == "min":
        return jnp.minimum(v, s)
    if name == "shr":
        return jnp.right_shift(v, s)
    if name == "mul":
        return v * s
    raise ValueError(name)


def _run_stages(acc, dst, stages, vals):
    """Reduce a stage program over pre-gathered operand VALUES.

    ``vals`` aligns positionally with the stage operand slots: each entry
    is the already-materialized ``acc[rows]``-shaped array — whether it was
    gathered from the scratchpad or read straight out of a DRAM tensor is
    the caller's business, the arithmetic is identical."""
    it = iter(vals)
    v = None
    for st in stages:
        kind = st[0]
        if kind == "read_dst":
            v = acc[dst]
        elif kind == "seed_imm":
            v = jnp.broadcast_to(jnp.int32(st[1]), acc[dst].shape)
        elif kind == "seed_copy":
            v = next(it)
        elif kind == "seed_mac":                # (g,...) * (1,...) broadcast
            v = next(it) * next(it)
        elif kind == "mac":
            srcs = next(it)                     # (T, g, BV, BO) | per-tap
            src2 = next(it)                     # (T, BV, BO)
            if isinstance(srcs, list):          # tap-unrolled: no (T, g,
                for t, s in enumerate(srcs):    # BV, BO) temp, each tap's
                    v = v + s * src2[t]         # gather+FMA fuses
            else:
                v = v + (srcs * src2[:, None]).sum(0)
        elif kind == "red":
            s = next(it)                        # (T, g, BV, BO) | per-tap
            name = st[1]
            if isinstance(s, list):
                for x in s:
                    v = _binop(name, v, x)
            elif name == "add":
                v = v + s.sum(0)
            elif name == "max":
                v = jnp.maximum(v, s.max(0))
            else:
                v = jnp.minimum(v, s.min(0))
        elif kind == "src":
            v = _binop(st[1], v, next(it))
        elif kind == "imm":
            name, imm = st[1], st[2]
            if name == "clip":
                bound = abs(int(imm))
                v = jnp.clip(v, -bound, bound)
            else:
                v = _binop(name, v, jnp.int32(imm))
        else:
            raise ValueError(kind)
    return v


def eval_chain(acc, dst, stages, args, *, unique: bool = False,
               sorted_: bool = False):
    """Evaluate one stage program against ``acc`` (depth, BV, BO) int32.

    ``dst`` (g,) — the chain's destination rows; ``args`` — index arrays
    consumed positionally by the stages (see module docstring). Returns the
    updated acc (one scatter).
    """
    v = _run_stages(acc, dst, stages, [acc[a] for a in args])
    return acc.at[dst].set(v, unique_indices=unique,
                           indices_are_sorted=sorted_)


def eval_sweep(acc, dst, stages, ops_args, *, slabs=(),
               write_acc: bool = True,
               unique: bool = False, sorted_: bool = False,
               out_flat=None, store_idx=None, store_mask=None,
               store_unique: bool = False, store_sorted: bool = False,
               store_affine=None):
    """The DRAM-direct sweep: gather -> reduce -> scatter as ONE kernel.

    ``slabs`` entries ``(flat, idx, mask, fill)`` replay the chain's feeder
    GatherLoads locally: each source DRAM tensor is gathered ONCE with the
    load's own index map (mask False -> fill, widen to int32 — byte-
    identical to the gather-to-acc path it replaces) and the slab values
    concatenate into a local buffer that never touches the scratchpad.
    ``ops_args`` entries are ``("acc", rows)`` — read the scratchpad as
    ``eval_chain`` does — or ``("local", rows)`` — row-index the slab
    buffer. When ``out_flat`` is given the chain value is clipped to int8
    and scattered straight into that tensor (``store_mask`` False lanes
    drop); ``write_acc=False`` additionally skips the acc scatter when
    lowering proved nothing reads it, making the sweep pure DRAM -> DRAM.

    Returns ``(acc', out_flat')`` — unchanged inputs where not written.
    """
    parts = []
    for flat, idx, mask, fill in slabs:
        s = flat[idx]
        if mask is not None:
            s = jnp.where(mask, s, jnp.asarray(fill, s.dtype))
        parts.append(s)
    local = None
    if parts:
        # Keep the buffer in the tensors' native (usually int8) dtype so
        # the T-tap row-gathers below move 1/4 the bytes; widening to
        # int32 commutes with gather/where, so values are unchanged.
        if len({p.dtype for p in parts}) > 1:
            parts = [p.astype(jnp.int32) for p in parts]
        local = parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

    def val(d):
        if d[0] == "acc":
            return acc[d[1]]
        return local[d[1]].astype(jnp.int32)

    def taps(d):
        # tap axis unrolled: one (g, BV, BO) gather per tap instead of a
        # single (T, g, BV, BO) gather whose int32 temp XLA materializes
        if d[0] == "acc":
            return [acc[r] for r in d[1]]
        return [local[r].astype(jnp.int32) for r in d[1]]

    si = iter(ops_args)
    vals = []
    for st in stages:
        k = st[0]
        if k == "seed_copy" or k == "src":
            vals.append(val(next(si)))
        elif k == "seed_mac":
            vals.append(val(next(si)))
            vals.append(val(next(si)))
        elif k == "mac":
            vals.append(taps(next(si)))
            vals.append(val(next(si)))
        elif k == "red":
            # stacked, not tap-unrolled: reductions tree-reduce over the
            # tap axis, and the (T, g, BV, BO) temp is what enables that
            d = next(si)
            vals.append(acc[d[1]] if d[0] == "acc"
                        else local[d[1]].astype(jnp.int32))

    v = _run_stages(acc, dst, stages, vals)
    acc2 = acc.at[dst].set(v, unique_indices=unique,
                           indices_are_sorted=sorted_) if write_acc else acc
    out2 = out_flat
    if out_flat is not None:
        vals = jnp.clip(v, -128, 127).astype(out_flat.dtype)
        if store_affine is not None:
            # lowering proved the index map is a constant-stride block:
            # the scatter becomes one contiguous dynamic_update_slice
            # (XLA's CPU scatter serializes element by element).
            # ``store_idx`` carries the per-chain block starts.
            view_shape, perm, sizes = store_affine
            block = vals.transpose(perm).reshape(sizes)
            view = out_flat.reshape(view_shape)
            starts = tuple(store_idx[i] for i in range(len(view_shape)))
            out2 = jax.lax.dynamic_update_slice(view, block, starts) \
                .reshape(out_flat.shape)
        else:
            idx = store_idx if store_mask is None else \
                jnp.where(store_mask, store_idx, out_flat.shape[0])
            out2 = out_flat.at[idx].set(vals, mode="drop",
                                        unique_indices=store_unique,
                                        indices_are_sorted=store_sorted)
    return acc2, out2


register_kernel("alu_chain", "lax", eval_chain)
register_kernel("alu_sweep", "lax", eval_sweep)
