"""Scratchpad-blocked Pallas GEMM — one kernel behind two entry points.

This is the MXU analogue of the paper's pipelined GEMM core (§IV.A.1),
shared by the TPU-plane epilogue entry point (kernels/gemm.py) and the VTA
execution backend's per-instruction contraction (registry ``gemm``):

  * BlockSpec tiles (bm, bn, bk) come from core/tile_search.select_gemm_tile
    — the paper's TPS constrained-byte-minimization (core/tps.py Appendix-A
    math) applied to VMEM instead of scratchpads;
  * grid order (m, n, k) with k innermost: the f32 accumulator tile stays
    resident in VMEM across the reduction (output-stationary), and Pallas's
    automatic grid pipelining provides the double buffering the paper's
    virtual threads implement by hand;
  * odd/prime shapes are handled by **zero-padding operands up to the block
    multiple and slicing the result** (a masked tail): padding contributes
    exact zeros to every partial sum, so the grid never degrades to bm=1
    the way divisor-shrinking did;
  * the optional epilogue fuses the paper's `clip` instruction (+ bias /
    activation) into the final reduction step — one pass instead of
    separate ALU ops.

Exactness: f32 accumulation is blocked, and f32 addition is not associative
in general — but for the VTA contract (int8-valued operands, at most
``F32_EXACT_TERMS`` contraction terms per call) every partial sum is an
exact integer below 2^24, so any blocking is bit-identical to ``jnp.dot``.
The generic float entry (kernels/gemm.py) is validated by allclose instead.

Validated in interpret mode on CPU (tests/test_kernels.py,
tests/test_backend.py); on TPU/GPU pass ``interpret=False``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tile_search import (LANE, GemmTile, _sublane,
                                    select_gemm_tile)
from repro.kernels.registry import register_kernel


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def gemm_blocking(M: int, N: int, K: int, *, in_bytes: int = 4) -> tuple:
    """(bm, bn, bk) for an (M, K) @ (K, N) matmul: the TPS-for-BlockSpecs
    argmin, clamped to the alignment-padded problem so small problems run
    as a single whole-problem tile instead of over-padding."""
    t = select_gemm_tile(M, N, K, in_bytes=in_bytes)
    sub = _sublane(in_bytes)
    return (min(t.bm, _round_up(M, sub)),
            min(t.bn, _round_up(N, LANE)),
            min(t.bk, _round_up(K, LANE)))


def _gemm_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, n_k: int,
                 k_axis: int, act: Optional[str], clip: Optional[float],
                 has_bias: bool):
    k = pl.program_id(k_axis)           # the reduction is the last grid axis

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out = acc_ref[...]
        if has_bias:
            out = out + b_ref[...].astype(jnp.float32)
        if act == "relu":
            out = jax.nn.relu(out)
        elif act == "silu":
            out = jax.nn.silu(out)
        elif act == "gelu":
            out = jax.nn.gelu(out, approximate=True)
        if clip is not None:
            out = jnp.clip(out, -clip, clip)
        o_ref[...] = out.astype(o_ref.dtype)


def blocked_gemm(x, w, bias=None, *, act: Optional[str] = None,
                 clip: Optional[float] = None, tile=None,
                 interpret: bool = True):
    """x (M,K) @ w (K,N) -> (M,N) with optional fused epilogue, or a batch
    of independent matmuls x (B,M,K) @ w (B,K,N) -> (B,M,N) as ONE kernel
    launch, the batch a leading grid axis.

    ``tile``: a ``GemmTile`` or (bm, bn, bk) tuple; default
    ``gemm_blocking``. Operands are zero-padded to block multiples and the
    result sliced back — exact for the matmul (zero rows/columns), and the
    epilogue's padded lanes are discarded by the slice.
    """
    batch = x.shape[:-2]
    assert len(batch) <= 1 and w.shape[:-2] == batch, (x.shape, w.shape)
    M, K = x.shape[-2:]
    K2, N = w.shape[-2:]
    assert K == K2, (x.shape, w.shape)
    if tile is None:
        bm, bn, bk = gemm_blocking(M, N, K, in_bytes=x.dtype.itemsize)
    elif isinstance(tile, GemmTile):
        bm, bn, bk = tile.bm, tile.bn, tile.bk
    else:
        bm, bn, bk = tile
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    has_bias = bias is not None
    b = bias if has_bias else jnp.zeros((N,), x.dtype)
    lead = ((0, 0),) * len(batch)
    if (Mp, Kp) != (M, K):
        x = jnp.pad(x, lead + ((0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        w = jnp.pad(w, lead + ((0, Kp - K), (0, Np - N)))
        b = jnp.pad(b, (0, Np - N))
    n_m, n_n, n_k = Mp // bm, Np // bn, Kp // bk

    if batch:          # grid (l, i, j, k): the batch is a squeezed block dim
        grid = (batch[0], n_m, n_n, n_k)
        in_specs = [
            pl.BlockSpec((None, bm, bk), lambda l, i, j, k: (l, i, k)),
            pl.BlockSpec((None, bk, bn), lambda l, i, j, k: (l, k, j)),
            pl.BlockSpec((bn,), lambda l, i, j, k: (j,)),
        ]
        out_spec = pl.BlockSpec((None, bm, bn),
                                lambda l, i, j, k: (l, i, j))
    else:
        grid = (n_m, n_n, n_k)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bn,), lambda i, j, k: (j,)),
        ]
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    kernel = functools.partial(_gemm_kernel, n_k=n_k, k_axis=len(grid) - 1,
                               act=act, clip=clip, has_bias=has_bias)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(batch + (Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w, b)
    return out[..., :M, :N] if (Mp, Np) != (M, N) else out


# ---------------------------------------------------------------------------
# Registry: the VTA backend's matmul entry points ((x, w) f32 -> f32)
# ---------------------------------------------------------------------------
def _einsum_gemm(x, w):
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


register_kernel("gemm", "einsum", _einsum_gemm)
register_kernel("gemm", "pallas",
                lambda x, w: blocked_gemm(x, w, interpret=False))
register_kernel("gemm", "pallas_interpret",
                lambda x, w: blocked_gemm(x, w, interpret=True))
