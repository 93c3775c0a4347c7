"""TPS-tiled Pallas matmul with fused VTA-style epilogue (bias/act/clip).

Entry point over the shared blocked kernel in ``kernels/vta_gemm.py`` — the
same kernel the VTA execution backend uses for its GEMM instructions (the
``gemm`` registry's ``pallas`` impl); this module adds nothing but the
epilogue defaults. See vta_gemm's docstring for the blocking derivation
(TPS tile math on VMEM), the padded-tail handling of odd shapes, and the
exactness argument.

Validated in interpret mode on CPU against kernels/ref.py::matmul_ref; on a
real TPU pass interpret=False.
"""
from __future__ import annotations

from typing import Optional

from repro.core.tile_search import GemmTile
from repro.kernels.vta_gemm import blocked_gemm


def gemm(x, w, bias=None, *, act: Optional[str] = None,
         clip: Optional[float] = None, tile: Optional[GemmTile] = None,
         interpret: bool = True):
    """x (M,K) @ w (K,N) -> (M,N) with fused epilogue."""
    return blocked_gemm(x, w, bias, act=act, clip=clip, tile=tile,
                        interpret=interpret)
