"""Kernel registry: one kernel name, several interchangeable implementations.

The execution backends (vta/fsim_jax.py) and the standalone TPU-plane entry
points (kernels/ops.py, kernels/gemm.py) historically each carried their own
Pallas kernels; this registry makes the kernel the unit of sharing instead.
A *kernel* is a named contract (argument/return convention + exactness
requirements, stated below); an *implementation* is one way to execute it —
a plain-XLA composite, a compiled Pallas kernel, or the same Pallas kernel
in interpret mode for CPU validation.

Built-ins (registered lazily on first lookup so importing this module never
pays for jax tracing):

  ``"gemm"``       f32 ``(M, K) @ (K, N) -> (M, N)`` matmul, or a batch of
                   them, ``(B, M, K) @ (B, K, N) -> (B, M, N)``, in one
                   call. Bit-exact for
                   int8-valued operands with partial sums below 2^24 (the
                   ``F32_EXACT_TERMS`` contract in vta/lowering.py), on
                   every implementation.
                   impls: ``einsum`` | ``pallas`` | ``pallas_interpret``
                   (kernels/vta_gemm.py — TPS-blocked, padded tails).
                   The platform picks ``einsum`` (CPU) or ``pallas`` (TPU);
                   ``pallas_interpret`` checks the TPU's kernel on the CPU.

  ``"alu_chain"``  fused gather -> reduce -> scatter evaluation of a legal
                   ALU-sweep chain against the int32 acc scratchpad, and
  ``"alu_sweep"``  its DRAM-direct form (kernels/alu_sweep.py). Bit-exact
                   vs the sequential numpy FSim by construction (int32
                   wraparound, arithmetic shift).
                   impls: ``lax`` (every platform)

``register_kernel`` is open: tests and experiments may add implementations
(e.g. a reference impl to diff against) without touching the backends.
"""
from __future__ import annotations

from typing import Callable, Dict

_KERNELS: Dict[str, Dict[str, Callable]] = {}
_BUILTINS_READY = False


def register_kernel(name: str, impl: str, fn: Callable, *,
                    replace: bool = False) -> None:
    """Register ``fn`` as implementation ``impl`` of kernel ``name``."""
    impls = _KERNELS.setdefault(name, {})
    if not replace and impl in impls:
        raise ValueError(f"kernel {name!r} impl {impl!r} already registered")
    impls[impl] = fn


def _ensure_builtins() -> None:
    global _BUILTINS_READY
    if _BUILTINS_READY:
        return
    # the modules self-register at import (under Python's import lock, so
    # threads tracing their first chunks at once all see the full table);
    # tolerate a jax-less environment (the numpy backend never touches
    # this registry)
    try:
        from repro.kernels import alu_sweep, vta_gemm  # noqa: F401
    except ImportError:                                # pragma: no cover
        pass
    _BUILTINS_READY = True


def get_kernel(name: str, impl: str) -> Callable:
    """Resolve one implementation; KeyError names the alternatives."""
    _ensure_builtins()
    impls = _KERNELS.get(name)
    if not impls:
        raise KeyError(f"unknown kernel {name!r}; "
                       f"available: {sorted(_KERNELS)}")
    if impl not in impls:
        raise KeyError(f"kernel {name!r} has no impl {impl!r}; "
                       f"available: {sorted(impls)}")
    return impls[impl]


def swap_kernel(name: str, impl: str, fn: Callable) -> Callable:
    """Atomically replace implementation ``impl`` of kernel ``name`` and
    return the previous callable so callers can restore it — the hook
    fault-injection wrappers (serve/faults.py) and instrumented test
    doubles use. KeyError (naming the alternatives) when the pair is
    unknown: swapping never silently registers a new implementation."""
    old = get_kernel(name, impl)
    _KERNELS[name][impl] = fn
    return old


def available_impls(name: str) -> list:
    _ensure_builtins()
    return sorted(_KERNELS.get(name, {}))
