"""Execution-backend protocol + registry for the lowered tensor-op trace.

A *backend* executes the typed trace that ``vta/lowering.py`` produces from
a Program — nothing else. Because the trace resolves all meta-dict and
uop-buffer interpretation statically, every backend is bit-for-bit
comparable by construction, and equivalence is a tested invariant
(tests/test_backend.py, the CI equivalence smoke job).

Built-ins:

  * ``"numpy"`` — the reference ``FSim`` (vta/fsim.py): per-image, in-place,
    program order. The oracle everything else is judged against.
  * ``"jax"``  — loaded lazily from vta/fsim_jax.py: ``jax.jit``-compiled
    XLA execution of the same trace, ``vmap``-batched over N input images
    (one compiled program verifies a whole calibration batch), with fused
    ALU-chain kernels and whole-segment launches (repro.kernels registry).
    The platform picks the GEMM kernel (``fsim_jax.kernel_impls``): XLA's
    einsum on CPU, the compiled Pallas GEMM on a TPU; the ALU sweeps are
    the ``lax`` composites everywhere, because the TPU compiler refuses
    their Pallas form (docs/pallas.md).

Pick ``"numpy"`` for debugging (trace hooks, per-instruction digests — see
vta/trace.py) and small one-off runs; pick ``"jax"`` when the same program
runs over many images (autotuner winner verification, calibration sweeps)
or wherever fsim wall-clock is the bottleneck.

``run_batched``'s contract: ``batched`` maps tensor names to ``(N, ...)``
stacks (per-image inputs and output placeholders), ``shared`` maps names to
single arrays every image reuses (weights, biases); the return value maps
every tensor the program stores to its result. A stack may be a host array
or a device array, and also the jax executor's flat layout ``(N, size)``
(``lowering.dispatch_shapes`` restores its per-image shape). The numpy
backend returns host ``(N, ...)`` arrays. The jax backend returns device
arrays in the flat layout, unfetched, and adds every batched tensor it put
from the host, so a chain of dispatches that passes its results on puts
each host tensor once; read a result with ``np.asarray(v).reshape((N,) +
shape)``.
"""
from __future__ import annotations

from typing import Callable, Dict, Protocol, Union, runtime_checkable

import numpy as np

from repro.vta.isa import VTAConfig
from repro.vta.lowering import dispatch_shapes, lower_cached
from repro.vta.runtime import Program


@runtime_checkable
class Backend(Protocol):
    name: str

    def run(self, prog: Program, hw: VTAConfig, dram: dict) -> None:
        """Execute one image in place: stored tensors in ``dram`` are
        overwritten with the program's outputs."""
        ...

    def run_batched(self, prog: Program, hw: VTAConfig, *, shared: dict,
                    batched: dict) -> dict:
        """Execute N images; returns {stored tensor name: (N, ...) array}."""
        ...


class NumpyBackend:
    """Reference backend: the trace-executing FSim, image by image.

    ``run_batched`` lowers once and reuses the trace across the batch — the
    honest sequential baseline the JIT backend's speedup is measured
    against.
    """

    name = "numpy"

    def run(self, prog: Program, hw: VTAConfig, dram: dict) -> None:
        from repro.vta.fsim import FSim
        shapes = {k: np.asarray(v).shape for k, v in dram.items()}
        FSim(hw, dram).run(prog, trace=lower_cached(prog, hw, shapes))

    def run_batched(self, prog: Program, hw: VTAConfig, *, shared: dict,
                    batched: dict) -> dict:
        from repro.vta.fsim import FSim
        n = next(iter(batched.values())).shape[0]
        shapes = dispatch_shapes(prog, shared, batched)
        trace = lower_cached(prog, hw, shapes)
        batched = {k: np.asarray(v).reshape((n,) + shapes[k])
                   for k, v in batched.items()}
        outs: dict = {t: [] for t in trace.tensors_written}
        for i in range(n):
            dram = dict(shared)
            # fresh copies: callers keep their (N, ...) stacks untouched,
            # matching the jax backend's functional behavior
            dram.update({k: np.array(v[i]) for k, v in batched.items()})
            FSim(hw, dram).run(prog, trace=trace)
            for t in outs:
                outs[t].append(dram[t])
        return {t: np.stack(v) for t, v in outs.items()}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_FACTORIES: Dict[str, Callable[[], Backend]] = {}
_INSTANCES: Dict[str, Backend] = {}


def register_backend(name: str, factory: Callable[[], Backend], *,
                     replace: bool = False) -> None:
    if not replace and name in _FACTORIES:
        raise ValueError(f"backend {name!r} already registered")
    _FACTORIES[name] = factory
    _INSTANCES.pop(name, None)


def available_backends() -> list:
    return sorted(_FACTORIES)


def get_backend(backend: Union[str, Backend, None]) -> Backend:
    """Resolve a backend name (or pass an instance through). ``None`` means
    the numpy reference."""
    if backend is None:
        backend = "numpy"
    if not isinstance(backend, str):
        return backend
    if backend in _INSTANCES:
        return _INSTANCES[backend]
    if backend not in _FACTORIES:
        raise KeyError(f"unknown backend {backend!r}; "
                       f"available: {available_backends()}")
    _INSTANCES[backend] = _FACTORIES[backend]()
    return _INSTANCES[backend]


def _jax_factory() -> Backend:
    try:
        from repro.vta.fsim_jax import JaxBackend
    except ImportError as e:                        # pragma: no cover
        raise ImportError(
            "the 'jax' execution backend needs jax installed "
            "(pip install jax); underlying error: " + str(e)) from e
    return JaxBackend()


register_backend("numpy", NumpyBackend)
register_backend("jax", _jax_factory)


# ---------------------------------------------------------------------------
# Degradation ladder (serving reliability, serve/breaker.py)
# ---------------------------------------------------------------------------
# Best-first order for fault degradation. Because every backend executes
# the identical lowered trace bit-for-bit, stepping down the ladder under
# faults trades throughput only — result fidelity is preserved by
# construction (asserted in tests/test_faults.py).
DEGRADATION_LADDER = ("jax", "numpy")


def backend_kernel_impls(backend: Union[str, Backend]) -> tuple:
    """The registry (kernel, impl) pairs the resolved backend instance
    routes compute through — the coordinates per-(backend, kernel-impl)
    circuit breakers and ``kernel.impl`` fault specs are scoped by: the
    jax backend's GEMM (``gemm_impl``) and its ``lax`` ALU chains. The
    numpy reference resolves no registry kernels: ``()``."""
    gemm = getattr(get_backend(backend), "gemm_impl", None)
    return () if gemm is None else (("gemm", gemm), ("alu_chain", "lax"))
