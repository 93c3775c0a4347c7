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
    ALU-chain kernels and whole-segment launches (repro.kernels registry),
    chosen by platform (``fsim_jax.kernel_impls``): XLA composites on CPU;
    on a TPU the compiled Pallas GEMM with the ``lax`` ALU sweeps, whose
    Pallas kernels the TPU compiler refuses (docs/pallas.md).
  * ``"jax-pallas"`` — the jax backend with the Pallas kernels forced on:
    interpret mode on CPU (slow — validation, not performance; equivalent
    to running under REPRO_FSIM_PALLAS=1); on a TPU the same kernels as
    ``"jax"``, so the degradation ladder drops it there.

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


def _jax_pallas_factory() -> Backend:
    import jax
    from repro.vta.fsim_jax import JaxBackend, kernel_impls
    impls = kernel_impls(jax.default_backend(), pallas=True)
    be = JaxBackend(gemm_impl=impls["gemm"], alu_impl=impls["alu"])
    be.name = "jax-pallas"
    return be


register_backend("numpy", NumpyBackend)
register_backend("jax", _jax_factory)
register_backend("jax-pallas", _jax_pallas_factory)


# ---------------------------------------------------------------------------
# Degradation ladder (serving reliability, serve/breaker.py)
# ---------------------------------------------------------------------------
# Best-first order for fault degradation. Because every backend executes
# the identical lowered trace bit-for-bit, stepping down the ladder under
# faults trades throughput only — result fidelity is preserved by
# construction (asserted in tests/test_faults.py).
DEGRADATION_LADDER = ("jax-pallas", "jax", "numpy")


def backend_kernel_impls(backend: Union[str, Backend]) -> tuple:
    """The registry (kernel, impl) pairs the resolved backend instance
    routes compute through — the coordinates per-(backend, kernel-impl)
    circuit breakers and ``kernel.impl`` fault specs are scoped by. The
    numpy reference resolves no registry kernels: ``()``."""
    be = get_backend(backend)
    pairs = []
    for kernel, attr in (("gemm", "gemm_impl"), ("alu_chain", "alu_impl")):
        impl = getattr(be, attr, None)
        if impl is not None:
            pairs.append((kernel, impl))
    return tuple(pairs)


def distinct_ladder(ladder: tuple = DEGRADATION_LADDER) -> tuple:
    """``ladder`` with every rung dropped that resolves to the same kernels
    as a later one. On a TPU ``jax-pallas`` and ``jax`` run the same
    kernels (``fsim_jax.kernel_impls``), and a second rung of the same
    kernels only repeats the failure, so the ladder there is
    ``("jax", "numpy")``; on the CPU every rung is distinct."""
    impls = [backend_kernel_impls(name) for name in ladder]
    return tuple(name for i, name in enumerate(ladder)
                 if impls[i] not in impls[i + 1:])
