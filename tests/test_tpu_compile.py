"""Compile the served path's kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed without a chip, compiles
for a chip that is described and not attached, and refuses what the chip
would refuse (block shapes, gathers inside kernels, memory). These are the
kernels ``kernel_impls("tpu")`` picks, at the shapes the served models
launch. The topology is described inside a fixture, never at import: one
process at a time may load the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.serve.model import served_model
from repro.vta.fsim_jax import JaxBackend, kernel_impls
from repro.vta.lowering import lower_cached

BATCH = 8                      # the bucket chip_smoke.py serves


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the topology, with the persistent compilation cache off
    meanwhile: an executable for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _tpu_backend() -> JaxBackend:
    be = JaxBackend()
    be.gemm_impl = kernel_impls("tpu")["gemm"]
    return be


def _compile_chunk(model, pick, sharding):
    """Compile the first chunk of ``model`` (bucket ``BATCH``) for which
    ``pick(segment trace, chunk spec)`` holds."""
    be = _tpu_backend()
    for seg in model.segments:
        batched = {t: np.broadcast_to(np.int8(0),
                                      (BATCH,) + model.dram_shapes[t])
                   for t in model._activations(seg)}
        shared = model._weights_of(seg)
        shapes = {k: v.shape for k, v in shared.items()}
        shapes.update({k: v.shape[1:] for k, v in batched.items()})
        trace = lower_cached(seg.program, model.hw, shapes)
        for key, thunk in be.chunk_compiles(seg.program, model.hw,
                                            shared=shared, batched=batched,
                                            sharding=sharding).items():
            if pick(trace, key[0]):
                return key[0], thunk()
    raise AssertionError(f"{model.name}: no chunk matched")


def test_kernel_impls_for_tpu():
    assert kernel_impls("tpu") == {"gemm": "pallas", "alu": "lax"}


@pytest.mark.parametrize("lead,m,k,n", [
    ((1,), 196, 288, 16),     # resnet18-small 3x3 conv
    ((2,), 196, 32, 16),      # mobilenet-small pointwise, 2 weight blocks
    ((16,), 49, 288, 16),     # resnet18-full stage 3, 16 weight blocks
])
def test_blocked_gemm_compiles(one_chip, lead, m, k, n):
    from repro.kernels.registry import get_kernel
    gemm = get_kernel("gemm", kernel_impls("tpu")["gemm"])
    x = jax.ShapeDtypeStruct(lead + (m, k), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct(lead + (k, n), jnp.float32, sharding=one_chip)
    compiled = jax.jit(gemm).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_conv_add_clip_chunk_compiles(one_chip):
    spec, compiled = _compile_chunk(
        served_model("resnet18", "small"),
        lambda trace, spec: trace.fused_segment, one_chip)
    kinds = {e[0] for e in spec}
    assert {"gemm_window", "aluchain", "store"} <= kinds
    assert "tpu_custom_call" in compiled.as_text()      # the Pallas GEMM


def test_depthwise_chunk_compiles(one_chip):
    spec, compiled = _compile_chunk(
        served_model("mobilenet", "small"),
        lambda trace, spec: any(e[0] == "alusweep" for e in spec), one_chip)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


def test_padded_fused_project_add_chunk_compiles(one_chip):
    """MobileNetV2's 24-channel stage at resolution 32: the project conv
    (144 -> 24, padded to 32) fused with its residual add, whose skip
    tensor is padded too."""
    from repro.serve.model import ServedModel, device_graph
    from repro.vta.isa import DEFAULT_VTA
    from repro.vta.workloads import mobilenet_v2_graph
    model = ServedModel.compile(
        "mobilenetv2-r32", device_graph(mobilenet_v2_graph(resolution=32)),
        DEFAULT_VTA)
    add = next(s for s in model.segments if s.label == "mbv2.b2.add")
    assert add.padded == 16
    spec, compiled = _compile_chunk(
        model, lambda trace, spec: trace.tensors_written == ("mbv2.b2.add",),
        one_chip)
    assert {"gemm_window", "store"} <= {e[0] for e in spec}
    assert "tpu_custom_call" in compiled.as_text()      # the Pallas GEMM
