"""chip_smoke.py on the CPU at a tiny scale, and the bring-up pieces it
rests on: the full-width served ResNet-18, platform kernel choice, the
ladder on a TPU, one process per chip and the compile cache."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.serve.model import served_model
from repro.vta import backend as backend_mod
from repro.vta import fsim_jax

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = {"full": ("resnet18", "tiny"),
        "mix": (("alice", "resnet18", "tiny"), ("bob", "mobilenet", "tiny"))}


def _run(code: str, **env) -> subprocess.CompletedProcess:
    e = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    e.update(env)
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=e,
                          capture_output=True, text=True, timeout=300)


def test_run_smoke_tiny_on_cpu():
    lines = []
    res = chip_smoke.run_smoke(n_burst=4, n_check=2, n_mix=2, bucket=2,
                               log=lines.append, **TINY)
    assert res["new_traces"] == 0
    assert res["batches"] == 2 and res["checked"] == 2
    assert res["mix_checked"] == 4
    assert res["launches_per_batch"] > 0
    assert res["upload_bytes_per_batch"] > 0
    assert any("bit-exact" in s for s in lines)


def test_smoke_fails_on_a_broken_output(monkeypatch):
    real = chip_smoke._compare

    def off_by_one(outs, refs, what):
        return real([o + 1 for o in outs], refs, what)
    monkeypatch.setattr(chip_smoke, "_compare", off_by_one)
    with pytest.raises(chip_smoke.SmokeFailure, match="differ from numpy"):
        chip_smoke.run_smoke(n_burst=2, n_check=1, n_mix=1, bucket=2,
                             log=lambda s: None, **TINY)


def test_run_scaleout_on_four_virtual_devices():
    """The --chips 4 path on four CPU devices: every device dispatches and
    every output of 1 and 4 workers matches numpy."""
    code = ("import json, chip_smoke\n"
            "r = chip_smoke.run_scaleout(full=('resnet18', 'tiny'), "
            "n_burst=8, bucket=2, log=lambda s: None)\n"
            "print(json.dumps({str(k): v for k, v in r.items()}))")
    p = _run(code, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert len(res["4"]["launches_by_device"]) == 4
    assert res["1"]["checked"] == res["4"]["checked"] == 8


def test_main_refuses_without_a_tpu():
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=ROOT, capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert '"ok"' not in p.stdout


def test_full_resnet18_is_the_dse_graph_without_its_stem():
    m = served_model("resnet18", "full")
    assert len(m.segments) == 21
    assert m.image_shape == (1, 64, 112, 112)
    assert m.output_shape == (1, 1008, 1, 1)
    assert not any(n.on_cpu for n in m.graph.topo())
    assert sum(w.nbytes for w in m.weights.values()) > 11e6
    with pytest.raises(KeyError, match="unknown scale"):
        served_model("resnet18", "huge")


@pytest.mark.parametrize("platform,want", [
    ("cpu", {"gemm": "einsum", "alu": "lax"}),
    ("gpu", {"gemm": "einsum", "alu": "lax"}),
    ("tpu", {"gemm": "pallas", "alu": "lax"}),
])
def test_kernel_impls_by_platform(platform, want):
    assert fsim_jax.kernel_impls(platform) == want


def test_ladder_is_jax_then_numpy_with_breakers_keyed_by_kernel():
    from repro.serve.breaker import DegradingBackendExecutor
    assert backend_mod.DEGRADATION_LADDER == ("jax", "numpy")
    gemm = fsim_jax.kernel_impls(jax.default_backend())["gemm"]
    ex = DegradingBackendExecutor({})
    assert [r.breaker.key for r in ex.rungs] == [
        f"jax[gemm:{gemm},alu_chain:lax]", "numpy[reference]"]


def test_one_process_per_chip(monkeypatch):
    from repro.core import dse
    from repro.serve.workers import WorkerPool
    assert not dse._on_accelerator("jax")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dse._on_accelerator("jax") and not dse._on_accelerator("numpy")
    with pytest.raises(RuntimeError, match="process transport needs JAX on "
                                           "the CPU"):
        WorkerPool(n=1, transport="process", backend="numpy",
                   process_specs={"m": ("mobilenet", "tiny")})


def test_precompiled_batch_traces_nothing():
    m = served_model("mobilenet", "tiny")
    assert m.precompile(3) > 0
    assert m.precompile(3, backend="numpy") == 0
    fsim_jax.reset_xla_trace_log()
    imgs = m.random_images(3, seed=5)
    out = m.run_batch(imgs, backend="jax")
    assert fsim_jax.xla_trace_log() == {}
    for i in range(3):
        np.testing.assert_array_equal(out[i], m.run_single(imgs[i]))


def test_compile_cache_dir_is_left_to_jax(tmp_path):
    code = ("import jax\n"
            "from repro.serve.model import served_model\n"
            "m = served_model('mobilenet', 'tiny')\n"
            "m.run_batch(m.random_images(1), backend='jax')\n"
            "print(jax.config.jax_compilation_cache_dir)")
    p = _run(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir())
    assert fsim_jax.DEFAULT_CACHE_DIR == str(ROOT / ".jax_cache")
