"""The chunks' index maps resident on the device: a served model's first
batch on a device puts them there once, later batches launch the same
chunks on the resident copies, bit-exact against numpy, with no new trace
and with the upload counters telling the two apart; one copy per device."""
import json
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.serve.model import served_model
from repro.vta import fsim_jax
from repro.vta.backend import get_backend
from repro.vta.lowering import lower_cached

ROOT = Path(__file__).resolve().parents[1]
CASES = [(net, n) for net in ("resnet18", "mobilenet") for n in (1, 8)]


def _fresh(network):
    """A model with programs of its own, so nothing of it is resident."""
    return served_model.__wrapped__(network, "tiny")


def _traces(model) -> list:
    out = []
    for seg in model.segments:
        shapes = {t: model.shapes[t] for t in model._activations(seg)}
        shapes.update({t: w.shape for t, w in model._weights_of(seg).items()})
        out.append(lower_cached(seg.program, model.hw, shapes))
    return out


def _map_bytes(model) -> int:
    return sum(np.asarray(a).nbytes for tr in _traces(model)
               for _, args in fsim_jax._spec_chunks(tr) for a in args)


def _batches(model, n, count=3):
    """``count`` batches of ``n`` on the jax backend, each checked bit for
    bit against numpy; yields after each with its index-map bytes."""
    for i in range(count):
        before = fsim_jax.upload_bytes_by_kind()["index_maps"]
        images = model.random_images(n, seed=40 + i)
        out = model.run_batch(images, backend="jax")
        np.testing.assert_array_equal(
            out, model.run_batch(images, backend="numpy"))
        yield fsim_jax.upload_bytes_by_kind()["index_maps"] - before


@pytest.mark.parametrize("network,n", CASES)
def test_consecutive_batches_are_bit_exact_against_numpy(network, n):
    model = _fresh(network)
    assert len(list(_batches(model, n))) == 3


@pytest.mark.parametrize("network,n", CASES)
def test_only_the_first_batch_puts_the_index_maps(network, n):
    model = _fresh(network)
    model.precompile(n, threads=2)
    fsim_jax.reset_kernel_launch_log()
    per_batch = list(_batches(model, n))
    assert per_batch == [_map_bytes(model), 0, 0]
    launches = fsim_jax.kernel_launch_log()
    log = fsim_jax.index_map_residency_log()
    assert log["uploaded_dispatches"] == launches // 3
    assert log["resident_dispatches"] == 2 * launches // 3
    assert log["resident_bytes"] == {
        str(jax.devices()[0]): _map_bytes(model)}
    assert sum(fsim_jax.upload_bytes_by_kind().values()) == \
        fsim_jax.upload_bytes_log()
    fsim_jax.reset_kernel_launch_log()
    assert fsim_jax.index_map_residency_log() == {
        "resident_dispatches": 0, "uploaded_dispatches": 0,
        "resident_bytes": {}}


@pytest.mark.parametrize("network,n", CASES)
def test_precompile_and_the_first_batch_leave_nothing_to_trace(network, n):
    model = _fresh(network)
    assert model.precompile(n, threads=2) > 0
    fsim_jax.reset_xla_trace_log()
    for _ in _batches(model, n, count=2):
        assert fsim_jax.xla_trace_log() == {}


def test_the_resident_maps_outlive_many_donated_chunk_chains():
    model = _fresh("resnet18")
    for _ in _batches(model, 2, count=8):
        pass
    checked = 0
    for tr in _traces(model):
        [resident] = tr.__dict__["_resident_chunks"].values()
        for (spec, host), (rspec, dev) in zip(fsim_jax._spec_chunks(tr),
                                              resident):
            assert spec == rspec and len(host) == len(dev)
            for a, r in zip(host, dev):
                assert isinstance(r, jax.Array) and not r.is_deleted()
                np.testing.assert_array_equal(np.asarray(r), a)
                checked += 1
    assert checked > 0


def test_the_batch1_run_path_reuses_the_resident_maps():
    """``run``, the DSE's verification path, puts the maps on its first
    call of a program and reuses them on the next."""
    model = _fresh("mobilenet")
    seg = model.segments[0]
    dram = {t: model.random_images(1, seed=3)[0].reshape(model.shapes[t])
            if t == model.input_name else np.zeros(model.shapes[t], np.int8)
            for t in model._activations(seg)}
    dram.update({t: w.copy() for t, w in model._weights_of(seg).items()})
    want = {k: v.copy() for k, v in dram.items()}
    get_backend("numpy").run(seg.program, model.hw, want)
    be = fsim_jax.JaxBackend()
    fsim_jax.reset_kernel_launch_log()
    for put in (True, False):
        got = {k: v.copy() for k, v in dram.items()}
        before = fsim_jax.upload_bytes_by_kind()["index_maps"]
        be.run(seg.program, model.hw, got)
        assert (fsim_jax.upload_bytes_by_kind()["index_maps"] > before) == put
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
    log = fsim_jax.index_map_residency_log()
    assert log["uploaded_dispatches"] == log["resident_dispatches"] > 0


def test_threads_sharing_a_model_keep_one_copy_and_exact_counts():
    """More threads than cores serve one fresh model at once, switching
    often: every output stays exact, each trace keeps one copy, and the
    counters add up (a race may put a copy twice, and counts it)."""
    model = _fresh("mobilenet")
    images = model.random_images(1, seed=9)
    want = model.run_batch(images, backend="numpy")
    fsim_jax.reset_kernel_launch_log()
    n_threads = len(os.sched_getaffinity(0)) + 2
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(max_workers=n_threads) as pool:
            outs = [f.result(timeout=120) for f in
                    [pool.submit(model.run_batch, images, "jax")
                     for _ in range(n_threads)]]
    finally:
        sys.setswitchinterval(interval)
    for out in outs:
        np.testing.assert_array_equal(out, want)
    for tr in _traces(model):
        assert len(tr.__dict__["_resident_chunks"]) == 1
    log = fsim_jax.index_map_residency_log()
    assert log["resident_dispatches"] + log["uploaded_dispatches"] == \
        fsim_jax.kernel_launch_log()
    put = fsim_jax.upload_bytes_by_kind()["index_maps"]
    assert log["resident_bytes"] == {str(jax.devices()[0]): put}
    assert put >= _map_bytes(model) and log["uploaded_dispatches"] > 0


TWO_DEVICES = """
import json, numpy as np, jax
import chip_smoke
from repro.serve.model import served_model
from repro.vta import fsim_jax
m = served_model('resnet18', 'tiny')
images = m.random_images(2, seed=4)
want = m.run_batch(images, backend='numpy')
fsim_jax.reset_kernel_launch_log()
exact = {}
for d in jax.local_devices():
    with jax.default_device(d):
        exact[str(d)] = [bool(np.array_equal(
            m.run_batch(images, backend='jax'), want)) for _ in range(2)]
log = fsim_jax.index_map_residency_log()
placed = []
for seg in m.segments:
    for tr in seg.program.__dict__['_lowered'].values():
        for key, chunks in tr.__dict__.get('_resident_chunks', {}).items():
            devs = {str(d) for _, args in chunks for a in args
                    for d in a.devices()}
            placed.append([key, sorted(devs)])
pool = chip_smoke.run_scaleout(full=('resnet18', 'tiny'), n_workers=2,
                               n_burst=4, bucket=2, log=lambda s: None)
print(json.dumps({'exact': exact, 'log': log, 'placed': placed,
                  'checked': pool[2]['checked'],
                  'devices': sorted(pool[2]['launches_by_device'])}))
"""


def test_each_device_gets_its_own_resident_copy():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", TWO_DEVICES], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    devices = sorted(res["exact"])
    assert len(devices) == 2
    assert all(all(v) for v in res["exact"].values())
    per_device = res["log"]["resident_bytes"]
    assert sorted(per_device) == devices
    assert len(set(per_device.values())) == 1 and per_device[devices[0]] > 0
    assert res["log"]["uploaded_dispatches"] == \
        res["log"]["resident_dispatches"]
    # every copy lives wholly on the device it is keyed by
    assert sorted({k for k, _ in res["placed"]}) == devices
    assert all(devs == [k] for k, devs in res["placed"])
    assert res["checked"] == 4 and res["devices"] == devices
