"""A batch's tensors stay on the device across its segments: the jax
backend returns device arrays in its flat layout, a served batch puts only
its images and fetches only its output, weights are put once per (array,
device) and never donated, and everything stays bit-exact against numpy,
also when new weight arrays are assigned or a segment comes back as host
numpy."""
import math
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax

from repro.serve.model import served_model
from repro.vta import fsim_jax
from repro.vta.backend import get_backend
from repro.vta.lowering import dispatch_shapes

NETWORKS = ("resnet18", "mobilenet")


def _fresh(network):
    """A model with programs and weights of its own, so nothing of it is
    resident yet."""
    return served_model.__wrapped__(network, "tiny")


def _exact(model, images):
    out = model.run_batch(images, backend="jax")
    np.testing.assert_array_equal(out,
                                  model.run_batch(images, backend="numpy"))
    return out


def _resident_weights(model) -> dict:
    """{weight name: the device copy the executor keeps of it}."""
    names = {id(w): k for k, w in model.weights.items()}
    return {names[key]: dev for (key, _), (ref, dev) in
            list(get_backend("jax").resident_host.items())
            if key in names and ref() is model.weights[names[key]]}


@pytest.mark.parametrize("network", NETWORKS)
def test_batches_are_exact_and_follow_reassigned_weights(network):
    model = _fresh(network)
    first = [_exact(model, model.random_images(2, seed=s)) for s in (1, 2)]
    rng = np.random.default_rng(7)
    model.weights = {k: rng.integers(-8, 8, v.shape).astype(v.dtype)
                     for k, v in model.weights.items()}
    fsim_jax.reset_kernel_launch_log()
    again = _exact(model, model.random_images(2, seed=1))
    assert not np.array_equal(again, first[0])     # the new weights count
    assert fsim_jax.upload_bytes_by_kind()["weights"] == sum(
        w.nbytes for w in model.weights.values())


@pytest.mark.parametrize("network", NETWORKS)
def test_a_later_batch_puts_only_its_images(network):
    model = _fresh(network)
    _exact(model, model.random_images(4, seed=3))
    fsim_jax.reset_kernel_launch_log()
    images = model.random_images(4, seed=4)
    model.run_batch(images, backend="jax")
    assert fsim_jax.upload_bytes_by_kind() == {
        "activations": images.nbytes, "weights": 0, "index_maps": 0}
    log = fsim_jax.tensor_residency_log()
    inputs = sum(len(model._activations(s)) + len(model._weights_of(s))
                 for s in model.segments)
    assert log == {"resident": inputs - 1, "uploaded": 1}
    fsim_jax.reset_kernel_launch_log()
    assert fsim_jax.tensor_residency_log() == {"resident": 0, "uploaded": 0}


@pytest.mark.parametrize("network", NETWORKS)
def test_the_resident_weights_are_never_donated(network):
    model = _fresh(network)
    for s in range(3):
        _exact(model, model.random_images(2, seed=s))
    resident = _resident_weights(model)
    assert sorted(resident) == sorted(model.weights)
    for name, dev in resident.items():
        assert isinstance(dev, jax.Array) and not dev.is_deleted()
        np.testing.assert_array_equal(np.asarray(dev),
                                      model.weights[name].reshape(-1))


def test_threads_sharing_a_model_keep_one_copy_of_each_weight():
    """More threads than cores serve one fresh model at once, switching
    often: every output stays exact, each weight keeps one live copy, and
    the counters add up (a race may put a weight twice, and counts it)."""
    model = _fresh("resnet18")
    images = model.random_images(2, seed=9)
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
    assert sorted(_resident_weights(model)) == sorted(model.weights)
    log = fsim_jax.tensor_residency_log()
    inputs = sum(len(model._activations(s)) + len(model._weights_of(s))
                 for s in model.segments)
    assert log["resident"] + log["uploaded"] == n_threads * inputs
    put = fsim_jax.upload_bytes_by_kind()
    assert put["activations"] == n_threads * images.nbytes
    assert put["weights"] >= sum(w.nbytes for w in model.weights.values())


class _HostMidChain:
    """A backend by instance that hands one segment's results back as host
    numpy, as a wrapper with a fault of its own does."""

    def __init__(self, at: int):
        self.inner = get_backend("jax")
        self.name = "jax"
        self.at, self.calls = at, 0

    def run_batched(self, prog, hw, *, shared, batched):
        out = self.inner.run_batched(prog, hw, shared=shared, batched=batched)
        self.calls += 1
        if self.calls - 1 == self.at:
            return {k: np.array(v) for k, v in out.items()}
        return out


@pytest.mark.parametrize("network", NETWORKS)
def test_a_segment_returning_host_numpy_mid_chain_still_runs(network):
    model = _fresh(network)
    images = model.random_images(3, seed=5)
    want = model.run_batch(images, backend="numpy")
    for at in range(len(model.segments)):
        be = _HostMidChain(at)
        np.testing.assert_array_equal(model.run_batch(images, backend=be),
                                      want)
        assert be.calls == len(model.segments)


@pytest.mark.parametrize("network", NETWORKS)
def test_precompile_covers_two_resident_batches(network):
    model = _fresh(network)
    assert model.precompile(2, threads=2) > 0
    fsim_jax.reset_xla_trace_log()
    for s in range(2):
        _exact(model, model.random_images(2, seed=10 + s))
        assert fsim_jax.xla_trace_log() == {}


def test_run_batched_returns_flat_device_arrays_and_the_put_inputs():
    model = _fresh("resnet18")
    seg = model.segments[0]
    n = 2
    images = model.random_images(n, seed=6)
    batched = {t: images if t == model.input_name
               else np.zeros((n,) + model.shapes[t], np.int8)
               for t in model._activations(seg)}
    out = get_backend("jax").run_batched(
        seg.program, model.hw, shared=model._weights_of(seg),
        batched=batched)
    assert set(out) == set(batched)           # what it stores, what it put
    for t, v in out.items():
        assert isinstance(v, jax.Array)
        assert v.shape == (n, math.prod(model.shapes[t]))
    np.testing.assert_array_equal(
        np.asarray(out[model.input_name]).reshape(images.shape), images)
    want = get_backend("numpy").run_batched(
        seg.program, model.hw, shared=model._weights_of(seg),
        batched=batched)
    for t, v in want.items():
        np.testing.assert_array_equal(np.asarray(out[t]).reshape(v.shape), v)
    # the flat results fed back show their per-image shapes again
    assert dispatch_shapes(seg.program, model._weights_of(seg), out) == {
        **{t: model.shapes[t] for t in out},
        **{t: w.shape for t, w in model._weights_of(seg).items()}}


def test_the_numpy_backend_counts_nothing_and_returns_host_arrays():
    model = _fresh("mobilenet")
    fsim_jax.reset_kernel_launch_log()
    out = model.run_batch(model.random_images(2, seed=8), backend="numpy")
    assert isinstance(out, np.ndarray)
    assert out.shape == (2,) + model.output_shape
    assert fsim_jax.upload_bytes_log() == 0
    assert fsim_jax.tensor_residency_log() == {"resident": 0, "uploaded": 0}
