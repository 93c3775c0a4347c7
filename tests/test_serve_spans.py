"""The served path's profiler spans, op scopes and upload counters on the
CPU: one profiled batch of the tiny ResNet through the engine nests
``vta.batch`` > ``vta.segment`` > ``vta.upload``/``vta.launch``, with one
``vta.fetch`` in the batch after its last segment, and shares its batch
number with ``serve.resolve``; the upload counter splits by kind and puts
only the images, first-seen weights and first-seen index maps; the scopes
name the compiled ops and change no program."""
import glob
import re

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.serve.engine import VTAServeEngine
from repro.serve.model import served_model
from repro.vta import fsim_jax
from repro.vta.lowering import lower_cached

PHASES = ("vta.upload", "vta.launch", "vta.fetch")
SEGMENT_PHASES = PHASES[:2]


def _spans(trace_dir) -> list:
    """(name, start, end, {stat: value}) of the program's host spans, in
    start order."""
    [path] = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                       for e in line.events
                       if e.name.startswith(("vta.", "serve.")))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One batch of 2 of the tiny ResNet-18 served through the engine under
    the profiler, after a warm-up batch outside it."""
    model = served_model("resnet18", "tiny")
    engine = VTAServeEngine({"r": model}, buckets=(2,))
    images = model.random_images(2, seed=5)

    def serve_one_batch():
        tickets = [engine.submit("t", "r", img) for img in images]
        engine.drain()
        assert all(t.ok for t in tickets)
    serve_one_batch()
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        serve_one_batch()
    finally:
        jax.profiler.stop_trace()
    return model, _spans(trace_dir)


def test_a_batch_nests_segments_and_their_phases(profiled):
    model, spans = profiled
    [batch] = [s for s in spans if s[0] == "vta.batch"]
    assert batch[3]["model"] == "resnet18-tiny" and batch[3]["bucket"] == 2
    segs = [s for s in spans if s[0] == "vta.segment"]
    assert [s[3]["segment"] for s in segs] == \
        [seg.label for seg in model.segments] == \
        ["+".join(seg.writes) for seg in model.segments]
    for seg in segs:
        assert _inside(seg, batch)
        phases = [s for s in spans if s[0] in PHASES and _inside(s, seg)]
        assert [s[0] for s in phases] == list(SEGMENT_PHASES)
        assert phases[1][3]["chunks"] >= 1
    assert len([s for s in spans if s[0] in SEGMENT_PHASES]) == 2 * len(segs)
    # one fetch per batch: the output, once the last segment is launched
    [fetch] = [s for s in spans if s[0] == "vta.fetch"]
    assert _inside(fetch, batch) and fetch[1] >= segs[-1][2]


def test_segment_spans_carry_their_layer_kinds(profiled):
    model, spans = profiled
    segs = [s for s in spans if s[0] == "vta.segment"]
    assert [s[3]["kinds"] for s in segs] == \
        [seg.kinds for seg in model.segments] == \
        ["conv", "conv+add", "conv", "conv+add"]
    assert served_model("mobilenet", "tiny").segments[0].kinds == \
        "depthwise+conv"


def test_the_engine_spans_carry_the_batch_number(profiled):
    _, spans = profiled
    [batch] = [s for s in spans if s[0] == "vta.batch"]
    [resolve] = [s for s in spans if s[0] == "serve.resolve"]
    assert resolve[3]["batch"] == batch[3]["batch"]
    assert resolve[1] >= batch[2]
    plans = [s for s in spans if s[0] == "serve.plan"]
    assert any(p[2] <= batch[1] for p in plans)
    assert not any(_inside(p, batch) for p in plans)


def test_batch_numbers_count_up_per_model():
    from repro.serve.model import take_batch
    model = served_model("mobilenet", "tiny")
    images = model.random_images(1, seed=1)
    model.run_batch(images, backend="numpy")
    first = take_batch()
    assert take_batch() is None                      # cleared by the call
    model.run_batch(images, backend="numpy")
    assert take_batch() == first + 1


def _chunk_arg_bytes(model, n: int) -> int:
    total = 0
    for seg in model.segments:
        shapes = {t: model.shapes[t] for t in model._activations(seg)}
        shapes.update({t: w.shape for t, w in model._weights_of(seg).items()})
        trace = lower_cached(seg.program, model.hw, shapes)
        chunks = fsim_jax._spec_chunks(trace)
        total += sum(np.asarray(a).nbytes for _, args in chunks for a in args)
    return total


# (network, batch) -> programs precompile builds, launches and bytes a
# model's first batch uploads: programs and launches as read before spans,
# scopes and the split by kind; the bytes are the index maps, the weights
# and the images, since the batch's other tensors stay on the device. A
# load read as a slice puts its block starts, not its index map and mask,
# and a GEMM read as windows its three starts, not its index vectors
BEFORE = {("resnet18", 1): (2, 4, 29232), ("resnet18", 8): (2, 4, 36400),
          ("mobilenet", 1): (1, 2, 33976), ("mobilenet", 8): (1, 2, 41144)}


@pytest.mark.parametrize("network,n", sorted(BEFORE))
def test_counts_and_programs_are_as_before_and_uploads_split_by_kind(
        network, n):
    # programs of its own: the batch is the model's first, which puts the
    # index maps on the device (later batches find them there)
    model = served_model.__wrapped__(network, "tiny")
    programs, launches, upload = BEFORE[network, n]
    assert model.precompile(n, threads=2) == programs
    fsim_jax.reset_kernel_launch_log()
    fsim_jax.reset_xla_trace_log()
    images = model.random_images(n, seed=3)
    model.run_batch(images, backend="jax")
    assert fsim_jax.kernel_launch_log() == launches
    assert fsim_jax.upload_bytes_log() == upload
    kinds = fsim_jax.upload_bytes_by_kind()
    assert list(kinds) == list(fsim_jax.UPLOAD_KINDS)
    assert sum(kinds.values()) == upload
    assert kinds["index_maps"] == _chunk_arg_bytes(model, n)
    assert kinds["weights"] == sum(w.nbytes for w in model.weights.values())
    assert kinds["activations"] == images.nbytes
    assert fsim_jax.xla_trace_log() == {}          # precompile built them all
    fsim_jax.reset_kernel_launch_log()
    assert fsim_jax.upload_bytes_by_kind() == dict.fromkeys(
        fsim_jax.UPLOAD_KINDS, 0)


def test_every_compiled_op_of_a_chunk_is_named_by_its_instruction_class():
    model = served_model("resnet18", "tiny")
    seg = model.segments[1]                      # conv -> add -> clip, fused
    batched = {t: np.zeros((2,) + model.shapes[t], np.int8)
               for t in model._activations(seg)}
    jobs = fsim_jax.JaxBackend().chunk_compiles(
        seg.program, model.hw, shared=model._weights_of(seg), batched=batched)
    text = "\n".join(job().as_text() for job in jobs.values())
    scopes = set(re.findall(r'op_name="[^"]*\b(vta\.\w+)', text))
    assert scopes == {"vta.load", "vta.gemm", "vta.alu", "vta.store"}
    assert scopes == set(fsim_jax.ENTRY_SCOPES.values())
