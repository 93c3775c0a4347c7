"""The benchmark's harness: finds a cell's files by name, builds the system
under test, drives one measured window and decides ``correct``.

Everything one configuration, traffic mix or metric needs lives in a file of
its own, found by the name ``BENCHMARK.json`` gives it:

* ``<file>`` of the configuration entry: the network's layers with their
  shapes, post-ops and weight ranges, and how the program serves it;
* ``chipbench/traffic/<traffic>.json``: a mix's parameters, read by the
  generator it names;
* ``chipbench/generators/<generator>.py``: one kind of traffic;
  ``make_engine(models, executor, traffic)`` builds the engine the window
  drives and ``drive(...)`` drives it (see ``generators/closed.py``);
* ``chipbench/metrics/<metric>.py``: ``read(record)`` returns the metric's
  value, or None where the run has nothing to read.

The program is imported only here (``build_model``, ``serve``), and only
its served path, its counters and its kernel names are read.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from chipbench import reference

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPAN_WINDOW = "chipbench.window"
SPAN_BATCH = "chipbench.batch"
SPAN_SEGMENT = "chipbench.segment"
IMAGE_POOL = 64          # distinct images a run draws from its seed
TRACE_S = 10.0           # the traced run profiles the window's first seconds
BIAS_RANGE = 100         # int32 biases are uniform in [-100, 100]


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start / os.sysconf("SC_CLK_TCK")


# ---------------------------------------------------------------------------
# finding a cell's files
# ---------------------------------------------------------------------------
def load_spec(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell_of(spec: dict, workload: str) -> tuple:
    """(workload entry, configuration entry) of ``workload``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def load_config(root: Path, entry: dict) -> dict:
    return json.loads((Path(root) / entry["file"]).read_text())


def load_traffic(bench: Path, name: str) -> dict:
    return json.loads((Path(bench) / "traffic" / f"{name}.json").read_text())


def _load_module(bench: Path, kind: str, name: str):
    path = Path(bench) / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_generator(bench: Path, name: str):
    """The module ``chipbench/generators/<name>.py``."""
    return _load_module(bench, "generators", name)


def load_reader(bench: Path, name: str):
    """``read`` of ``chipbench/metrics/<name>.py``."""
    return _load_module(bench, "metrics", name).read


def traced_requests(run: dict) -> list:
    """The requests completed inside the traced sub-window."""
    a, b = run["traced"]
    return [r for r in run["requests"]
            if r["done"] is not None and a <= r["done"] <= b]


def host_times() -> tuple:
    """(this process's CPU seconds, steal seconds summed over the machine's
    cores): steal is time the hypervisor gave those cores to other guests,
    which slows a window whose rate the host's work sets."""
    with open("/proc/stat") as f:
        steal = int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    t = os.times()
    return t.user + t.system, steal


def metrics_of(spec: dict, workload: str, trace: bool) -> list:
    """The metric entries a run of ``workload`` reports: end-to-end ones
    with ``trace`` off, per-layer ones with it on."""
    entries = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in entries if workload in m.get("workloads", [workload])]


# ---------------------------------------------------------------------------
# inputs and weights, made on the device from the seed
# ---------------------------------------------------------------------------
def _key(seed: int, stream: int):
    import jax
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.random.fold_in(key, stream)


def weight_specs(config: dict) -> list:
    """(tensor name, shape, low, high, dtype) of every weight and bias, in
    the program's DRAM naming (``<layer>.wgt``, ``<layer>.bias``)."""
    chans = {config["input_name"]: config["input_shape"][0]}
    out = []
    for layer in config["layers"]:
        chans[layer["name"]] = layer["shape"][0]
        kind, k = layer["kind"], layer.get("k", 1)
        if kind not in ("conv", "dense", "depthwise"):
            continue
        lo, hi = layer["weights"]
        fo, fi = layer["shape"][0], chans[layer["inputs"][0]]
        shape = (fo, k, k) if kind == "depthwise" else (fo, fi, k, k)
        out.append((f"{layer['name']}.wgt", shape, lo, hi, "int8"))
        if layer.get("bias"):
            out.append((f"{layer['name']}.bias", (fo,), -BIAS_RANGE,
                        BIAS_RANGE, "int32"))
    return out


def _uniform_ints(key, sizes: list, lows: list, highs: list) -> np.ndarray:
    """One flat int32 vector of ``sum(sizes)`` integers, segment ``i``
    uniform in ``[lows[i], highs[i]]``, made in one jitted call on the
    default device (one small program, whatever the number of segments)."""
    import jax
    import jax.numpy as jnp
    total = int(sum(sizes))
    rep = np.asarray(sizes)

    @jax.jit
    def gen(k):
        lo = jnp.repeat(jnp.asarray(lows, jnp.int32), rep,
                        total_repeat_length=total)
        span = jnp.repeat(jnp.asarray(np.subtract(highs, lows) + 1,
                                      jnp.uint32), rep,
                          total_repeat_length=total)
        bits = jax.random.bits(k, (total,), jnp.uint32)
        return lo + (bits % span).astype(jnp.int32)
    return np.asarray(gen(key))


def make_weights(config: dict, seed: int) -> dict:
    """Every weight and bias of the network, uniform integers in each
    layer's range, as the host int8/int32 arrays the program serves from."""
    specs = weight_specs(config)
    sizes = [int(np.prod(shape)) for _, shape, *_ in specs]
    flat = _uniform_ints(_key(seed, 0), sizes, [lo for *_, lo, _, _ in specs],
                         [hi for *_, hi, _ in specs])
    out, at = {}, 0
    for (name, shape, _, _, dt), n in zip(specs, sizes):
        out[name] = flat[at:at + n].reshape(shape).astype(dt)
        at += n
    return out


def make_images(config: dict, seed: int, n: int = IMAGE_POOL) -> np.ndarray:
    """(n, 1, C, H, W) int8 images, uniform in the configuration's input
    range."""
    shape = (n, 1) + tuple(config["input_shape"])
    lo, hi = config["input_range"]
    return _uniform_ints(_key(seed, 1), [int(np.prod(shape))], [lo],
                         [hi]).reshape(shape).astype(np.int8)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
def layers_from_graph(graph) -> list:
    """The program's graph in the configuration file's layer format."""
    out = []
    for node in graph.topo():
        if node.kind == "input":
            continue
        layer = {"name": node.name, "kind": node.kind,
                 "inputs": list(node.inputs), "shape": list(node.shape[1:])}
        if node.kind not in ("add",):
            wl = node.layer.wl
            layer.update(k=wl.kh, stride=wl.sh, pad=wl.ph)
        if node.kind not in ("maxpool", "avgpool"):
            layer["post_op"] = node.layer.post_op
        if node.kind in ("conv", "dense"):
            layer["bias"] = bool(node.layer.bias)
        out.append(layer)
    return out


def _without_weights(layers: list) -> list:
    return [{k: v for k, v in layer.items() if k != "weights"}
            for layer in layers]


def build_model(config: dict, weights: dict):
    """Compile the configuration's network with the program's graph
    compiler, check that the graph it serves is the one the configuration
    file describes, and give it the benchmark's weights."""
    from repro.serve.model import SERVE_GRAPHS, ServedModel, device_graph
    from repro.vta.isa import DEFAULT_VTA
    from repro.vta.workloads import network_graph
    net, scale = config["served"]["network"], config["served"]["scale"]
    graph = (device_graph(network_graph(net)) if scale == "full"
             else SERVE_GRAPHS[net](scale))
    model = ServedModel.compile(config["name"], graph, DEFAULT_VTA)
    if (layers_from_graph(model.graph) != _without_weights(config["layers"])
            or model.input_name != config["input_name"]
            or list(model.image_shape[1:]) != list(config["input_shape"])):
        raise ValueError(f"{config['name']}: the program's graph differs "
                         f"from the configuration file")
    want = {k: (v.shape, v.dtype) for k, v in model.weights.items()}
    got = {k: (v.shape, v.dtype) for k, v in weights.items()}
    if want != got:
        raise ValueError(f"{config['name']}: weights differ in name, shape "
                         f"or type: program {want}, benchmark {got}")
    model.weights = dict(weights)
    return model


class SpanBackend:
    """A backend passed by instance: each segment's ``run_batched`` runs in
    a profiler span named after the tensors the segment writes."""

    def __init__(self, inner, labels: dict):
        self.inner = inner
        self.labels = labels
        self.name = inner.name

    def run(self, prog, hw, dram):
        return self.inner.run(prog, hw, dram)

    def run_batched(self, prog, hw, *, shared, batched):
        from jax.profiler import TraceAnnotation
        label = self.labels.get(id(prog), "?")
        with TraceAnnotation(f"{SPAN_SEGMENT}:{label}"):
            return self.inner.run_batched(prog, hw, shared=shared,
                                          batched=batched)


def span_executor(inner):
    """The engine's executor with each batch in a profiler span."""
    from jax.profiler import TraceAnnotation

    def call(model_key, images, bucket):
        with TraceAnnotation(f"{SPAN_BATCH}:b{bucket}"):
            return inner(model_key, images, bucket)
    return call


def warm_executor(model, images: np.ndarray, buckets: tuple, *,
                  backend="jax", spans: bool = False, log=print):
    """The engine's executor for ``model``, every program of ``buckets``
    compiled (or read from the persistent cache) and one whole batch of
    each bucket run through it. With ``spans`` each batch and each
    segment's ``run_batched`` runs in a profiler span."""
    from repro.serve.engine import BackendExecutor
    from repro.vta.backend import get_backend
    be = get_backend(backend)
    if spans:
        be = SpanBackend(be, {id(s.program): "+".join(s.writes)
                              for s in model.segments})
    executor = BackendExecutor({model.name: model}, backend=be)
    if spans:
        executor = span_executor(executor)
    for b in buckets:
        n = model.precompile(b, getattr(be, "inner", be))
        executor(model.name, list(images[:b]), b)
        log(f"process age {process_age_s():.3f} s: bucket {b}: {n} "
            f"programs compiled or read, one warm-up batch")
    return executor


def serve_window(model, executor, images: np.ndarray, traffic: dict,
                 generator, *, seed: int, seconds: float,
                 trace_dir=None) -> dict:
    """Drive one window of ``traffic`` with ``generator`` through the
    engine's ``submit`` and its serve loop over a warmed ``executor``;
    returns the run record. With ``trace_dir`` the profiler traces the
    window's first ``TRACE_S`` seconds, up to the generator's first tick
    after them, inside a ``chipbench.window`` span; ``run["traced"]`` is
    that sub-window on the engine's clock."""
    import jax

    from repro.vta import fsim_jax

    key = model.name
    engine = generator.make_engine({key: model}, executor, traffic)
    gc.collect()
    gc.freeze()
    fsim_jax.reset_xla_trace_log()
    fsim_jax.reset_kernel_launch_log()
    metrics = engine.reset_metrics()
    mark = {}

    def on_open():
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir))
            # made once tracing is on: a span begun before it is not kept
            mark["span"] = jax.profiler.TraceAnnotation(SPAN_WINDOW)
            mark["span"].__enter__()
            mark["traced"] = [engine.clock.now(), None]
        mark["setup_s"] = process_age_s()
        mark["host"] = host_times()

    def close_trace():
        if trace_dir is not None and mark["traced"][1] is None:
            mark["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            mark["traced"][1] = engine.clock.now()

    def on_tick(now):
        if trace_dir is not None and now - mark["traced"][0] >= TRACE_S:
            close_trace()

    def on_close():
        close_trace()
        mark["host"] = np.subtract(host_times(), mark["host"])

    engine.start()
    try:
        run = generator.drive(engine, key, images, traffic, seconds, seed,
                              on_open=on_open, on_close=on_close,
                              on_tick=on_tick)
    finally:
        engine.close()
        gc.unfreeze()
    devices = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devices]
    run.update(
        setup_s=mark["setup_s"], new_traces=sum(fsim_jax.xla_trace_log().values()),
        launches=fsim_jax.kernel_launch_log(),
        upload_bytes=fsim_jax.upload_bytes_log(), batches=metrics.batches,
        host_cpu_s=float(mark["host"][0]), host_steal_s=float(mark["host"][1]),
        memory_peak_bytes=max(s.get("peak_bytes_in_use", 0) for s in stats))
    if trace_dir is not None:
        run["traced"] = tuple(mark["traced"])
    return run


# ---------------------------------------------------------------------------
# correctness: the served outputs against the plain reference
# ---------------------------------------------------------------------------
def check_outputs(config: dict, weights: dict, images: np.ndarray,
                  served: list, seed: int) -> dict:
    """Compare whole batches of ``served`` ``(image index, output, batch)``
    triples with the reference's outputs: batches drawn from the seed until
    ``check_sample`` outputs or all are taken, so every slot of a batch is
    read in every run. Returns the outputs checked, the widest gap and the
    number of output values that differ."""
    batches = {}
    for idx, out, b in served:
        batches.setdefault(b, []).append((idx, out))
    order = np.random.default_rng([seed, 2]).permutation(sorted(batches))
    checked, diff, bad = 0, 0, 0
    for b in order:                     # a batch a block bounds the memory
        if checked >= int(config["check_sample"]):
            break
        part = batches[b]
        x = np.stack([images[idx][0] for idx, _ in part])
        ref = reference.forward(config, weights, x)
        out = np.stack([np.asarray(o).reshape(ref.shape[1:])
                        for _, o in part])
        d = np.abs(out.astype(np.int64) - ref.astype(np.int64))
        diff, bad = max(diff, int(d.max())), bad + int((d > 0).sum())
        checked += len(part)
    return {"checked": checked, "max_abs_diff": diff, "mismatched": bad}


def judge(config: dict, weights: dict, images: np.ndarray, run: dict,
          seed: int) -> tuple:
    """(correct, attempted, failed, checks): each compared number with its
    limit, all of them 0. An output that differs from the reference, fewer
    outputs checked than ``check_sample``, a request that failed or never
    finished, and a compile inside the window each fail the run."""
    counted = run["counted"]
    missing = [r for r in run["requests"] if r["done"] is None
               and r["submit"] <= run["t_close"]]
    attempted = len({id(r) for r in counted + missing})
    served = [(r["image"], r["output"], r["done"]) for r in run["requests"]
              if r["done"] is not None]
    cmp = (check_outputs(config, weights, images, served, seed) if served
           else {"checked": 0, "max_abs_diff": 0, "mismatched": 0})
    values = {"max_abs_diff": cmp["max_abs_diff"],
              "mismatched_values": cmp["mismatched"],
              "unchecked_outputs": max(0, int(config["check_sample"])
                                   - cmp["checked"]),
              "failed_requests": len(missing),
              "compiles_in_window": run["new_traces"]}
    checks = {k: {"value": v, "limit": 0} for k, v in values.items()}
    correct = all(0 <= v <= 0 for v in values.values())
    return correct, attempted, len(missing), checks


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run_cell(root: Path, workload: str, *, seed: int, seconds: float,
             trace: bool, device, peaks: dict, backend="jax",
             log=print) -> dict:
    """One run of ``workload`` from the benchmark under ``root``: set up,
    window, reference check; returns the result line as a dict. ``device``
    has ``platform``, ``device_kind`` and ``count``; ``backend`` is the
    execution backend the served path runs on."""
    bench = Path(root) / "chipbench"
    spec = load_spec(root)
    cell, entry = cell_of(spec, workload)
    config = load_config(root, entry)
    traffic = load_traffic(bench, cell["traffic"])
    generator = load_generator(bench, traffic["generator"])
    wanted = metrics_of(spec, workload, trace)
    readers = {m["name"]: load_reader(bench, m["name"]) for m in wanted}
    log(f"process age {process_age_s():.3f} s: devices found")
    weights = make_weights(config, seed)
    images = make_images(config, seed)
    log(f"process age {process_age_s():.3f} s: weights and images made")
    model = build_model(config, weights)
    log(f"process age {process_age_s():.3f} s: {config['name']}: "
        f"{len(model.segments)} segments built")
    trace_dir = (Path(tempfile.mkdtemp(prefix="chipbench-trace-"))
                 if trace else None)
    try:
        executor = warm_executor(model, images, tuple(traffic["buckets"]),
                                 backend=backend, spans=trace, log=log)
        run = serve_window(model, executor, images, traffic, generator,
                           seed=seed, seconds=seconds, trace_dir=trace_dir)
        if trace_dir is not None:
            from chipbench import trace_reduce
            run["trace"] = trace_reduce.reduce(
                *trace_reduce.load_xplane(trace_dir))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    del model
    run.update(config=config, traffic=traffic, peaks=peaks,
               window_s=run["t_close"] - run["t0"])
    log(f"window: {run['window_s']:.3f} s, {len(run['requests'])} requests "
        f"sent, {run['batches']} batches; process CPU "
        f"{run['host_cpu_s']:.3f} s, host steal {run['host_steal_s']:.3f} s, "
        f"load average {os.getloadavg()[0]:.2f}")
    done = sorted({r["done"] for r in run["requests"] if r["done"] is not None})
    if len(done) > 2:
        gaps = np.diff(done) * 1e3
        log(f"ms between completions: min {gaps.min():.1f}, median "
            f"{np.median(gaps):.1f}, max {gaps.max():.1f}")

    t = time.perf_counter()
    correct, attempted, failed, checks = judge(
        config, weights, images, run, seed)
    log(f"reference check: {time.perf_counter() - t:.3f} s")
    metrics = {}
    for m in wanted:
        value = readers[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": device.count,
           "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if trace:
        dev.update(busy_s=run["trace"]["busy_s"],
                   window_s=run["trace"]["window_s"])
        result["breakdown"] = run["trace"]["breakdown"]
    result["checks"] = checks
    return result
