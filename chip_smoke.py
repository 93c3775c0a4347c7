"""Bring-up smoke of the served VTA path on TPU chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-worker scale-out, four chips

One chip: 16 full-width ResNet-18 requests (the body after the CPU stem, so
each request is the stem's (1, 64, 112, 112) int8 output) through
``VTAServeEngine`` with one bucket of 8, after one warm-up batch; then the
resnet18-small + mobilenet-small two-tenant mix. Four chips: only the
``WorkerPool`` path — four thread workers, one per device, each owning one
replica key of the full-width model — against the same burst on one worker.

Every phase checks its results and the run fails (exit 1) on the first
miss: a kernel impl ending in ``_interpret``; a failed ticket; a non-zero
retry, bisection, timeout, loop-error or fallback count; an output that
differs from ``ServedModel.run_single`` on the numpy reference; an XLA
trace inside the timed burst; or, on four chips, a device that ran no
dispatch. Without a TPU it exits 2 and prints no result. Only then does the
last line of stdout read ``{"ok": true, "device": {...}}``.

``run_smoke`` and ``run_scaleout`` take the model scales as arguments, so
the tests run them at a tiny scale on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

RELIABILITY = ("retries", "bisections", "timeouts", "loop_errors")


class SmokeFailure(AssertionError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _serve(models: dict, requests: list, *, bucket: int, workers=None):
    """Serve ``[(tenant, model key, image)]`` through ``VTAServeEngine`` on
    the jax backend's degradation ladder — one executor, or a
    ``WorkerPool`` of ``workers`` thread workers, one per device; returns
    (outputs in request order, ``ServeMetrics``, wall seconds). Fails on
    any ticket that did not complete and on any reliability event."""
    from repro.serve.breaker import DegradingBackendExecutor
    from repro.serve.engine import VTAServeEngine
    from repro.serve.metrics import ServeMetrics
    from repro.serve.workers import WorkerPool

    metrics = ServeMetrics()
    executor = pool = None
    if workers is None:
        executor = DegradingBackendExecutor(models, metrics=metrics)
    else:
        pool = WorkerPool(models, workers, backend="jax", transport="thread",
                          metrics=metrics)
    engine = VTAServeEngine(models, buckets=(bucket,), executor=executor,
                            metrics=metrics, workers=pool,
                            queue_capacity=max(64, len(requests)))
    try:
        for tenant in sorted({t for t, _, _ in requests}):
            engine.add_tenant(tenant)
        t0 = time.perf_counter()
        tickets = [engine.submit(t, m, img) for t, m, img in requests]
        engine.drain()
        wall = time.perf_counter() - t0
    finally:
        engine.close()
    failed = [t.request.error for t in tickets if not t.ok]
    _check(not failed, f"{len(failed)} tickets failed: {failed[:2]}")
    rel = metrics.snapshot()["reliability"]
    events = {k: rel[k] for k in RELIABILITY if rel[k]}
    if rel["fallbacks"]:
        events["fallbacks"] = rel["fallbacks"]
    _check(not events, f"reliability events: {events}")
    return [t.result() for t in tickets], metrics, wall


def _references(model, images):
    """Start ``run_single`` on the numpy reference for ``images`` in a
    background thread, where it overlaps compilation; returns the thread
    pool and the future of the outputs."""
    pool = ThreadPoolExecutor(max_workers=1)
    return pool, pool.submit(lambda: [model.run_single(x) for x in images])


def _compare(outs, refs, what: str) -> int:
    import numpy as np
    bad = [i for i, (o, r) in enumerate(zip(outs, refs))
           if not np.array_equal(o, r)]
    _check(not bad, f"{what}: outputs {bad} differ from numpy")
    return len(refs)


def _impls_check(log) -> None:
    from repro.vta.backend import DEGRADATION_LADDER, backend_kernel_impls
    impls = dict(backend_kernel_impls(DEGRADATION_LADDER[0]))
    _check(not any(i.endswith("_interpret") for i in impls.values()),
           f"interpret-mode kernel impl on the served path: {impls}")
    log(f"kernel impls: {impls}; ladder {list(DEGRADATION_LADDER)}")


def run_smoke(*, full=("resnet18", "full"),
              mix=(("alice", "resnet18", "small"),
                   ("bob", "mobilenet", "small")),
              n_burst: int = 16, n_check: int = 4, n_mix: int = 8,
              bucket: int = 8, log=print) -> dict:
    """The one-chip smoke; returns the numbers it printed."""
    from repro.serve.model import served_model
    from repro.vta import fsim_jax

    _impls_check(log)
    big = served_model(*full)
    key = big.name
    images = big.random_images(bucket + n_burst, seed=11)
    warm, burst = images[:bucket], images[bucket:]
    small = {t: served_model(m, s) for t, m, s in mix}
    mix_reqs = [(t, small[t].name, img) for t in sorted(small)
                for img in small[t].random_images(n_mix, seed=12)]
    refpool, refs = _references(big, burst[:n_check])
    try:
        t0 = time.perf_counter()
        programs = big.precompile(bucket)
        compile_s = time.perf_counter() - t0
        log(f"{key}: compile {compile_s:.3f} s for {programs} programs "
            f"({len(big.segments)} segments, batch {bucket})")
        _, m, _ = _serve({key: big}, [("warm", key, x) for x in warm],
                         bucket=bucket)
        log(f"{key}: warm-up batch {m.batch_exec_s.mean:.3f} s")

        fsim_jax.reset_xla_trace_log()
        fsim_jax.reset_kernel_launch_log()
        outs, m, wall = _serve({key: big}, [("burst", key, x) for x in burst],
                               bucket=bucket)
        traces = sum(fsim_jax.xla_trace_log().values())
        batches = m.batches
        res = {"compile_s": compile_s, "burst_s": wall,
               "batch_s": m.batch_exec_s.mean, "batches": batches,
               "images_per_s": len(burst) / wall, "new_traces": traces,
               "launches_per_batch": fsim_jax.kernel_launch_log() / batches,
               "upload_bytes_per_batch":
                   fsim_jax.upload_bytes_log() / batches}
        log(f"{key}: burst of {len(burst)} in {batches} batches, "
            f"{wall:.3f} s ({res['images_per_s']:.3f} images/s); "
            f"steady batch {res['batch_s']:.3f} s; "
            f"{res['launches_per_batch']:.0f} launches and "
            f"{res['upload_bytes_per_batch'] / 1e6:.1f} MB uploaded per "
            f"batch; {traces} new XLA traces")
        _check(traces == 0, f"{traces} XLA traces inside the timed burst")

        t0 = time.perf_counter()
        progs = sum(mdl.precompile(bucket) for mdl in small.values())
        mix_outs, m, wall = _serve({mdl.name: mdl for mdl in small.values()},
                                   mix_reqs, bucket=bucket)
        res["mix_s"] = time.perf_counter() - t0
        mix_refs = [small[t].run_single(img) for t, _, img in mix_reqs]
        res["mix_checked"] = _compare(mix_outs, mix_refs, "mix")
        log(f"mix {sorted(mdl.name for mdl in small.values())}: "
            f"{len(mix_reqs)} requests in {m.batches} batches, "
            f"{progs} programs compiled, {res['mix_s']:.3f} s with "
            f"compilation; {res['mix_checked']} outputs bit-exact vs numpy")
        res["checked"] = _compare(outs, refs.result(), key)
        log(f"{key}: {res['checked']} of {len(burst)} burst outputs "
            f"bit-exact vs numpy")
    finally:
        refpool.shutdown(wait=True)
    log("reliability: 0 failed tickets, retries, bisections, timeouts, "
        "loop errors and fallbacks")
    return res


def run_scaleout(*, full=("resnet18", "full"), n_workers: int = 4,
                 n_burst: int = 16, bucket: int = 8, log=print) -> dict:
    """The four-chip path: a ``WorkerPool`` of ``n_workers`` thread
    workers, each dispatching to its own device and owning one replica key
    of the model, against the same burst on one worker. Every output of
    both is compared with numpy."""
    import jax

    from repro.serve.model import served_model
    from repro.vta import fsim_jax

    _impls_check(log)
    devices = jax.local_devices()
    _check(len(devices) >= n_workers,
           f"{n_workers} workers need {n_workers} devices, "
           f"found {len(devices)}")
    big = served_model(*full)
    replicas = [f"{big.name}/r{i}" for i in range(n_workers)]
    models = {r: big for r in replicas}
    images = big.random_images(bucket + n_burst, seed=11)
    warm, burst = images[:bucket], images[bucket:]
    refpool, refs = _references(big, burst)
    try:
        t0 = time.perf_counter()
        threads = max(1, len(os.sched_getaffinity(0)) // n_workers)

        def compile_on(d):
            with jax.default_device(d):
                return big.precompile(bucket, threads=threads)
        with ThreadPoolExecutor(max_workers=n_workers) as pool:
            programs = list(pool.map(compile_on, devices[:n_workers]))
        compile_s = time.perf_counter() - t0
        log(f"{big.name}: compile {compile_s:.3f} s for {programs} programs "
            f"on {n_workers} devices")
        res = {"compile_s": compile_s}
        for n in (1, n_workers):
            keys = replicas[:n]
            _serve(models, [(k, k, x) for k in keys for x in warm],
                   bucket=bucket, workers=n)
            fsim_jax.reset_xla_trace_log()
            fsim_jax.reset_kernel_launch_log()
            # a tenant per replica: a batch takes the same-model heads of
            # each tenant's queue, so one queue of interleaved replicas
            # would dispatch every request alone
            reqs = [(k, k, x) for k, x in zip(keys * len(burst), burst)]
            outs, m, wall = _serve(models, reqs, bucket=bucket, workers=n)
            traces = sum(fsim_jax.xla_trace_log().values())
            by_dev = fsim_jax.kernel_launches_by_device()
            log(f"{n} worker(s): burst of {len(burst)} in {m.batches} "
                f"batches, {wall:.3f} s ({len(burst) / wall:.3f} images/s); "
                f"launches by device {by_dev}; {traces} new XLA traces")
            _check(traces == 0, f"{traces} XLA traces inside the timed burst")
            used = [str(d) for d in devices[:n] if by_dev.get(str(d))]
            _check(len(used) == n, f"only {used} of {n} devices ran "
                                   f"dispatches")
            res[n] = {"burst_s": wall, "images_per_s": len(burst) / wall,
                      "launches_by_device": by_dev,
                      "checked": _compare(outs, refs.result(),
                                          f"{n} worker(s)")}
        log(f"all {n_burst} outputs of 1 and {n_workers} workers bit-exact "
            f"vs numpy; every device ran dispatches")
    finally:
        refpool.shutdown(wait=True)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-worker scale-out path")
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {dev.platform!r} devices "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x {len(devices)} "
          f"(platform {dev.platform})", flush=True)
    log = lambda s: print(s, flush=True)          # noqa: E731
    try:
        if args.chips == 4:
            run_scaleout(log=log)
        else:
            run_smoke(log=log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
