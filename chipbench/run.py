"""Run one cell of the benchmark once, on the machine it is started on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, the cell's chips: build the configuration's served model with
weights and images made from ``--seed``, warm up the traffic's buckets (set
up), drive ``--seconds`` of the traffic mix through ``VTAServeEngine``, then
compare a sample of the served outputs with the plain numpy reference. With
``--trace 0`` the last line of stdout carries the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the line
carries the per-layer metrics, the device's busy time and a breakdown. The
numbers compared for ``correct`` are the last lines of stderr and the last
key of the result.

Without an accelerator, or with fewer chips than the cell asks for, it exits
2 and prints no result. JAX's compilation cache is kept in ``.jax_cache``
at the root of the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Device:
    """What the result line says of the devices JAX found."""

    def __init__(self, devices):
        self.platform = devices[0].platform
        self.device_kind = devices[0].device_kind
        self.count = len(devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # before JAX is imported: it reads the cache directory once
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # no eviction: its bookkeeping races with the threads precompile
    # compiles on, and an entry it fails to read compiles again
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from chipbench import harness, work
    cell, _ = harness.cell_of(harness.load_spec(ROOT), args.workload)
    import jax
    device = Device(jax.devices())
    if device.platform == "cpu" or device.count < cell["chips"]:
        log(f"run: the cell needs {cell['chips']} accelerator chip(s); JAX "
            f"found {device.count} {device.platform!r} device(s) "
            f"({device.device_kind})")
        return 2
    peaks = work.peaks(device.device_kind)
    log(f"device: {device.device_kind} x {device.count} "
        f"(platform {device.platform})")
    result = harness.run_cell(ROOT, args.workload, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              device=device, peaks=peaks, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
