"""Execution-backend benchmark: numpy fsim vs the JIT-compiled JAX backend.

Two modes:

* **Per-layer-kind breakdown** (default): representative layer programs per
  kind — conv / depthwise / pool / dense / fused-segment, with the
  depthwise rows taken from the mobilenet dw ladder — each executed on a
  calibration batch by the numpy reference and the JAX backend (ALU-chain
  kernels + whole-segment launches). Both must agree bit-exactly; the
  interesting numbers are the steady-state walls and the kernel-launch
  counts, which are deterministic and therefore what ``--check-baseline``
  ratchets.

* **Autotune sweep** (``--sweep``): wall-clock of verifying a full
  ``--tune full`` sweep (every winning candidate of every resnet18 +
  mobilenet layer executed on a calibration batch against the numpy
  oracle), numpy vs jax — identical tuned cycles by the bit-exactness
  contract, only wall-clock differs.

CLI:

  PYTHONPATH=src python -m benchmarks.bench_backend \
      --batch 4 --json-out results/bench --check-baseline benchmarks/baselines

``--json-out`` writes ``BENCH_backend.json`` (per-kind rows + headline
speedups); ``--check-baseline`` compares launch counts against the
checked-in copy — fused launches may not regress upward. Wall-clock is
reported but never gated (CI machines are noisy).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core.dse import make_config
from repro.core.tps import ConvWorkload, tps_search
from repro.vta.autotune import LayerTuner
from repro.vta.network import run_network
from repro.vta.workloads import _add, _conv, network_graph, resolve_network

KINDS = ("conv", "depthwise", "pool", "dense", "fused-segment")


# ---------------------------------------------------------------------------
# Per-kind representative suite
# ---------------------------------------------------------------------------
def _conv_prog(wl, hw, **kw):
    res = tps_search(wl, hw, require_db=True)
    if not res.feasible:
        res = tps_search(wl, hw)
    from repro.vta.scheduler import schedule_conv
    return schedule_conv(wl, res.tiling, hw, **kw).program


def _suite(hw):
    """(kind, name, program, shared tensors, per-image tensor shapes).

    The depthwise rows are the mobilenet1.0 dw ladder (28x28x256 down to
    7x7x1024) — the depthwise-heavy regime the fused ALU-sweep kernel
    targets. Shapes are kept moderate so the numpy oracle finishes in
    seconds per row.
    """
    from repro.vta.compiler import compile_graph
    from repro.vta.graph import Graph
    from repro.vta.scheduler import schedule_depthwise, schedule_pool
    rng = np.random.default_rng(5)
    rows = []

    wl = ConvWorkload("c3x3", 1, 28, 28, 3, 3, 64, 64, 1, 1, 1, 1)
    rows.append(("conv", "conv3x3_28x28x64", _conv_prog(wl, hw),
                 {"wgt": rng.integers(-8, 8, (64, 64, 3, 3), dtype=np.int8)},
                 {"inp": (1, 64, 28, 28), "out": (1, 64, 28, 28)}))

    for h, c, s in ((28, 256, 1), (14, 512, 1), (7, 1024, 1), (28, 256, 2)):
        wl = ConvWorkload(f"dw{h}x{c}s{s}", 1, h, h, 3, 3, c, c, 1, 1, s, s,
                          depthwise=True)
        from repro.vta.scheduler import schedule_depthwise as _sd
        rows.append(("depthwise", wl.name, _sd(wl, hw).program,
                     {"dw_wgt": rng.integers(-8, 8, (c, 3, 3),
                                             dtype=np.int8)},
                     {"inp": (1, c, h, h), "out": (1, wl.fo, wl.oh, wl.ow)}))

    wl = ConvWorkload("pool", 1, 28, 28, 3, 3, 128, 128, 1, 1, 2, 2)
    rows.append(("pool", "maxpool3x3_28x28x128",
                 schedule_pool(wl, hw, mode="max").program, {},
                 {"inp": (1, 128, 28, 28), "out": (1, 128, wl.oh, wl.ow)}))

    wl = ConvWorkload("pw", 1, 14, 14, 1, 1, 256, 256, 0, 0, 1, 1)
    rows.append(("dense", "pointwise_14x14x256", _conv_prog(wl, hw),
                 {"wgt": rng.integers(-8, 8, (256, 256, 1, 1),
                                      dtype=np.int8)},
                 {"inp": (1, 256, 14, 14), "out": (1, 256, 14, 14)}))

    g = Graph(name="seg")
    g.input("image", (1, 32, 14, 14))
    g.layer(_conv("a", 1, 14, 32, 32, 3, 1, 1), "image")
    g.layer(_conv("b", 1, 14, 32, 32, 3, 1, 1), "a")
    g.residual_add("add", "b", "a", layer=_add("add", 1, 14, 32))
    seg = [s for s in compile_graph(g, hw) if s.multi][0]
    rows.append(("fused-segment", "conv_add_clip_14x14x32", seg.program,
                 {"b.wgt": rng.integers(-8, 8, (32, 32, 3, 3),
                                        dtype=np.int8)},
                 {"a": (1, 32, 14, 14), "add": (1, 32, 14, 14)}))
    return rows


def _batched(shapes, batch, rng):
    out = {}
    for name, shp in shapes.items():
        if name in ("out", "add"):
            out[name] = np.zeros((batch,) + shp, np.int8)
        else:
            out[name] = rng.integers(-128, 128, (batch,) + shp,
                                     dtype=np.int8)
    return out


def run_kinds(batch: int = 4, passes: int = 2, verbose: bool = True) -> dict:
    """Per-kind breakdown: numpy vs jax, steady-state walls + the jax
    backend's launch counts, outputs asserted byte-identical."""
    from repro.vta import fsim_jax
    from repro.vta.backend import get_backend
    hw = make_config()
    rng = np.random.default_rng(17)
    numpy_be = get_backend("numpy")
    be = fsim_jax.JaxBackend()
    rows = []
    if verbose:
        print(f"== bench_backend: per-kind breakdown, batch={batch}, "
              f"steady state = pass {passes} ==")
    for kind, name, prog, shared, shapes in _suite(hw):
        data = _batched(shapes, batch, rng)
        t0 = time.perf_counter()
        o_np = numpy_be.run_batched(prog, hw, shared=shared,
                                    batched={k: v.copy()
                                             for k, v in data.items()})
        np_s = time.perf_counter() - t0
        for _ in range(passes):              # pass 1 pays XLA compile
            fsim_jax.reset_kernel_launch_log()
            t0 = time.perf_counter()
            o = be.run_batched(prog, hw, shared=shared,
                               batched={k: v.copy() for k, v in data.items()})
            o = {t: np.asarray(v).reshape(data[t].shape)
                 for t, v in o.items()}          # the fetch waits for it
            wall = time.perf_counter() - t0
            launches = fsim_jax.kernel_launch_log()
        for t in o_np:
            assert np.array_equal(o[t], o_np[t]), \
                f"{name}: jax diverges from numpy on {t!r}"
        row = {"kind": kind, "name": name, "batch": batch,
               "numpy_s": round(np_s, 3), "fused_s": round(wall, 3),
               "launches_fused": launches, "insns": len(prog.order)}
        rows.append(row)
        if verbose:
            print(f"  {kind:13s} {name:22s} numpy {np_s:7.3f}s  "
                  f"jax {wall:7.3f}s  launches {launches:3d}")

    kinds = {}
    for k in KINDS:
        sel = [r for r in rows if r["kind"] == k]
        if not sel:
            continue
        kinds[k] = {"numpy_s": round(sum(r["numpy_s"] for r in sel), 3),
                    "fused_s": round(sum(r["fused_s"] for r in sel), 3),
                    "launches_fused": sum(r["launches_fused"] for r in sel)}
    if verbose:
        print("  -> all kinds bit-exact across numpy / jax")
    return {"rows": rows, "kinds": kinds, "batch": batch}


def write_json(out: dict, out_dir: str) -> str:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "BENCH_backend.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    return path


def check_baseline(out: dict, baseline_dir: str) -> list:
    """Launch-count ratchet vs the checked-in BENCH_backend.json.

    Launch counts are deterministic compile-time facts (unlike wall-clock),
    so the guard is exact: the fused path may not launch MORE kernels per
    kind than the recorded baseline. Kinds absent from the baseline are
    skipped. Returns violation strings (empty = pass).
    """
    path = os.path.join(baseline_dir, "BENCH_backend.json")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        base = json.load(f)
    errs = []
    for k, v in out["kinds"].items():
        b = base.get("kinds", {}).get(k)
        if b is None:
            continue
        if v["launches_fused"] > b["launches_fused"]:
            errs.append(f"{k}: fused kernel launches regressed "
                        f"{b['launches_fused']} -> {v['launches_fused']}")
    return errs


# ---------------------------------------------------------------------------
# Full autotune-sweep mode (--sweep)
# ---------------------------------------------------------------------------
def run(nets=("resnet18", "mobilenet1.0"), batch: int = 8,
        backends=("numpy", "jax"), passes: int = 2,
        verbose: bool = True) -> dict:
    """``passes``: the jax backend pays XLA compilation on first sight of
    each chunk structure; pass 2+ measures the steady state (what repeated
    sweeps, pool workers and CI hit — executables persist on disk via the
    XLA compilation cache). The numpy interpreter has no warmup, so only
    its first pass is kept."""
    hw = make_config()
    rows = []
    if verbose:
        print(f"== bench_backend: full autotune sweep, verify batch={batch}, "
              f"default config ==")
    for be in backends:
        for p in range(passes if be != "numpy" else 1):
            tuner = LayerTuner(mode="full", backend=be, verify_batch=batch)
            t0 = time.perf_counter()
            reports = {}
            for net in nets:
                reports[net] = run_network(net, network_graph(net, 1), hw,
                                           dedup_loads=True, layer_cache={},
                                           tuner=tuner)
            wall = time.perf_counter() - t0
            row = {"backend": be, "batch": batch, "pass": p,
                   "verify_s": round(tuner.verify_seconds, 2),
                   "sweep_s": round(wall, 2),
                   "searches": tuner.searches,
                   "cycles": {n: r.total_cycles for n, r in reports.items()}}
            rows.append(row)
            if verbose:
                tag = "" if be == "numpy" else (
                    " (cold: + XLA compile)" if p == 0 else " (steady state)")
                print(f"  {be:6s}: verification {row['verify_s']:7.2f}s of "
                      f"{row['sweep_s']:7.2f}s sweep "
                      f"({tuner.searches} layer searches){tag}")
    out = {"rows": rows}
    if len({r["backend"] for r in rows}) == 2:
        a = next(r for r in rows if r["backend"] == rows[0]["backend"])
        b = rows[-1]                     # final pass of the second backend
        assert all(r["cycles"] == a["cycles"] for r in rows), \
            "backends disagree on tuned cycles"
        out["verify_speedup"] = round(a["verify_s"] / max(b["verify_s"], 1e-9),
                                      2)
        out["sweep_speedup"] = round(a["sweep_s"] / max(b["sweep_s"], 1e-9), 2)
        if verbose:
            print("  -> identical tuned cycles on both backends")
            print(f"  -> steady-state verification speedup "
                  f"{out['verify_speedup']}x, whole-sweep "
                  f"{out['sweep_speedup']}x "
                  f"({a['backend']} -> {b['backend']})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmarks.bench_backend")
    ap.add_argument("--batch", type=int, default=4,
                    help="calibration images per run (default 4)")
    ap.add_argument("--passes", type=int, default=2,
                    help="jax passes (pass 1 pays XLA compile; the last "
                         "pass is the steady-state measurement)")
    ap.add_argument("--json-out", default=None,
                    help="directory to write BENCH_backend.json into")
    ap.add_argument("--check-baseline", default=None,
                    help="directory holding the checked-in "
                         "BENCH_backend.json launch-count baseline")
    ap.add_argument("--sweep", action="store_true",
                    help="also run the full autotune-sweep comparison "
                         "(slow: tunes resnet18 + mobilenet end to end)")
    ap.add_argument("--no-sweep", action="store_true",
                    help="accepted for compatibility; the sweep is already "
                         "opt-in via --sweep")
    ap.add_argument("--nets", default="resnet18,mobilenet",
                    help="networks for --sweep mode")
    ap.add_argument("--backends", default="numpy,jax",
                    help="backends for --sweep mode")
    args = ap.parse_args(argv)

    out = run_kinds(batch=args.batch, passes=args.passes)
    rc = 0
    if args.check_baseline:
        errs = check_baseline(out, args.check_baseline)
        for e in errs:
            print(f"BASELINE VIOLATION: {e}", file=sys.stderr)
        rc = 1 if errs else 0
    if args.sweep and not args.no_sweep:
        nets = tuple(resolve_network(n) for n in args.nets.split(",") if n)
        backends = tuple(b for b in args.backends.split(",") if b)
        out["sweep"] = run(nets=nets, batch=args.batch, backends=backends,
                           passes=args.passes)
    if args.json_out:
        print(f"wrote {write_json(out, args.json_out)}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
