"""The control of the comparison that decides ``correct``: the reference put
in the program's place at the next precision below the configuration's
(int4 for int8), judged exactly as a run judges served outputs. It has to
come out not correct.

    python3 chipbench/control.py --workload resnet18-full.backlog --seeds 1,2,3

Each seed makes the weights and the image pool a run of that seed makes,
serves the first ``--requests`` requests from the int4 reference, and
prints the numbers ``harness.check_outputs`` compares, one JSON line per
seed. It drives no program, so the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def control_readings(config: dict, seed: int, requests: int) -> dict:
    from chipbench import harness, reference
    weights = harness.make_weights(config, seed)
    images = harness.make_images(config, seed)
    idx = [i % len(images) for i in range(requests)]
    low = reference.forward(config, weights, images[idx, 0], quant="int4")
    served = [(i, out, n // 8)            # batches of 8, in order
              for n, (i, out) in enumerate(zip(idx, low))]
    return harness.check_outputs(config, weights, images, served, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--requests", type=int, default=48)
    args = ap.parse_args(argv)
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import harness
    spec = harness.load_spec(ROOT)
    _, entry = harness.cell_of(spec, args.workload)
    config = harness.load_config(ROOT, entry)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = control_readings(config, seed, args.requests)
        print(json.dumps({"workload": args.workload, "seed": seed, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
