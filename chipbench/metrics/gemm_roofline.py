"""The GEMM's share of its roofline, in %: the least time the chip needs
for the network's conv and dense layers over the images served in the
traced window, over the device time of GEMM-class ops there.

The least time is the larger of their int8 operations over the chip's int8
peak and their least bytes (``work.gemm_min_bytes``) over its HBM
bandwidth. GEMM-class ops are the Pallas GEMM kernel, a
``custom-call(tpu_custom_call)`` (on a TPU the program's only Pallas kernel
is the GEMM; its ALU sweeps run as ``lax`` ops, and its other custom calls,
such as ``ConcatBitcast``, move data), and any XLA dot or convolution,
matched by ``PATTERNS`` on the op's family (``trace_reduce.op_key``).
"""
import re

from chipbench import harness, work

PATTERNS = re.compile(r"^(custom-call\(tpu_custom_call\)|dot|convolution)[ (]")


def is_gemm(op_name: str) -> bool:
    return bool(PATTERNS.search(op_name))


def served(rec):
    """(images, batches) completed in the traced window."""
    done = harness.traced_requests(rec)
    return len(done), len({r["done"] for r in done})


def read(rec):
    trace = rec.get("trace")
    gemm_s = sum(s for n, s in trace["ops"].items() if is_gemm(n)) if trace else 0
    if gemm_s <= 0:
        return None
    images, batches = served(rec)
    if images == 0:
        return None
    cfg, peaks = rec["config"], rec["peaks"]
    ops = 2 * work.macs_per_image(cfg) * images
    least = max(ops / peaks["int8_ops_per_s"],
                work.gemm_min_bytes(cfg, images, batches)
                / peaks["hbm_bytes_per_s"])
    return 100.0 * least / gemm_s
