"""Device milliseconds per served image of every op that is not GEMM-class:
the lax ALU sweeps, gathers, scatters and copies of the executor's chunks,
over the images completed in the traced window."""
from chipbench import harness
from chipbench.metrics.gemm_roofline import is_gemm


def read(rec):
    trace = rec.get("trace")
    if not trace or not trace["ops"]:
        return None
    images = len(harness.traced_requests(rec))
    if images == 0:
        return None
    other = sum(s for n, s in trace["ops"].items() if not is_gemm(n))
    return 1e3 * other / images
