"""Percent of the multiply-accumulates the GEMM instructions executed that
the network needs: its published conv and dense MACs per image
(``work.macs_per_image``) times the images of the window's batches, over
``fsim_jax.gemm_mac_log``, read from the program in the run's own process
once the window has closed. Below 100 where the program pads channels to
the VTA block. Each batch is padded to its bucket, so the images are the
batches times the bucket; None where the mix has more than one bucket,
or where the program has no such counter or executed no GEMM."""
from chipbench import work


def read(rec):
    from repro.vta import fsim_jax
    macs = getattr(fsim_jax, "gemm_mac_log", None)
    buckets = rec["traffic"]["buckets"]
    if macs is None or len(buckets) != 1 or not rec["batches"]:
        return None
    executed = macs()
    if not executed:
        return None
    images = rec["batches"] * buckets[0]
    return 100.0 * work.macs_per_image(rec["config"]) * images / executed
