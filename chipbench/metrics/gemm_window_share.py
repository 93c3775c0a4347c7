"""Percent of the GEMM entries dispatched in the run that read their
operands as windows of the scratchpads, not as gathered rows: ``100 *
window / (window + gather)`` from ``fsim_jax.gemm_form_log``, read from the
program in the run's own process once the window has closed. None where
the program has no such counter, or dispatched no GEMM."""


def read(rec):
    from repro.vta import fsim_jax
    forms = getattr(fsim_jax, "gemm_form_log", None)
    if forms is None:
        return None
    n = forms()
    total = n["window"] + n["gather"]
    return 100 * n["window"] / total if total else None
