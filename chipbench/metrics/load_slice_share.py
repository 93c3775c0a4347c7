"""Percent of the load entries dispatched in the run that read their tile as
a strided slice of the padded DRAM tensor, not as an element gather:
``100 * slice / (slice + gather)`` from ``fsim_jax.load_form_log``, read
from the program in the run's own process once the window has closed. None
where the program has no such counter, or dispatched no load."""


def read(rec):
    from repro.vta import fsim_jax
    forms = getattr(fsim_jax, "load_form_log", None)
    if forms is None:
        return None
    n = forms()
    total = n["slice"] + n["gather"]
    return 100 * n["slice"] / total if total else None
