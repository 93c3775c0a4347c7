"""Megabytes (1e6 bytes) per served batch that the ALU instructions read or
wrote through index arrays (gathers, scatters, indexed updates; the
depthwise taps on MobileNet): the ``alu`` part of
``fsim_jax.indexed_bytes_by_class``, read from the program in the run's own
process once the window has closed. None where the program has no such
counter."""


def read(rec):
    from repro.vta import fsim_jax
    by_class = getattr(fsim_jax, "indexed_bytes_by_class", None)
    if by_class is None or not rec["batches"]:
        return None
    return by_class()["alu"] / 1e6 / rec["batches"]
