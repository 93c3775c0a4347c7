"""Host-to-device megabytes (1e6 bytes) of the chunks' index maps per
served batch: the ``index_maps`` part of ``fsim_jax.upload_bytes_by_kind``,
read from the program in the run's own process once the window has closed.
None where the program does not split its uploads, or where its split does
not sum to the window's ``upload_bytes``."""


def read(rec):
    from repro.vta import fsim_jax
    by_kind = getattr(fsim_jax, "upload_bytes_by_kind", None)
    if by_kind is None or not rec["batches"]:
        return None
    kinds = by_kind()
    if sum(kinds.values()) != rec["upload_bytes"]:
        return None
    return kinds["index_maps"] / 1e6 / rec["batches"]
