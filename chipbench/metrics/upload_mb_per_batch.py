"""Host-to-device megabytes (1e6 bytes) uploaded per served batch: tensors,
weights and index maps (``fsim_jax.upload_bytes_log``)."""


def read(rec):
    return rec["upload_bytes"] / 1e6 / rec["batches"] if rec["batches"] else None
