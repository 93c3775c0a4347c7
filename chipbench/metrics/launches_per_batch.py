"""Chunk dispatches to the device per served batch (``fsim_jax``'s launch
counter over the engine's batches, both since the window opened)."""


def read(rec):
    return rec["launches"] / rec["batches"] if rec["batches"] else None
