"""Images completed in the window over the window's seconds (closed loop:
the window closes on a completion, so it holds whole batches)."""


def read(rec):
    return len(rec["counted"]) / rec["window_s"] if rec["window_s"] > 0 else None
