"""Share of the traced window, in %, in which no op ran on the device
(1 - the union of op intervals over the window, averaged over devices)."""


def read(rec):
    trace = rec.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
