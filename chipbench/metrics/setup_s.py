"""Seconds from process start to the first timed request: building the
model, compiling or loading its programs, the warm-up batches."""


def read(rec):
    return rec["setup_s"]
