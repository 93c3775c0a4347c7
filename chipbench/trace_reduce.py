"""Reduce a ``jax.profiler`` trace to device busy time, device time per
operation and idle gaps named by the host span open during them.

``load_xplane`` reads the ``.xplane.pb`` the profiler wrote (with nothing
but ``jax.profiler.ProfileData``) into plain tuples; ``reduce`` works on
those tuples alone, so a test can hand it a synthetic trace:

* device events: ``{device plane name: [(op name, start_ns, dur_ns), ...]}``
  from the ``XLA Ops`` line of every ``/device:`` plane;
* host spans: ``[(name, start_ns, dur_ns), ...]``, the benchmark's own
  ``chipbench.*`` annotations.

The window is the ``chipbench.window`` span. Busy time is the union of a
device's op intervals clipped to the window, averaged over the devices that
ran an op; an idle gap is a stretch of the window in which that union is
empty, and its time goes to the innermost host span that overlaps it (a
segment's ``run_batched`` before the batch around it), the rest to
``outside a batch``.
"""
from __future__ import annotations

import bisect
import functools
import glob
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
WINDOW = "chipbench.window"
BATCH = "chipbench.batch"
SEGMENT = "chipbench.segment"
OUTSIDE = "outside a batch"
TOP = 10


def load_xplane(trace_dir) -> tuple:
    """(device events, host spans) of the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    data = ProfileData.from_file(paths[0])
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs = [(e.name, e.start_ns, e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if evs:
                device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host.extend((e.name, e.start_ns, e.duration_ns)
                        for line in plane.lines for e in line.events
                        if e.name.startswith("chipbench."))
    return device, host


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%?([\w.\-]+) = (?:\([^()]*\)|\S+) ([\w\-]+)\(")
_KIND = re.compile(r"kind=(k\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@functools.lru_cache(maxsize=None)
def op_key(text: str) -> str:
    """``<family> <name>`` of a device op. The TPU trace names an op by its
    HLO text (``%fusion.20 = s8[...]{layout} fusion(...), kind=kCustom,
    ...``): the family is its opcode, a fusion's with its kind
    (``fusion(kCustom)``), and the name its instruction name. A bare name
    (``fusion.12``) is its own family without the instance number."""
    m = _HLO.match(_LAYOUT.sub("", text))
    if m is None:
        family = re.sub(r"[.\d]+$", "", text) or text
        return f"{family} {text}"
    name, opcode = m.groups()
    detail = {"fusion": _KIND, "custom-call": _TARGET}.get(opcode)
    found = detail.search(text) if detail else None
    if found:
        opcode = f"{opcode}({found.group(1)})"
    return f"{opcode} {name}"


def op_family(key: str) -> str:
    return key.split(" ", 1)[0]


def _attribute(gaps: list, spans: list) -> dict:
    """Idle seconds per host-span label: each gap's overlap with segment
    spans goes to their labels, what batch spans cover beyond that to the
    batch, and the rest to ``OUTSIDE``."""
    out = defaultdict(float)
    by_level = {lvl: sorted((s, s + d, n) for n, s, d in spans
                            if n.startswith(lvl)) for lvl in (SEGMENT, BATCH)}

    def overlaps(lvl, a, b):
        ivs = by_level[lvl]
        i = max(0, bisect.bisect_right(ivs, (a,)) - 1)
        while i < len(ivs) and ivs[i][0] < b:
            s, e, n = ivs[i]
            if e > a:
                yield n, max(s, a), min(e, b)
            i += 1

    for a, b in gaps:
        covered = 0.0
        for n, s, e in overlaps(SEGMENT, a, b):
            out[n] += (e - s) / 1e9
            covered += e - s
        in_batch = sum(e - s for _, s, e in overlaps(BATCH, a, b))
        if in_batch > covered:
            out[BATCH] += (in_batch - covered) / 1e9
        out[OUTSIDE] += max(0.0, (b - a) - max(in_batch, covered)) / 1e9
    return dict(out)


def reduce(device: dict, host: list) -> dict:
    """``window_s``, ``busy_s``, ``ops`` ({``op_key``: device seconds in the
    window}, summed over devices), ``idle`` ({host label: idle seconds,
    averaged over devices}) and the ``breakdown`` the result line carries."""
    wins = [(s, s + d) for n, s, d in host if n == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(wins)}")
    w0, w1 = wins[0]
    spans = [(n, s, d) for n, s, d in host if n != WINDOW]
    ops: dict = defaultdict(float)
    busy, idle = [], defaultdict(float)
    for evs in device.values():
        clipped = [(max(s, w0), min(s + d, w1), n) for n, s, d in evs
                   if s < w1 and s + d > w0]
        if not clipped:
            continue
        for s, e, n in clipped:
            ops[op_key(n)] += (e - s) / 1e9
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for label, sec in _attribute(gaps, spans).items():
            idle[label] += sec
    if not busy:
        return {"window_s": (w1 - w0) / 1e9, "busy_s": 0.0, "ops": {},
                "idle": {}, "breakdown": {"device_ops": [], "idle_gaps": []}}
    n_dev = len(busy)
    idle = {k: v / n_dev for k, v in idle.items()}
    families: dict = defaultdict(float)
    for n, sec in ops.items():
        families[op_family(n)] += sec
    top = sorted(families.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": (w1 - w0) / 1e9, "busy_s": sum(busy) / n_dev,
            "ops": dict(ops), "idle": idle,
            "breakdown": {"device_ops": [list(kv) for kv in top],
                          "idle_gaps": [list(kv) for kv in gaps]}}
