"""Reduce the program's own spans and op scopes in a ``jax.profiler`` trace:
the device's idle time split by what the served path was doing in it, and
device time by VTA instruction class.

``load_xplane`` reads the trace (with nothing but ``jax.profiler.ProfileData``)
into plain tuples; ``reduce`` works on those tuples alone, so a test can hand
it a synthetic trace:

* device events: ``{device plane name: [(op name, start_ns, dur_ns, scope),
  ...]}`` from the ``XLA Ops`` line of every ``/device:`` plane; ``scope`` is
  the VTA instruction class (``load``, ``gemm``, ``alu``, ``store``) the
  program's ``jax.named_scope`` gave the op, or None. A device op's event
  carries no ``op_name``: the class is read from the compiled HLO of its
  program, which the trace keeps in its ``/host:metadata`` plane (a ``Hlo
  Proto`` per program, keyed like the ``XLA Modules`` event the op runs in);
  ``ProfileData`` does not show that plane, so ``op_scopes`` reads the
  protobuf's wire format itself;
* host spans: ``[(name, start_ns, dur_ns), ...]``, the program's
  ``vta.batch``, ``vta.upload``, ``vta.launch`` and ``vta.fetch`` spans and
  the benchmark's ``chipbench.window``.

The window and each device's busy union are those of ``trace_reduce.reduce``.
Each stretch of an idle gap goes to the innermost program span open over it:
``upload``, ``launch`` or ``fetch`` (the executor's ``vta.upload``,
``vta.launch``, ``vta.fetch``); ``segment_other``, inside a ``vta.batch`` but
in none of those (the served model's and the segment's own Python); or
``engine``, outside every ``vta.batch``. The five partition the idle time.
A trace with no ``vta.batch`` span (a program without these spans) splits
nothing, and one with no scoped op gives no scope times.

The harness does not call this module yet: its traced run keeps only
``trace_reduce``'s result, and the call belongs in ``harness.run_cell``,
beside ``trace_reduce``'s, before the trace directory is removed.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import Counter, defaultdict

from chipbench import trace_reduce

PHASES = {"vta.upload": "upload", "vta.launch": "launch",
          "vta.fetch": "fetch", "vta.batch": "segment_other"}
INNERMOST = ("upload", "launch", "fetch", "segment_other")
PARTS = INNERMOST + ("engine",)
SCOPES = ("load", "gemm", "alu", "store")
_SCOPE = re.compile(r"\bvta\.(load|gemm|alu|store)\b")
MODULES_LINE = "XLA Modules"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a serialized protobuf
    message: an int for a varint, the bytes (a memoryview) for a
    length-delimited field; fixed-width fields are skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:                               # 64- or 32-bit
            i += 8 if wire == 1 else 4
            continue
        yield key >> 3, value


def _field(buf, number: int, default=None):
    return next((v for n, v in _fields(buf) if n == number), default)


def scope_of(op_name: str) -> str | None:
    """The VTA instruction class in an HLO ``op_name``
    (``jit(_exec_chunk)/vmap(vta.gemm)/dot_general``), or None."""
    m = _SCOPE.search(op_name)
    return m.group(1) if m else None


def program_key(name: str) -> str:
    """A program's key in the trace: the id in ``jit__exec_chunk(<id>)``, the
    name the ``XLA Modules`` event and the ``Hlo Proto`` both carry, or the
    whole name where it has none."""
    m = _PROGRAM_ID.search(name)
    return m.group(1) if m else name


def op_scopes(xspace: bytes) -> dict:
    """``{(program, instruction name): class}`` of every instruction with a
    class in its ``op_name``, from each ``Hlo Proto`` of the serialized
    XSpace's ``/host:metadata`` plane (XSpace.planes 1; XPlane.name 2,
    event_metadata 4, stat_metadata 5; XEventMetadata.name 2, stats 5;
    XStat.metadata_id 1, bytes_value 6; HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, metadata 7; OpMetadata.op_name 2)."""
    out = {}
    for number, plane in _fields(memoryview(xspace)):
        if number != 1 or bytes(_field(plane, 2, b"")) != b"/host:metadata":
            continue
        entries = [(n, _field(v, 2)) for n, v in _fields(plane) if n in (4, 5)]
        hlo = {_field(m, 1) for n, m in entries
               if n == 5 and bytes(_field(m, 2, b"")) == b"Hlo Proto"}
        for n, meta in entries:
            if n != 4:
                continue
            program = program_key(bytes(_field(meta, 2, b"")).decode())
            for stat in (v for k, v in _fields(meta) if k == 5):
                if _field(stat, 1) not in hlo:
                    continue
                module = _field(_field(stat, 6), 1)
                for comp in (v for k, v in _fields(module) if k == 3):
                    for ins in (v for k, v in _fields(comp) if k == 2):
                        op = _field(ins, 7, b"")
                        sc = scope_of(bytes(_field(op, 2, b"")).decode())
                        if sc is not None:
                            out[program, bytes(_field(ins, 1)).decode()] = sc
    return out


def _modules(plane) -> tuple:
    """(starts, ends, names) of the ``XLA Modules`` events of a device
    plane: the program each op runs in."""
    evs = sorted((e.start_ns, e.start_ns + e.duration_ns, program_key(e.name))
                 for line in plane.lines if line.name == MODULES_LINE
                 for e in line.events)
    return [e[0] for e in evs], [e[1] for e in evs], [e[2] for e in evs]


def load_xplane(trace_dir) -> tuple:
    """(device events with their scope, host spans) of the one trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    with open(paths[0], "rb") as f:
        raw = f.read()
    scopes = op_scopes(raw)
    data = ProfileData.from_serialized_xspace(raw)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            starts, ends, names = _modules(plane)
            evs = []
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                for e in line.events:
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    program = names[i] if i >= 0 and e.start_ns < ends[i] \
                        else None
                    name = trace_reduce.op_key(e.name).split(" ", 1)[1]
                    evs.append((e.name, e.start_ns, e.duration_ns,
                                scopes.get((program, name))))
            if evs:
                device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host.extend((e.name, e.start_ns, e.duration_ns)
                        for line in plane.lines for e in line.events
                        if e.name in PHASES or e.name == trace_reduce.WINDOW)
    return device, host


def _timeline(spans: list) -> list:
    """Sorted ``(start, end, part)`` pieces of the stretches some program
    span covers, each named by the innermost part open over it."""
    edges = sorted((t, delta, PHASES[n]) for n, s, d in spans if n in PHASES
                   for t, delta in ((s, 1), (s + d, -1)))
    open_, out, prev = Counter(), [], None
    for t, delta, part in edges:
        if prev is not None and t > prev:
            top = next((p for p in INNERMOST if open_[p] > 0), None)
            if top is not None:
                out.append((prev, t, top))
        open_[part] += delta
        prev = t
    return out


def _split(gaps: list, pieces: list) -> dict:
    """Idle seconds per part: each gap's overlap with the timeline's pieces
    to their parts, the rest to ``engine``."""
    out = dict.fromkeys(PARTS, 0.0)
    starts = [p[0] for p in pieces]
    for a, b in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(pieces) and pieces[i][0] < b:
            s, e, part = pieces[i]
            if e > a:
                out[part] += (min(e, b) - max(s, a)) / 1e9
                covered += min(e, b) - max(s, a)
            i += 1
        out["engine"] += ((b - a) - covered) / 1e9
    return out


def reduce(device: dict, host: list) -> dict:
    """``window_s``; ``idle_s`` ({part: idle seconds, averaged over the
    devices that ran an op}, None without a ``vta.batch`` span or a busy
    device); ``scope_s`` ({class: device seconds in the window, summed over
    devices}, None where no op carries a class)."""
    wins = [(s, s + d) for n, s, d in host if n == trace_reduce.WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {trace_reduce.WINDOW} span, "
                         f"found {len(wins)}")
    w0, w1 = wins[0]
    pieces = _timeline(host)
    idle, scope, n_dev = defaultdict(float), dict.fromkeys(SCOPES, 0.0), 0
    scoped = False
    for evs in device.values():
        clipped = [(max(s, w0), min(s + d, w1), sc) for _, s, d, sc in evs
                   if s < w1 and s + d > w0]
        if not clipped:
            continue
        n_dev += 1
        for s, e, sc in clipped:
            if sc is not None:
                scope[sc] += (e - s) / 1e9
                scoped = True
        merged = trace_reduce._union([(s, e) for s, e, _ in clipped])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for part, sec in _split(gaps, pieces).items():
            idle[part] += sec
    batches = any(n == "vta.batch" for n, _, _ in host)
    return {"window_s": (w1 - w0) / 1e9,
            "idle_s": ({p: idle[p] / n_dev for p in PARTS}
                       if n_dev and batches else None),
            "scope_s": scope if scoped else None}

