"""The reduction of the program's spans and op scopes, on a synthetic trace:
the five idle shares partition ``device_idle_share``, the innermost program
span over a gap takes it, device time splits by VTA instruction class, and a
trace or a record from a program without these spans or counters reads
nothing. Then a traced CPU run of the tiny ResNet reports the index-map
counter, and its real profile holds the program's spans."""
import re

import pytest

from _chipbench_fixtures import run, tiny_root

from chipbench import program_trace, trace_reduce
from chipbench.metrics import device_idle_share, index_map_mb_per_batch

MS = 1_000_000          # ns


def _trace(batch=True):
    """A 100 ms window. Device ops (ms): 0-5 (a load), 22-26 (a GEMM),
    40-45 (a store), 60-70 (unscoped) and one after the window. Host: a
    batch 10-80 holding a segment 10-80 with an upload 10-20, a launch
    20-30, a fetch 30-50 and a second upload 55-58; a resolve 80-82."""
    device = {"/device:TPU:0": [
        ("fusion.1", 0, 5 * MS, "load"),
        ("custom-call.4", 22 * MS, 4 * MS, "gemm"),
        ("fusion.9", 40 * MS, 5 * MS, "store"),
        ("copy.3", 60 * MS, 10 * MS, None),
        ("copy.4", 150 * MS, 10 * MS, "alu"),         # outside the window
    ]}
    host = [("chipbench.window", 0, 100 * MS),
            ("vta.segment", 10 * MS, 70 * MS),
            ("vta.upload", 10 * MS, 10 * MS),
            ("vta.launch", 20 * MS, 10 * MS),
            ("vta.fetch", 30 * MS, 20 * MS),
            ("vta.upload", 55 * MS, 3 * MS),
            ("serve.resolve", 80 * MS, 2 * MS)]
    if batch:
        host.append(("vta.batch", 10 * MS, 70 * MS))
    return device, host


def _shares(red):
    """Each part's idle time in % of the window."""
    return {p: 100.0 * s / red["window_s"] for p, s in red["idle_s"].items()}


def test_idle_goes_to_the_innermost_span_and_the_shares_sum_to_the_idle_share():
    red = program_trace.reduce(*_trace())
    # gaps: 5-22 (engine 5-10, upload 10-20, launch 20-22), 26-40 (launch
    # 26-30, fetch 30-40), 45-60 (fetch 45-50, batch 50-55, upload 55-58,
    # batch 58-60), 70-100 (batch 70-80, engine 80-100)
    assert red["idle_s"] == pytest.approx(
        {"upload": 0.013, "launch": 0.006, "fetch": 0.015,
         "segment_other": 0.017, "engine": 0.025})
    device, host = _trace()
    plain = [(n, s, d) for n, s, d, _ in device["/device:TPU:0"]]
    whole = trace_reduce.reduce({"/device:TPU:0": plain},
                                [h for h in host if h[0] == "chipbench.window"])
    shares = _shares(red)
    assert shares["engine"] == pytest.approx(25.0)
    assert sum(shares.values()) == pytest.approx(
        device_idle_share.read({"trace": whole}))


def test_the_split_averages_over_devices_as_the_idle_share_does():
    device, host = _trace()
    other = [(n, s + 50 * MS, d, sc) for n, s, d, sc in device["/device:TPU:0"]]
    device["/device:TPU:1"] = other
    red = program_trace.reduce(device, host)
    whole = trace_reduce.reduce(
        {k: [e[:3] for e in v] for k, v in device.items()}, host)
    assert sum(red["idle_s"].values()) == pytest.approx(
        whole["window_s"] - whole["busy_s"])


def test_device_time_splits_by_instruction_class():
    red = program_trace.reduce(*_trace())
    assert red["scope_s"] == pytest.approx(
        {"load": 0.005, "gemm": 0.004, "alu": 0.0, "store": 0.005})


def test_a_program_without_the_spans_or_counters_reads_nothing(monkeypatch):
    from repro.vta import fsim_jax
    device, host = _trace(batch=False)
    red = program_trace.reduce(
        {k: [(n, s, d, None) for n, s, d, _ in v] for k, v in device.items()},
        [h for h in host if not h[0].startswith("vta.")])
    assert red["idle_s"] is None and red["scope_s"] is None
    with pytest.raises(ValueError, match="chipbench.window"):
        program_trace.reduce({}, [])
    rec = {"upload_bytes": 9_000_000, "batches": 3}
    monkeypatch.setattr(fsim_jax, "upload_bytes_by_kind", lambda: {
        "activations": 2e6, "weights": 1e6, "index_maps": 6e6})
    assert index_map_mb_per_batch.read(rec) == 2.0
    # a split that is not the window's upload count reads nothing
    assert index_map_mb_per_batch.read({**rec, "upload_bytes": 1}) is None
    assert index_map_mb_per_batch.read({**rec, "batches": 0}) is None
    monkeypatch.delattr(fsim_jax, "upload_bytes_by_kind")
    assert index_map_mb_per_batch.read(rec) is None


def test_scopes_are_read_from_the_op_name():
    assert program_trace.scope_of(
        "jit(_exec_chunk)/vmap(vta.gemm)/dot_general") == "gemm"
    assert program_trace.scope_of("jit(f)/vmap(vta.load)/jit(_where)") == \
        "load"
    assert program_trace.scope_of("args[3]") is None
    assert program_trace.scope_of("jit(f)/vta.loader/x") is None


def _varint(n: int) -> bytes:
    out = b""
    while n >= 0x80:
        out, n = out + bytes([n & 0x7F | 0x80]), n >> 7
    return out + bytes([n])


def _xspace(tmp_path):
    """A serialized XSpace laid out as a TPU trace is, written where the
    profiler writes one: a program compiled here with two scopes, its HLO
    in the ``/host:metadata`` plane, one run of it on ``/device:TPU:0``
    (0-50 us) with its two ops (1-3 us, 4-6 us) and one op outside any
    program (60-62 us), and the window and a batch on the host (0-100 us,
    0.5-20.5 us). Returns the trace directory and the ops' names."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    @jax.jit
    def f(x, idx):
        with jax.named_scope("vta.load"):
            y = x[idx]
        with jax.named_scope("vta.gemm"):
            return y @ y.T
    compiled = f.lower(jnp.ones((64, 64)), jnp.arange(32)).compile()
    [module] = compiled.runtime_executable().hlo_modules()
    text = module.to_string()
    gather = next(n for n in re.findall(r"%([\w.\-]+) = ", text)
                  if "gather" in n or "fusion" in n)
    dot = next(n for n in re.findall(r"%([\w.\-]+) = ", text) if "dot" in n)
    proto = module.as_serialized_hlo_module_proto()
    hlo = "".join(f"\\{b:03o}" for b in b"\x0a" + _varint(len(proto)) + proto)

    def ev(mid, start_us, dur_us):
        return (f"events {{ metadata_id: {mid} offset_ps: {start_us * 10**6} "
                f"duration_ps: {dur_us * 10**6} }}")

    def meta(mid, name):
        return f'event_metadata {{ key: {mid} value {{ id: {mid} name: "{name}" }} }}'
    space = f"""
    planes {{ id: 1 name: "/host:metadata"
      event_metadata {{ key: 1 value {{ id: 1 name: "jit_f(7)"
        stats {{ metadata_id: 1 bytes_value: "{hlo}" }} }} }}
      stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }} }}
    planes {{ id: 2 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {ev(1, 0, 50)} }}
      lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {ev(2, 1, 2)} {ev(3, 4, 2)}
               {ev(4, 60, 2)} }}
      {meta(1, "jit_f(7)")} {meta(2, gather)} {meta(3, dot)} {meta(4, gather)} }}
    planes {{ id: 3 name: "/host:CPU"
      lines {{ id: 1 name: "python" timestamp_ns: 0 {ev(1, 0, 100)} }}
      lines {{ id: 2 name: "vta-serve" timestamp_ns: 0
               events {{ metadata_id: 2 offset_ps: 500000 duration_ps: 20000000 }}
               {ev(3, 70, 1)} }}
      {meta(1, "chipbench.window")} {meta(2, "vta.batch")}
      {meta(3, "serve.plan")} }}
    """
    out = tmp_path / "plugins" / "profile" / "run"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(space))
    return tmp_path, gather, dot


def test_the_loader_reads_each_op_class_from_its_program_hlo(tmp_path):
    trace_dir, gather, dot = _xspace(tmp_path)
    device, host = program_trace.load_xplane(trace_dir)
    assert device == {"/device:TPU:0": [
        (gather, 1000.0, 2000.0, "load"), (dot, 4000.0, 2000.0, "gemm"),
        (gather, 60000.0, 2000.0, None)]}        # in no program: no class
    assert sorted(host) == [("chipbench.window", 0.0, 100000.0),
                            ("vta.batch", 500.0, 20000.0)]
    red = program_trace.reduce(device, host)
    assert red["scope_s"] == pytest.approx(
        {"load": 2e-6, "gemm": 2e-6, "alu": 0.0, "store": 0.0})
    # idle 0-1, 3-4, 6-60, 62-100 us; the batch holds 0.5-1, 3-4, 6-20.5
    assert red["idle_s"] == pytest.approx(
        {"upload": 0.0, "launch": 0.0, "fetch": 0.0,
         "segment_other": 16e-6, "engine": 78e-6})


def test_a_traced_cpu_run_reads_the_counter_and_finds_the_spans(
        tmp_path, monkeypatch):
    """The index-map counter reads in a traced run, and the loader finds the
    program's spans in its profile, which it reads beside the harness's own
    reduction; the CPU has no device plane, so the idle split reads nothing
    there."""
    from chipbench import harness
    root = tiny_root(tmp_path, {"r.backlog": ("resnet18", "backlog")})
    seen = {}
    load = trace_reduce.load_xplane

    def keep(trace_dir):
        seen["program"] = program_trace.load_xplane(trace_dir)
        return load(trace_dir)
    monkeypatch.setattr(trace_reduce, "load_xplane", keep)
    monkeypatch.setattr(harness, "TRACE_S", 0.5)
    res = run(root, "r.backlog", trace=True, seconds=2.0)
    assert res["correct"]
    m = res["metrics"]
    assert 0 < m["index_map_mb_per_batch"]["value"] \
        < m["upload_mb_per_batch"]["value"]
    device, host = seen["program"]
    assert {n for n, _, _ in host} == {"chipbench.window", "vta.batch",
                                       "vta.upload", "vta.launch",
                                       "vta.fetch"}
    assert program_trace.reduce(device, host)["idle_s"] is None
