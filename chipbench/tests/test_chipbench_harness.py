"""The harness on the CPU with the program's tiny served models: the closed
loop, the comparison that decides ``correct`` and the faults it has to
catch, the no-TPU refusal, and that new configurations, mixes, generators
and metrics are found by name."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from _chipbench_fixtures import BENCH, ROOT, run, tiny_config, tiny_root

from chipbench import harness, reference
from repro.serve.clock import FakeClock

closed = harness.load_generator(BENCH, "closed")


# ---------------------------------------------------------------------------
# the load generator, against an engine stand-in on a fake clock
# ---------------------------------------------------------------------------
class _Engine:
    """Serves requests one at a time in arrival order, ``service_s`` each,
    on a fake clock; waiting on a ticket moves the clock to its completion,
    and each ``sleep`` overshoots by ``overshoot_s``."""

    def __init__(self, service_s: float, overshoot_s: float = 0.0):
        self.clock = FakeClock(100.0)
        sleep = self.clock.sleep
        self.clock.sleep = lambda s: sleep(max(0.0, s) + overshoot_s)
        self.service_s = service_s
        self.free_at = 0.0
        self.log = []             # (submit time, outstanding after submit)

    def submit(self, tenant, model, image):
        now = self.clock.now()
        self.free_at = max(self.free_at, now) + self.service_s
        req = types.SimpleNamespace(status="done", done_t=self.free_at,
                                    result=image)
        clock = self.clock

        class _Ticket:
            request = req

            def done(self):
                return True

            def result(self, timeout=None):
                if req.done_t > clock.now():
                    clock.advance(req.done_t - clock.now())
                return req.result
        ticket = _Ticket()
        self.log.append((now, ticket))
        return ticket

    def outstanding_at_each_submit(self):
        return [sum(1 for s, t in self.log[:i + 1] if t.request.done_t > now)
                for i, (now, _) in enumerate(self.log)]


def test_closed_loop_keeps_n_outstanding():
    eng = _Engine(service_s=0.05)
    run_ = closed.drive(eng, "m", np.zeros((4, 1)), {"clients": 6}, 2.0, 0)
    counts = eng.outstanding_at_each_submit()
    assert max(counts) == 6 and counts[5:] == [6] * len(counts[5:])
    done = run_["counted"]
    # the window closes on the first completion at or after 2 s
    assert run_["t_close"] - run_["t0"] == pytest.approx(2.0, abs=0.05)
    assert len(done) == pytest.approx(2.0 / 0.05, abs=1)
    assert all(r["done"] <= run_["t_close"] for r in done)


def _served(n_batches: int, size: int = 8) -> list:
    return [(8 * b + s, None, float(b)) for b in range(n_batches)
            for s in range(size)]


@pytest.mark.parametrize("sample,batches", [(32, 4), (30, 4), (8, 1), (200, 6)])
def test_the_check_reads_whole_batches_drawn_from_the_seed(
        monkeypatch, sample, batches):
    seen = []

    def forward(config, weights, x):
        seen.append(len(x))
        return np.zeros((len(x), 1), np.int8)
    monkeypatch.setattr(reference, "forward", forward)
    served = [(i, np.zeros(1, np.int8), b) for i, _, b in _served(6)]
    r = harness.check_outputs({"check_sample": sample}, {}, np.zeros((64, 1, 1)),
                              served, seed=2**31 + 1)
    assert seen == [8] * batches                  # every slot of a batch
    assert r == {"checked": 8 * batches, "max_abs_diff": 0, "mismatched": 0}


def test_an_altered_slot_is_caught_in_every_run(monkeypatch):
    """One slot of every batch wrong: the check reads whole batches, so no
    seed misses it."""
    monkeypatch.setattr(reference, "forward",
                        lambda c, w, x: np.zeros((len(x), 1), np.int8))
    served = [(i, np.full(1, int(i % 8 == 5), np.int8), b)
              for i, _, b in _served(22)]
    for seed in range(2**31, 2**31 + 40):
        r = harness.check_outputs({"check_sample": 32}, {},
                                  np.zeros((200, 1, 1)), served, seed)
        assert r["mismatched"] == 4 and r["max_abs_diff"] == 1


# ---------------------------------------------------------------------------
# the reference against the program, and the control
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("network", ["resnet18", "mobilenet"])
def test_reference_matches_the_program(network):
    config = tiny_config(f"{network}-tiny", network)
    weights = harness.make_weights(config, 7)
    images = harness.make_images(config, 7, n=3)
    model = harness.build_model(config, weights)
    ref = reference.forward(config, weights, images[:, 0])
    for i in range(3):
        np.testing.assert_array_equal(model.run_single(images[i]),
                                      ref[i:i + 1])
    assert np.abs(ref.astype(int)).max() > 8          # the output has spread


@pytest.mark.parametrize("network", ["resnet18", "mobilenet"])
def test_the_int4_control_comes_out_not_correct(network):
    from chipbench.control import control_readings
    r = control_readings(tiny_config(f"{network}-tiny", network), 2**31 + 3,
                         requests=24)
    assert r["checked"] == 16                          # two whole batches
    assert r["max_abs_diff"] > 0 and r["mismatched"] > 0


def test_the_graph_and_the_configuration_file_must_agree():
    config = tiny_config("resnet18-tiny", "resnet18")
    weights = harness.make_weights(config, 1)
    config["layers"][0]["post_op"] = "relu_shift"
    with pytest.raises(ValueError, match="differs from the configuration"):
        harness.build_model(config, weights)


# ---------------------------------------------------------------------------
# whole runs on the CPU, and the faults that must fail them
# ---------------------------------------------------------------------------
class _Fault:
    """The jax backend with the timed path broken underneath."""

    def __init__(self, kind):
        from repro.vta.backend import get_backend
        self.inner = get_backend("jax")
        self.name = "jax"
        self.kind = kind

    def run_batched(self, prog, hw, *, shared, batched):
        out = self.inner.run_batched(prog, hw, shared=shared, batched=batched)
        if self.kind == "unchanged":       # returns its state unchanged
            return {k: np.array(batched[k]) for k in out}
        if self.kind == "half_batch":      # half the batch left out
            n = next(iter(out.values())).shape[0]
            return {k: np.concatenate([v[:n // 2]] * 2 + [v[:n % 2]])
                    for k, v in out.items()}
        # one slot's answer altered where it is produced
        out = {k: np.array(v) for k, v in out.items()}
        for v in out.values():
            v.reshape(len(v), -1)[len(v) // 2, 0] += 1
        return out


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"), {
        "r.backlog": ("resnet18", "backlog"),
        "m.backlog": ("mobilenet", "backlog")})


@pytest.mark.parametrize("workload", ["r.backlog", "m.backlog"])
def test_a_sound_run_is_correct_and_reports_its_metrics(root, workload):
    res = run(root, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert all(c == {"value": 0, "limit": 0} for c in res["checks"].values())
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["metrics"]["images_per_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_a_broken_timed_path_makes_correct_false(root, fault):
    res = run(root, "r.backlog", backend=_Fault(fault))
    assert not res["correct"]
    assert res["checks"]["max_abs_diff"]["value"] > 0


def test_a_traced_run_reports_the_per_layer_metrics(root):
    res = run(root, "r.backlog", trace=True)
    assert res["correct"]
    m = res["metrics"]
    assert m["launches_per_batch"]["value"] > 0
    assert m["upload_mb_per_batch"]["value"] > 0
    assert 0 < m["mfu_int8"]["value"] < 100
    assert "setup_s" not in m
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_the_traced_run_profiles_whole_batches_from_the_window_start(
        root, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_S", 0.5)
    res = run(root, "r.backlog", trace=True, seconds=4.0)
    assert res["correct"]
    assert 0.5 <= res["device"]["window_s"] < 4.0


def test_new_configuration_traffic_and_metric_are_found_by_name(root, tmp_path):
    """A later cell adds only files and entries: a configuration, a mix,
    a generator and a metric reader, each under its own name."""
    new = tiny_root(tmp_path, {"x.trickle": ("mobilenet", "trickle")})
    (new / "chipbench/traffic/trickle.json").write_text(json.dumps(
        {"generator": "one_by_one", "clients": 2, "buckets": [2]}))
    # a generator of its own: the closed loop with one client at a time
    (new / "chipbench/generators/one_by_one.py").write_text(
        "from chipbench.generators.closed import make_engine, drive as _d\n"
        "def drive(engine, key, images, traffic, *a, **k):\n"
        "    return _d(engine, key, images, dict(traffic, clients=1), *a, **k)\n")
    (new / "chipbench/metrics/requests_sent.py").write_text(
        "def read(rec):\n    return len(rec['requests'])\n")
    spec = json.loads((new / "BENCHMARK.json").read_text())
    spec["end_to_end"].append({"name": "requests_sent", "unit": "requests",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock"})
    (new / "BENCHMARK.json").write_text(json.dumps(spec))
    res = run(new, "x.trickle")
    assert res["correct"]
    assert res["metrics"]["requests_sent"]["value"] >= 2


def test_the_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                          "resnet18-full.backlog", "--seed", "1",
                          "--seconds", "1", "--trace", "0"], cwd=ROOT,
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 2
    assert res.stdout == ""
    assert "accelerator" in res.stderr
