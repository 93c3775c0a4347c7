"""The reader ``alu_indexed_mb_per_batch`` on the CPU: a traced run of each
tiny cell reads the ALU part of the program's indexed-bytes counter per
served batch, and a program without the counter reports no value."""
import pytest

from _chipbench_fixtures import run, tiny_root

from repro.vta import fsim_jax

# the ALU part of one batch of 8 of each tiny model, in MB
# (tests/test_indexed_bytes.py checks the counter against the traced chunks)
ALU_MB_PER_BATCH = {"r.backlog": 0.458752, "m.backlog": 0.372256}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"), {
        "r.backlog": ("resnet18", "backlog"),
        "m.backlog": ("mobilenet", "backlog")})


@pytest.mark.parametrize("workload", sorted(ALU_MB_PER_BATCH))
def test_a_traced_run_reads_the_alu_part_per_batch(root, workload):
    res = run(root, workload, trace=True)
    assert res["correct"], res["checks"]
    got = res["metrics"]["alu_indexed_mb_per_batch"]
    assert got["unit"] == "MB"
    assert got["value"] == pytest.approx(ALU_MB_PER_BATCH[workload],
                                         rel=1e-12)


def test_a_program_without_the_counter_reports_no_value(root, monkeypatch):
    monkeypatch.delattr(fsim_jax, "indexed_bytes_by_class")
    res = run(root, "m.backlog", trace=True)
    assert res["correct"], res["checks"]
    assert "alu_indexed_mb_per_batch" not in res["metrics"]
    assert res["metrics"]["launches_per_batch"]["value"] > 0
