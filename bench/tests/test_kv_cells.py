"""A kv cell's whole run on the CPU at a small size, past the look for a
chip: sound runs are correct, and the control and every fault the cell can
have make ``correct`` false."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import harness, run
from bench.peaks import UnknownDevice, peaks_for
from bench.runners.kv_region import KvRegion

ROOT = pathlib.Path(__file__).resolve().parents[2]
PEAKS = peaks_for("TPU v5 lite")


class Small(KvRegion):
    READ_SAMPLE = 1

    def __init__(self, cell, seed, spans, control=False):
        super().__init__(cell, seed, spans, records=4096, control=control)


class Unchanged(Small):
    """The batch returns the heap as it found it."""
    def shard_body(self, heap, rk, wk, wf, b):
        _, reads, mask = super().shard_body(heap, rk, wk, wf, b)
        return heap, reads, mask


class HalfBatch(Small):
    """Only the first half of the batch's updates land."""
    def shard_body(self, heap, rk, wk, wf, b):
        h = wk.shape[0] // 2
        new, reads, _ = super().shard_body(heap, rk, wk[:h], wf[:h], b)
        _, _, mask = super().shard_body(heap, rk, wk, wf, b)
        return new, reads, mask


class AlteredAnswer(Small):
    """One word of every batch's reads is altered where it is produced."""
    def shard_body(self, heap, rk, wk, wf, b):
        heap, reads, mask = super().shard_body(heap, rk, wk, wf, b)
        return heap, reads.at[0, 0].add(1), mask


class NoMarking(Small):
    """The batch's writes are never marked dirty."""
    def make_step(self):
        self.store.on_write = lambda red, events=None, **kw: red
        return super().make_step()


MIXES = {"kv-ycsb-a": "ycsb-a", "kv-ycsb-c": "ycsb-c"}


def run_small(cls, workload="kv-ycsb-a", trace=False, control=False):
    cell = harness.make_cell(workload, "bench/configs/ycsb-kv-2g.json",
                             MIXES[workload], 1)
    cell.traffic["batches_ahead"] = 0
    return run.run_cell(cell, 2 ** 35 + 11, 0.6, trace, PEAKS,
                        make_run=lambda c, s, sp: cls(c, s, sp,
                                                      control=control))


def test_sound_run_correct_with_end_to_end_metrics():
    res = run_small(Small)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"kv_ops_per_s", "kv_p99_ms", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    json.dumps(res)


def test_traced_run_reports_its_layers():
    res = run_small(Small, trace=True)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {
        "tick_host_ms.kv", "update_device_ms.kv", "update_roofline.kv",
        "patrol_device_ms.kv", "idle_share.kv"}
    assert 0 < res["metrics"]["update_roofline.kv"]["value"] < 100
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("control", [False, True])
def test_read_only_mix(control):
    """YCSB-C writes nothing: only the detection guarantee can break."""
    res = run_small(Small, "kv-ycsb-c", control=control)
    assert res["correct"] is not control, res["checks"]
    assert res["checks"]["flip_missed"]["value"] == int(control)


def test_control_breaks_freshness_and_detection():
    res = run_small(Small, control=True)
    assert not res["correct"]
    assert res["checks"]["stale_old"]["value"] > 0
    assert res["checks"]["flip_missed"]["value"] == 1


@pytest.mark.parametrize("fault,check", [
    (Unchanged, "rows_wrong"), (HalfBatch, "rows_wrong"),
    (AlteredAnswer, "reads_wrong"), (NoMarking, "stale_flushed")])
def test_fault_makes_run_incorrect(fault, check):
    res = run_small(fault)
    assert not res["correct"]
    assert res["checks"][check]["value"] > 0


def test_no_chip_no_result(tmp_path):
    """Without a TPU, and in a checkout holding only the benchmark's files,
    the command exits non-zero and prints nothing on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload", "kv-ycsb-a",
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env.pop("PYTHONPATH", None)
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")
