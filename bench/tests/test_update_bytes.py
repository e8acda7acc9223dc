"""``update_roofline.kv`` counts Algorithm 1's bytes from the harness's own
record of rows written: that record's stripes are the store's dirty
stripes."""
import numpy as np

from bench import harness
from bench.runners.kv_region import KvRegion


def test_harness_stripes_equal_store_dirty_stats():
    cell = harness.load_cell("kv-ycsb-a")
    cell.traffic["batches_ahead"] = 0
    r = KvRegion(cell, 2 ** 33 + 5, harness.Spans(), records=2048)
    r.setup(warm_batches=0)
    r.spans.recording = True
    passes = 0
    for _ in range(20):
        r.one_batch()
        r.red = r.store.settle(r.red, {"heap": r.heap}, step=r.batch_i - 1)
        got = int(r.store.dirty_stats(r.red)["heap"]["vulnerable_stripes"])
        assert got == int(r.dirty_stripes.sum())
        passes += got == 0
    assert passes >= 2          # passes ran and reset the record
    per = 4 * 4096 + 4096 + 4 * 4
    assert r.work()["update_bytes"] == r.counters["pass_stripes"] * per
    assert r.counters["pass_stripes"] > 0
