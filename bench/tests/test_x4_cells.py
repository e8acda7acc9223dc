"""The four-chip kv cell on four virtual CPU devices: a sound run is
correct, and leaving out the exchange between chips (the sum of the
shards' reads) makes it incorrect."""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]

CHILD = r'''
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness, run
from bench.peaks import peaks_for
from bench.runners.kv_region import KvRegion

class Small(KvRegion):
    READ_SAMPLE = 1
    def __init__(self, cell, seed, spans):
        super().__init__(cell, seed, spans, records=8192)

class NoExchange(Small):
    def shard_body(self, heap, rk, wk, wf, b):
        mesh, self.mesh = self.mesh, None
        try:
            import jax
            off = jax.lax.axis_index(("data", "model")) * self.local_rows
            off = off * self.per_page
            return super().shard_body(heap, rk - off, wk - off, wf, b)
        finally:
            self.mesh = mesh

out = {{}}
for name, cls in (("sound", Small), ("no_exchange", NoExchange)):
    cell = harness.make_cell("kv-ycsb-a-x4", "bench/configs/ycsb-kv-1g-x4.json",
                             "ycsb-a-x4", 4)
    cell.traffic["batches_ahead"] = 0
    res = run.run_cell(cell, 2 ** 36 + 3, 0.6, False, peaks_for("TPU v5 lite"),
                       make_run=lambda c, s, sp: cls(c, s, sp))
    out[name] = res
print("RESULT " + json.dumps(out))
'''


def test_four_shards_sound_and_without_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=str(ROOT), src=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT ")]
    assert p.returncode == 0 and line, p.stderr[-3000:]
    out = json.loads(line[0][len("RESULT "):])
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert out["sound"]["device"]["count"] == 4
    assert not out["no_exchange"]["correct"]
    assert out["no_exchange"]["checks"]["reads_wrong"]["value"] > 0
