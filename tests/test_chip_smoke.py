"""chip_smoke.py's phases at tiny sizes on the CPU, kernels interpreted.

``main`` insists on a TPU; these tests steer the phase functions directly
so the script cannot rot between chip runs.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.configs import get_smoke
from subproc import run_snippet

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_region_phase_tiny():
    facts = chip_smoke.region_phase(rows=2048, steps=12,
                                    patrol_bytes=64 * 4096, interpret=True)
    assert facts["patrol_probes_ready"] + facts["patrol_probes_forced"] > 0


def test_serve_phase_tiny():
    facts = chip_smoke.serve_phase(get_smoke("olmo-1b"), batch=2,
                                   prompt_len=16, gen=6)
    assert facts["gen"] == 6


def test_train_phase_tiny():
    facts = chip_smoke.train_phase(get_smoke("olmo-1b"), steps=3, seq=32,
                                   batch=2)
    assert facts["steps"] == 3


def test_sharded_phase_tiny():
    run_snippet(f"""
        import sys
        sys.path.insert(0, {os.path.abspath(ROOT)!r})
        import chip_smoke
        chip_smoke.sharded_phase(rows=1024, steps=8, patrol_bytes=16 * 4096)
        print("SHARDED_OK")
    """, "SHARDED_OK", devices=4)


def test_main_refuses_without_tpu():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
