"""The trace reduction: interval arithmetic, a trace recorded on the CPU
here, and a small trace recorded on a TPU v5e (``data/``)."""
import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce as T

DATA = pathlib.Path(__file__).parent / "data"


def test_merge_and_clip():
    assert T.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_busy_modules_and_labelled_gaps():
    dev = T.Device(ops=[("jit_a", "op1", 10, 20), ("jit_a", "op2", 15, 25),
                        ("jit_b", "op3", 40, 50), ("jit_b", "op4", 95, 120)],
                   modules=[])
    tr = T.Trace(window=(0, 100), devices={"tpu:0": dev},
                 host=[("window", 0, 100), ("tick", 25, 38),
                       ("read", 50, 60), ("tick", 60, 90)])
    assert tr.busy("tpu:0") == [(10, 25), (40, 50), (95, 100)]
    assert tr.busy_s("tpu:0") == pytest.approx(30e-9)
    assert tr.module_s("tpu:0") == pytest.approx({"jit_a": 15e-9,
                                                  "jit_b": 15e-9})
    gaps = tr.idle_gaps("tpu:0")
    assert [g[0] for g in gaps] == ["none", "tick", "tick"]
    assert [g[1] for g in gaps] == pytest.approx([10e-9, 15e-9, 45e-9])


def test_module_name_drops_run_id():
    assert T.module_name("jit_many(17)") == "jit_many"
    assert T.module_name("jit_local") == "jit_local"


def test_cpu_trace_recorded_here(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.tick"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    tr = T.reduce_file(T.find_xplane(str(tmp_path)))
    assert list(tr.devices) == ["cpu:0"]
    assert 0.06 < tr.window_s < 5.0
    busy = tr.busy_s("cpu:0")
    assert 0 < busy < tr.window_s
    mods = tr.module_s("cpu:0")
    assert any(m.startswith("jit_") for m in mods)
    assert sum(mods.values()) >= busy * 0.999
    ticks = sum(s for label, s in tr.idle_gaps("cpu:0") if label == "tick")
    assert ticks >= 0.055


def test_v5e_trace_recorded_on_the_chip():
    """Three runs of two programs inside ``bench.write`` spans and after
    ``bench.tick`` spans of a 2 ms sleep, traced on one TPU v5e."""
    tr = T.reduce_file(str(DATA / "v5e_small.xplane.pb"))
    assert list(tr.devices) == ["tpu:0"]
    assert tr.window_s == pytest.approx(0.013380179)
    dev = tr.devices["tpu:0"]
    assert len(dev.modules) == 6 and len(dev.ops) == 24
    # Clock alignment: every program run now starts after its launch,
    # so each 2048x2048 matmul lands inside its write span.
    writes = [(s, e) for n, s, e in tr.host if n == "write"]
    assert all(any(ws <= s <= we for ws, we in writes)
               for m, s, e in dev.modules[::2])
    assert tr.busy_s("tpu:0") == pytest.approx(0.000849881)
    assert tr.module_s("tpu:0") == pytest.approx({"jit__lambda": 0.000849881})
    gaps = {}
    for label, s in tr.idle_gaps("tpu:0"):
        gaps[label] = gaps.get(label, 0.0) + s
    assert gaps == pytest.approx({"write": 0.001400748, "tick": 0.010585589,
                                  "none": 0.000543961})
