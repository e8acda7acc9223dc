"""The training cell's whole run on the CPU at a small size, past the look
for a chip: a sound run is correct, and the control and the faults a
one-chip training cell can have (a step that returns its state unchanged,
half of the batch left out) read far above it and make ``correct`` false
under the configuration's limits."""
import dataclasses

import jax
import pytest

from bench import harness, run
from bench.peaks import peaks_for
from bench.runners.train_state import TrainRun

PEAKS = peaks_for("TPU v5 lite")
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, vocab_size=512)


class Small(TrainRun):
    def __init__(self, cell, seed, spans, control=False):
        super().__init__(cell, seed, spans, model_overrides=TINY,
                         control=control)


class Unchanged(Small):
    """Every step returns the state it was given (metrics still computed)."""
    def make_trainer(self):
        from repro.train.train_loop import make_train_step
        trainer = super().make_trainer()
        real = make_train_step(self.model, self.opt, None)
        trainer.train_step = jax.jit(lambda st, b: (st, real(st, b)[1]))
        return trainer


class HalfBatch(Small):
    """The loss, and so the gradient, is the mean over half of the batch."""
    def build_model(self, mcfg):
        model = super().build_model(mcfg)
        loss = model.loss

        @dataclasses.dataclass
        class Half(type(model)):
            def loss(self, params, batch):
                half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return loss(params, half)

        return Half(cfg=model.cfg, ctx=model.ctx)


GAPS = ("loss_gap", "gnorm_gap", "grad_gap", "change_gap")


def run_small(cls, control=False):
    cell = harness.make_cell("train-olmo-1b",
                             "bench/configs/olmo-1b-train-state.json",
                             "olmo-pretrain-2k", 1)
    cell.traffic.update(seq_len=64, batch=4)
    return run.run_cell(cell, 2 ** 37 + 1, 0.5, False, PEAKS,
                        make_run=lambda c, s, sp: cls(c, s, sp,
                                                      control=control))


@pytest.fixture(scope="module")
def sound():
    return run_small(Small)


def test_sound_run_correct(sound):
    assert sound["correct"], sound["checks"]
    checks = sound["checks"]
    assert checks["stale_params"]["value"] == 0
    assert checks["stale_flushed"]["value"] == 0
    assert "setup_s" in sound["metrics"]
    for k in GAPS:
        assert checks[k]["value"] < 0.05


@pytest.mark.parametrize("cls,control", [
    (Small, True), (Unchanged, False), (HalfBatch, False)])
def test_control_and_faults_incorrect(sound, cls, control):
    res = run_small(cls, control)
    ratio = max(res["checks"][k]["value"] / sound["checks"][k]["value"]
                for k in GAPS)
    assert ratio >= 10, res["checks"]
    assert not res["correct"], res["checks"]
