"""Train-step factory + host Trainer, both driven by a ProtectedStore.

The redundancy lifecycle (dirty marking vs sync diff per leaf group,
Algorithm-1 scheduling, scrub double-check, straggler back-off, preemption
flush) lives behind :class:`repro.core.ProtectedStore`; this module only
wires the model/optimizer step into it.  The legacy
``Trainer(engine=..., mode=...)`` signature still works for one release via
the deprecation shim.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.core.engine import RedundancyEngine
from repro.core.store import ProtectedStore, as_store
from repro.optim.adamw import AdamW
from .state import TrainState, protected_leaves, replace_protected


def make_train_step(model, opt: AdamW,
                    store: Optional[Any] = None,
                    mode: Optional[str] = None,
                    accum_steps: int = 1) -> Callable:
    """accum_steps > 1 microbatches the global batch (gradient accumulation):
    activation memory scales down by the accumulation factor; gradients
    accumulate in fp32 across microbatches inside one jitted step.

    ``store`` is a ProtectedStore (or, deprecated, a RedundancyEngine paired
    with ``mode``)."""
    store = as_store(store, mode, caller="make_train_step")

    def grads_of(params, batch):
        if accum_steps == 1:
            return jax.value_and_grad(model.loss, has_aux=True)(params, batch)

        mb = jax.tree.map(
            lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                                *x.shape[1:]), batch)

        def mb_step(carry, microbatch):
            gacc, loss_acc, aux_acc = carry
            (loss, aux), g = jax.value_and_grad(
                model.loss, has_aux=True)(params, microbatch)
            gacc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gacc, g)
            aux_acc = {
                "ce": aux_acc["ce"] + aux["ce"],
                "aux_loss": aux_acc["aux_loss"] + aux["aux_loss"],
                "expert_counts": aux_acc["expert_counts"] + aux["expert_counts"],
                "logits_mean": aux_acc["logits_mean"] + aux["logits_mean"],
            }
            return (gacc, loss_acc + loss, aux_acc), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        aux0 = {"ce": jnp.float32(0), "aux_loss": jnp.float32(0),
                "expert_counts": jnp.zeros(
                    (model.cfg.n_groups, model.cfg.group_size,
                     max(model.cfg.n_experts, 1)), jnp.int32),
                "logits_mean": jnp.float32(0)}
        (gacc, loss_sum, aux_sum), _ = jax.lax.scan(
            mb_step, (g0, jnp.float32(0), aux0), mb,
            unroll=True if model.cfg.unroll_layers else 1)
        n = float(accum_steps)
        grads = jax.tree.map(lambda g: g / n, gacc)
        aux = {k: (v / n if k != "expert_counts" else v) for k, v in aux_sum.items()}
        return (loss_sum / n, aux), grads

    def train_step(state: TrainState, batch):
        (loss, aux), grads = grads_of(state.params, batch)
        if getattr(model.cfg, "opt_grad_barrier", False):
            # Keep the data-parallel gradient reduction on a bf16 wire: the
            # barrier stops XLA hoisting AdamW's f32 converts above the
            # all-reduce/reduce-scatter (§Perf).
            grads = jax.lax.optimization_barrier(grads)
        sparse_events = model.dirty_events_train(batch, aux)
        row_masks = {k: v for k, v in sparse_events.items()
                     if not isinstance(v, str)}
        new_params, new_opt, gnorm = opt.update(
            grads, state.opt, state.params, row_masks)
        red = state.red
        if store is not None and store.protects:
            old = new = None
            if store.has_sync:
                old = protected_leaves(state.params, state.opt)
                new = protected_leaves(new_params, new_opt)
            red = store.on_write(red, events=store.expand_events(sparse_events),
                                 old=old, new=new)
        metrics = {"loss": loss, "ce": aux["ce"], "grad_norm": gnorm,
                   "aux_loss": aux["aux_loss"]}
        return TrainState(new_params, new_opt, red, state.step + 1), metrics

    return train_step


def make_redundancy_step(store) -> Callable:
    """Algorithm 1 over the protected state (the paper's background thread).

    ``store`` may be a ProtectedStore or a bare RedundancyEngine — both
    expose a traceable ``redundancy_step(leaves, red)``."""
    def redundancy_step(state: TrainState) -> TrainState:
        leaves = protected_leaves(state.params, state.opt)
        red = store.redundancy_step(leaves, state.red)
        return dataclasses.replace(state, red=red)
    return redundancy_step


@dataclasses.dataclass
class Trainer:
    """Host-side loop around ``store.tick``: periodic redundancy, scrubbing
    with double-check, preemption flush, straggler back-off with recovery —
    all owned by the ProtectedStore."""
    model: Any
    opt: AdamW
    store: Optional[ProtectedStore] = None
    engine: Optional[RedundancyEngine] = None      # deprecated: use store=
    mode: Optional[str] = None                     # deprecated: use store=
    period_steps: int = 8
    # None defers to the store's per-leaf policy; 0 disables scrubbing.
    scrub_period_steps: Optional[int] = None
    donate: bool = True

    def __post_init__(self):
        if self.store is None and self.engine is not None:
            self.store = as_store(self.engine, self.mode or "vilamb",
                                  period_steps=self.period_steps,
                                  scrub_period_steps=self.scrub_period_steps or 0,
                                  caller="Trainer")
        if self.store is not None and not self.store.protects:
            self.store = None
        donate = (0,) if self.donate else ()
        self.train_step = jax.jit(
            make_train_step(self.model, self.opt, self.store),
            donate_argnums=donate)
        self.redundancy_step = (
            jax.jit(make_redundancy_step(self.store), donate_argnums=donate)
            if self.store is not None else None)
        self.scrub_fn = ((lambda state: self.store.scrub(
            protected_leaves(state.params, state.opt), state.red))
            if self.store is not None else None)

    @property
    def corruption_alarms(self) -> int:
        return self.store.corruption_alarms if self.store is not None else 0

    def init_state(self, key) -> TrainState:
        params = self.model.init(key)
        opt_state = self.opt.init(params)
        red = {}
        if self.store is not None:
            red = self.store.init(protected_leaves(params, opt_state))
        return TrainState.create(params, opt_state, red)

    def scrub_check(self, state: TrainState) -> int:
        """Scrub with the paper's double-check (delegated to the store)."""
        if self.store is None:
            return 0
        return self.store.scrub_check(
            protected_leaves(state.params, state.opt), state.red)

    def flush(self, state: TrainState) -> TrainState:
        """Battery/preemption flush: force Algorithm 1 now (paper §3.3).

        Resolves any in-flight overlapped update first, so the result is
        bitwise-identical to the blocking path."""
        if self.store is None:
            return state
        red = self.store.flush(
            protected_leaves(state.params, state.opt), state.red,
            step=int(state.step))
        return dataclasses.replace(state, red=red)

    def settle(self, state: TrainState) -> TrainState:
        """Adopt in-flight overlapped redundancy results (no new pass).

        Call before handing ``state.red`` to code outside the store's
        lifecycle (custom verification, external persistence).  ``flush``
        and ``scrub_check`` settle on their own."""
        if self.store is None:
            return state
        red = self.store.settle(
            state.red, protected_leaves(state.params, state.opt))
        return dataclasses.replace(state, red=red)

    def run(self, state: TrainState, data, steps: int,
            log_every: int = 10, on_step=None) -> TrainState:
        scrub_period = self.scrub_period_steps
        for i in range(steps):
            t0 = time.perf_counter()
            batch = data.get(int(state.step))
            state, metrics = self.train_step(state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            if self.store is not None:
                st = state
                red, report = self.store.tick(
                    lambda: protected_leaves(st.params, st.opt), st.red,
                    int(st.step), step_time=dt, scrub_period=scrub_period)
                state = dataclasses.replace(state, red=red)
                if report.repaired:
                    # The scrub patroller repaired or rebuilt leaves this
                    # tick; fold them back so training continues on the
                    # corrected state.
                    lv = protected_leaves(state.params, state.opt)
                    lv.update(report.repaired)
                    state = replace_protected(state, lv)
            if on_step is not None:
                on_step(state, metrics)
        return state
