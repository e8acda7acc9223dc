"""A training job whose parameters and AdamW moments the store protects:
``Trainer.run`` (the jitted train step with ``on_write`` inside, then
``ProtectedStore.tick``) over synthetic token batches.

Set-up builds one Trainer and its state from the seed, drives it through
the checked steps with the window's own call and feed (recording each
step's loss, the first gradient as AdamW's first moment holds it, and the
parameters' change after the checked steps), warms the rest, and hands the
same Trainer and state to the window.

Correctness, after the window:

* ``stale_params``: after ``settle``, parameter checksums, parity and meta
  checksums that disagree with the plain reference (sync protection);
* ``stale_flushed``: after ``flush``, the same over every protected leaf;
* with the program's state freed, the plain float32 reference runs the
  checked steps from the same weights and batches: ``loss_gap`` (worst
  step's relative loss gap), ``gnorm_gap`` (the first step's global
  gradient norm), ``grad_gap`` and ``change_gap`` (worst leaf's gap of
  norms, against the larger of that leaf's and the median leaf's reference
  norm; leaves whose reference gradient is under a thousandth of the
  median leaf's are left out of the change).
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench.generator import LmBatches
from bench.reference import olmo as ref_olmo
from bench.reference import redundancy as ref_red
from bench.runners.kv_region import Check


def model_dict(cfg: dict) -> dict:
    if not (cfg["tie_word_embeddings"]
            and cfg["optimizer"]["lazy_embedding_rows"]):
        raise ValueError("the reference ties the head and keeps lazy rows")
    return {"vocab_size": int(cfg["vocab_size"]),
            "rope_theta": float(cfg["rope_theta"])}


def opt_dict(cfg: dict) -> dict:
    o = cfg["optimizer"]
    return {"lr": float(o["lr"]), "beta1": float(o["beta1"]),
            "beta2": float(o["beta2"]), "eps": float(o["eps"]),
            "weight_decay": float(o["weight_decay"]),
            "clip_norm": float(o["clip_norm"]),
            "param_dtype": cfg["param_dtype"]}


def init_std(path: str, shape, cfg: dict) -> float:
    """Fan-in scaled normal for matmul weights, 0.02 for the embedding
    table (which is also the output head)."""
    if path == "embed":
        return 0.02
    fan_in = int(cfg["intermediate_size"]) if path.endswith("ffn/wo") \
        else int(cfg["hidden_size"])
    return fan_in ** -0.5


def make_params_fn(struct: Any, cfg: dict, seed: int):
    """One jitted call that makes every parameter of the model's tree
    (``struct``, from ``jax.eval_shape``) from the seed, in the dtype it is
    trained in."""
    import jax
    import jax.numpy as jnp
    key_int = int(np.random.default_rng(seed).integers(2 ** 31))
    flat, treedef = jax.tree_util.tree_flatten_with_path(struct)
    paths = ["/".join(str(getattr(k, "key", k)) for k in kp) for kp, _ in flat]
    index = {p: i for i, p in enumerate(sorted(paths))}

    def make():
        key = jax.random.PRNGKey(key_int)
        vals = []
        for p, (_, st) in zip(paths, flat):
            std = init_std(p, st.shape, cfg)
            vals.append((jax.random.normal(jax.random.fold_in(key, index[p]),
                                           st.shape, jnp.float32) * std
                         ).astype(st.dtype))
        return jax.tree_util.tree_unflatten(treedef, vals)

    return jax.jit(make)


class Feed:
    """The window's data source: ``get(step)`` -> device batch, inside the
    harness's ``data`` span."""

    def __init__(self, batches: LmBatches, spans):
        self.batches = batches
        self.spans = spans

    def get(self, step: int):
        import jax.numpy as jnp
        with self.spans("data"):
            return {k: jnp.asarray(v)
                    for k, v in self.batches.numpy(step).items()}


def leaf_norms(tree_flat):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(tree_flat)


def gap(prog: Dict[str, float], ref: Dict[str, float],
        keep: Optional[set] = None) -> float:
    """Worst leaf's |prog - ref| over the larger of the leaf's and the
    median leaf's reference norm."""
    keys = sorted(k for k in ref if keep is None or k in keep)
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


class TrainRun:
    def __init__(self, cell, seed: int, spans, model_overrides=None,
                 control: bool = False):
        self.cell = cell
        self.cfg = dict(cell.config, **(model_overrides or {}))
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.spans = spans
        self.control = control
        self.counters: Dict[str, Any] = {}

    # ---------------------------------------------------------------- set-up
    def model_config(self):
        from repro.models.config import ModelConfig
        c = self.cfg
        return ModelConfig(
            name="olmo-1b", family="dense",
            n_layers=int(c["num_hidden_layers"]),
            d_model=int(c["hidden_size"]),
            n_heads=int(c["num_attention_heads"]),
            n_kv_heads=int(c["num_key_value_heads"]),
            d_ff=int(c["intermediate_size"]),
            vocab_size=int(c["vocab_size"]), norm="nonparam_ln",
            activation="swiglu", rope_theta=float(c["rope_theta"]),
            tie_embeddings=bool(c["tie_word_embeddings"]),
            param_dtype=c["param_dtype"], moment_dtype=c["moment_dtype"],
            remat=c["activation_checkpointing"])

    def make_trainer(self):
        from repro.train import Trainer
        return Trainer(model=self.model, opt=self.opt, store=self.store,
                       scrub_period_steps=int(
                           self.cfg["protection"]["scrub_period_steps"]))

    def build_model(self, mcfg):
        from repro.models import build_model
        return build_model(mcfg)

    def setup(self) -> None:
        import jax
        from repro.core import ProtectedStore, RedundancyPolicy
        from repro.optim import AdamW
        from repro.train.state import (TrainState, protected_leaves,
                                       protected_structs)

        c, o, prot = self.cfg, opt_dict(self.cfg), self.cfg["protection"]
        mcfg = self.model_config()
        self.model = self.build_model(mcfg)
        lr = o["lr"]
        self.opt = AdamW(lr=lambda count: lr, b1=o["beta1"], b2=o["beta2"],
                         eps=o["eps"], weight_decay=o["weight_decay"],
                         clip_norm=o["clip_norm"],
                         moment_dtype=c["moment_dtype"])
        struct = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.make_params = make_params_fn(struct, c, self.seed)
        params = self.make_params()
        opt_state = jax.jit(self.opt.init)(params)
        policy = RedundancyPolicy.from_spec(
            prot["policy"], period_steps=int(prot["period_steps"]),
            scrub_period_steps=int(prot["scrub_period_steps"]),
            max_vulnerable_steps=int(prot["max_vulnerable_steps"]),
            lanes_per_block=int(prot["lanes_per_block"]),
            stripe_data_blocks=int(prot["stripe_data_blocks"]))
        self.store = ProtectedStore(policy).attach(
            protected_structs(params, opt_state))
        self.trainer = self.make_trainer()
        red = self.store.init(protected_leaves(params, opt_state))
        self.state = TrainState.create(params, opt_state, red)
        tick = self.store.tick

        def timed_tick(*a, **kw):
            with self.spans("tick"):
                return tick(*a, **kw)

        self.store.tick = timed_tick
        self.feed = Feed(LmBatches(self.traffic, int(c["vocab_size"]),
                                   self.seed), self.spans)
        self.tokens_per_step = self.feed.batches.batch * self.feed.batches.seq

        # The checked steps, through the window's own call and feed.
        self.prog = {"loss": [], "gnorm": []}
        checked = int(self.traffic["checked_steps"])
        self.state = self.run_steps(1, record=True)
        b1 = o["beta1"]
        self.prog["grad"] = {k: float(v) / (1 - b1) for k, v in
                             leaf_norms(ref_olmo.flatten(
                                 self.state.opt["m"])).items()}
        self.state = self.run_steps(checked - 1, record=True)
        p0 = self.make_params()
        self.prog["change"] = {k: float(v) for k, v in leaf_norms(
            jax.tree.map(lambda a, b: a.astype("float32") - b.astype(
                "float32"), ref_olmo.flatten(self.state.params),
                ref_olmo.flatten(p0))).items()}
        del p0
        warm = int(self.traffic["warm_steps"]) - checked
        self.state = self.run_steps(max(0, warm))
        jax.block_until_ready(self.state)

    def run_steps(self, n: int, record: bool = False):
        def on_step(st, metrics):
            if record:
                self.prog["loss"].append(float(metrics["loss"]))
                self.prog["gnorm"].append(float(metrics["grad_norm"]))
        return self.trainer.run(self.state, self.feed, n, on_step=on_step)

    # ---------------------------------------------------------------- window
    def window(self, seconds: float) -> Dict[str, float]:
        self.counters = {}
        t0 = time.perf_counter()
        end, steps = t0 + seconds, 0
        while time.perf_counter() < end:
            self.state = self.run_steps(1)
            steps += 1
        elapsed = time.perf_counter() - t0
        self.counters.update(steps=steps, window_s=elapsed,
                             tokens=steps * self.tokens_per_step)
        ticks = self.spans.durations("tick")
        if ticks:
            self.counters["tick_max_ms"] = max(ticks) * 1e3
        return {"train_step_ms": elapsed / steps * 1e3}

    def model_flops_per_token(self) -> float:
        """6 x matmul parameters + 12 x layers x d_model x seq (PaLM,
        appendix B); the embedding lookup is not a matmul."""
        c = self.cfg
        d, f, L = int(c["hidden_size"]), int(c["intermediate_size"]), \
            int(c["num_hidden_layers"])
        n = L * (4 * d * d + 3 * d * f) + d * int(c["vocab_size"])
        return 6.0 * n + 12.0 * L * d * self.feed.batches.seq

    def work(self) -> Dict[str, float]:
        return {"model_flops": self.counters.get("tokens", 0)
                * self.model_flops_per_token()}

    def attempted(self) -> int:
        return int(self.counters.get("steps", 0))

    def failed(self) -> int:
        return 0

    def close(self) -> None:
        self.state = self.trainer = self.store = None

    # ----------------------------------------------------------- correctness
    def check(self) -> List[Check]:
        import jax
        limits = self.cfg["limits"]
        self.state = self.trainer.settle(self.state)
        stale_params = self.compare_redundancy("params/")
        self.state = self.trainer.flush(self.state)
        stale_flushed = self.compare_redundancy("")
        jax.block_until_ready(self.state)
        self.close()
        gc.collect()
        gaps = self.training_gaps()
        inf = float("inf")
        return [Check("stale_params", stale_params, 0),
                Check("stale_flushed", stale_flushed, 0)] + [
            Check(k, v, limits.get(k) if limits.get(k) is not None else inf)
            for k, v in gaps.items()]

    def compare_redundancy(self, prefix: str) -> int:
        """Checksums, parity rows and meta checksums of the protected leaves
        under ``prefix`` that disagree with the plain reference."""
        import jax
        import jax.numpy as jnp
        from repro.train.state import protected_leaves
        prot = self.cfg["protection"]
        L, P = int(prot["lanes_per_block"]), int(prot["stripe_data_blocks"])
        leaves = protected_leaves(self.state.params, self.state.opt)
        bad = 0
        for path, leaf in leaves.items():
            if not path.startswith(prefix) or path not in self.state.red:
                continue
            r = self.state.red[path]
            shape, size = leaf.shape, leaf.dtype.itemsize

            @jax.jit
            def cmp(leaf, ck, par, meta):
                lanes = ref_red.as_lanes(jnp, leaf)
                c, p, m = ref_red.leaf_redundancy(jnp, lanes, shape, size, L, P)
                return (jnp.sum(c != ck) + jnp.sum(jnp.any(p != par, axis=1))
                        + (m != meta).astype(jnp.int32))

            bad += int(cmp(leaf, r.checksums, r.parity, r.meta_ck))
        return bad

    def reference_readings(self, precision: str) -> Dict[str, Any]:
        """The checked steps in the plain reference from the same weights
        and batches."""
        import jax
        import jax.numpy as jnp
        model, opt = model_dict(self.cfg), opt_dict(self.cfg)
        params = {k: v.astype(jnp.float32) for k, v in
                  ref_olmo.flatten(self.make_params()).items()}
        if precision == "low":
            params = {k: v.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                      for k, v in params.items()}
        p0 = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
        m = {k: jnp.zeros_like(v) for k, v in params.items()}
        v_ = {k: jnp.zeros_like(v) for k, v in params.items()}
        step = jax.jit(lambda p, m, v, c, t, l: ref_olmo.train_step(
            p, m, v, c, t, l, model, opt, precision), donate_argnums=(0, 1, 2))
        out = {"loss": [], "gnorm": []}
        for i in range(int(self.traffic["checked_steps"])):
            b = self.feed.batches.numpy(i)
            params, m, v_, loss, gn, clipped = step(
                params, m, v_, jnp.float32(i + 1), b["tokens"], b["labels"])
            out["loss"].append(float(loss))
            out["gnorm"].append(float(gn))
            if i == 0:
                out["grad"] = {k: float(x) for k, x in clipped.items()}
        out["change"] = {k: float(x) for k, x in leaf_norms(
            {k: params[k] - p0[k].astype(jnp.float32) for k in params}).items()}
        return out

    def training_gaps(self) -> Dict[str, float]:
        ref = self.reference_readings("float32")
        prog = self.prog
        if self.control:
            prog = self.reference_readings("low")
        return readings_gaps(prog, ref)


def readings_gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    med = float(np.median(list(ref["grad"].values())))
    keep = {k for k, g in ref["grad"].items() if g >= 1e-3 * med}
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["loss"], ref["loss"])),
        "gnorm_gap": abs(prog["gnorm"][0] - ref["gnorm"][0]) / ref["gnorm"][0],
        "grad_gap": gap(prog["grad"], ref["grad"]),
        "change_gap": gap(prog["change"], ref["change"], keep),
    }


def make(cell, seed: int, spans) -> TrainRun:
    return TrainRun(cell, seed, spans)
