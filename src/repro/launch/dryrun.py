import os
# A CPU tool: 512 virtual host devices stand in for the production mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM, or unsupported collectives fail here.
Results (memory analysis, HLO FLOPs/bytes, collective schedule, roofline
terms) are cached as JSON under results/dryrun/ for EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, list_archs
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_decode_setup, build_prefill_setup, build_train_setup
from repro.models.config import SHAPES

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def cell_applicability(cfg, shape) -> str:
    """'' if runnable, else the documented skip reason."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return "SKIP(full-attention arch; 500k decode requires sub-quadratic mixer)"
    return ""


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS per assignment: 6*N*D train (N_active for MoE), 2*N*D fwd."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def _spmd_dump_dir():
    import tempfile
    return tempfile.mkdtemp(prefix="spmd_dump_")


def _semantic_collectives(dump_dir):
    """Collective stats from the after-SPMD-partitioning dump.

    The CPU backend promotes bf16 compute to f32 during optimization, so the
    final module's collective shapes double every bf16 wire; the
    partitioner-output module keeps semantic dtypes (what a TPU would move).
    """
    import glob as _glob
    files = sorted(_glob.glob(f"{dump_dir}/*after_spmd-partitioning*"))
    best = None
    for f in files:  # take the largest train_step-ish module
        sz = pathlib.Path(f).stat().st_size
        if best is None or sz > best[0]:
            best = (sz, f)
    if not best:
        return None
    return H.parse_collectives(pathlib.Path(best[1]).read_text())


def _compile_cell(cfg, shape, mesh, mode, accum, return_setup=False):
    """Lower + compile one step function; returns (compiled, fallback_log)."""
    if shape.kind == "train":
        setup = build_train_setup(cfg, shape, mesh, mode=mode, accum_steps=accum)
        fn = jax.jit(
            setup.step_fn,
            in_shardings=(setup.state_sharding, setup.batch_sharding),
            out_shardings=(setup.state_sharding, None),
            donate_argnums=(0,))
        lowered = fn.lower(setup.state_struct, setup.batch_struct)
    elif shape.kind == "prefill":
        setup = build_prefill_setup(cfg, shape, mesh)
        fn = jax.jit(setup.step_fn, in_shardings=setup.args_sharding,
                     out_shardings=setup.out_sharding)
        lowered = fn.lower(*setup.args_struct)
    else:  # decode
        setup = build_decode_setup(cfg, shape, mesh, mode=mode)
        fn = jax.jit(
            setup.step_fn,
            in_shardings=setup.args_sharding,
            donate_argnums=(1, 2))
        lowered = fn.lower(*setup.args_struct)
    import shutil
    dump = _spmd_dump_dir()
    compiled = lowered.compile(compiler_options={
        "xla_dump_to": dump, "xla_dump_hlo_pass_re": "spmd-partitioning"})
    compiled._semantic_coll = _semantic_collectives(dump)  # type: ignore
    shutil.rmtree(dump, ignore_errors=True)
    if return_setup:
        return compiled, setup.fallback_log, setup
    return compiled, setup.fallback_log


def _costs(compiled):
    ca = H.cost_analysis_dict(compiled)
    coll = getattr(compiled, "_semantic_coll", None)
    if coll is None:
        coll = H.parse_collectives(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll.total_bytes,
            "coll_detail": coll.summary()}


def run_cell(arch: str, shape_name: str, multi_pod: bool, mode: str = "vilamb",
             out_dir: pathlib.Path = RESULTS, tag: str = "",
             cfg_override=None, accum: "int|None" = None,
             extrapolate: bool = True) -> dict:
    """One dry-run cell.

    Compile #1: full-scale with the layer scan (production artifact) —
      proves lower+compile succeeds and gives realistic memory analysis.
    Compiles #2+#3 (2-group and 4-group variants, scan unrolled): XLA cost
      analysis counts while bodies once, so the scanned artifact
      under-reports per-layer costs; the unrolled small variants give exact
      per-group FLOPs/bytes/collectives, extrapolated linearly to full depth
      (layers are structurally identical across groups).
    """
    import dataclasses as _dc
    cfg = cfg_override or get_arch(arch)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "mode": mode, "tag": tag, "status": "ok"}
    skip = cell_applicability(cfg, shape)
    if skip:
        rec["status"] = skip
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    if accum is None:
        from repro.launch.specs import default_accum
        accum = default_accum(cfg, shape, mesh)
    rec["accum_steps"] = accum

    with mesh:
        t0 = time.time()
        compiled, log, setup = _compile_cell(cfg, shape, mesh, mode, accum,
                                             return_setup=True)
        rec["compile_s"] = round(time.time() - t0, 1)
        rec["fallbacks"] = log
        rec["memory_analysis"] = H.memory_analysis_dict(compiled)
        rec["cost_analysis_scanned"] = _costs(compiled)
        try:
            from repro.launch.memory_model import analytic_hbm
            rec["hbm_model"] = analytic_hbm(cfg, shape, mesh, setup, mode, accum)
        except Exception as e:  # model must never break the dry-run
            rec["hbm_model"] = {"error": f"{type(e).__name__}: {e}"}

        G = cfg.n_groups
        gs = cfg.group_size
        if extrapolate and G > 2:
            t1 = time.time()
            c1 = _costs(_compile_cell(
                _dc.replace(cfg, n_layers=gs, unroll_layers=True),
                shape, mesh, mode, accum)[0])
            c2 = _costs(_compile_cell(
                _dc.replace(cfg, n_layers=2 * gs, unroll_layers=True),
                shape, mesh, mode, accum)[0])
            per_group = {k: (c2[k] - c1[k]) for k in ("flops", "bytes", "coll")}
            full = {k: c1[k] + (G - 1) * per_group[k] for k in per_group}
            rec["cost_extrapolation"] = {
                "g1": {k: c1[k] for k in per_group}, "g2": {k: c2[k] for k in per_group},
                "per_group": per_group, "extra_compile_s": round(time.time() - t1, 1),
                "coll_detail_g2": c2["coll_detail"],
            }
        else:
            # shallow model: unroll the real thing
            cu = _costs(_compile_cell(
                _dc.replace(cfg, unroll_layers=True), shape, mesh, mode, accum)[0])
            full = {k: cu[k] for k in ("flops", "bytes", "coll")}
            rec["cost_extrapolation"] = {"unrolled_exact": True,
                                         "coll_detail": cu["coll_detail"]}

    rec["collectives"] = rec["cost_analysis_scanned"]["coll_detail"]
    mf = model_flops(cfg, shape)
    rl = H.roofline_terms(
        flops_per_chip=full["flops"], bytes_per_chip=full["bytes"],
        coll_bytes_per_chip=full["coll"], chips=chips, model_flops=mf)
    rec["roofline"] = rl.as_dict()

    # HBM budget: analytic model gives the verdict (exact state bytes from
    # the real PartitionSpecs + working-set estimate); the CPU scheduler's
    # temp_size is recorded as a pessimistic upper bound (no TPU
    # memory-aware scheduling on the CPU backend).
    ma = rec["memory_analysis"]
    if ma:
        live = (ma.get("argument_size_in_bytes", 0)
                + ma.get("temp_size_in_bytes", 0)
                + ma.get("output_size_in_bytes", 0)
                - ma.get("alias_size_in_bytes", 0))
        rec["hbm_bytes_per_device_cpu_upper_bound"] = int(live)
    hm = rec.get("hbm_model", {})
    rec["hbm_bytes_per_device"] = int(hm.get("total", 0))
    rec["fits_16g"] = bool(hm.get("fits_16g_analytic", False))

    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{arch}__{shape_name}__{mesh_name}{('__' + tag) if tag else ''}.json"
    (out_dir / name).write_text(json.dumps(rec, indent=2, default=str))
    return rec


def run_redundancy_cell(arch: str, multi_pod: bool = False,
                        stripe: int = 4, lanes: int = 16384,
                        use_kernels: bool = False, dirty_frac: float = 1.0,
                        out_dir: pathlib.Path = RESULTS, tag: str = "red") -> dict:
    """Lower + compile Algorithm 1 itself over an arch's protected state.

    This is the paper's technique as its own roofline cell: memory-bound by
    construction, zero collectives (machine-local, §3.3). ``dirty_frac``
    scales the analytic amortized traffic; the compiled artifact is the
    full-pass (worst-case flush) cost.
    """
    import dataclasses as _dc
    import jax.numpy as jnp
    from repro.core.engine import RedundancyConfig, RedundancyEngine
    from repro.dist.sharding import param_specs
    from repro.common import flatten_dict
    from repro.launch.specs import make_ctx, tree_shardings
    from repro.models import build_model
    from repro.optim import AdamW, warmup_cosine
    from repro.train.state import protected_structs
    from repro.train.train_loop import make_redundancy_step
    from repro.train.state import TrainState

    cfg = get_arch(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    ctx = make_ctx(cfg, mesh)
    model = build_model(cfg, ctx)
    params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = AdamW(lr=warmup_cosine(3e-4, 100, 10000), moment_dtype=cfg.moment_dtype)
    opt_struct = jax.eval_shape(opt.init, params_struct)
    flat_p = flatten_dict(params_struct)
    p_specs, _ = param_specs(flat_p, ctx)
    prot = protected_structs(params_struct, opt_struct)
    prot_specs = {k: p_specs[k.partition("/")[2]] for k in prot}
    rcfg = RedundancyConfig(mode="vilamb", stripe_data_blocks=stripe,
                            lanes_per_block=lanes, use_kernels=use_kernels)
    engine = RedundancyEngine(prot, rcfg, mesh=mesh, specs=prot_specs)
    red_struct = engine.red_structs()
    red_shard = engine.red_shardings()

    from jax.sharding import NamedSharding, PartitionSpec as P
    p_shard = tree_shardings(params_struct, p_specs, mesh)
    rep = NamedSharding(mesh, P())
    state_struct = TrainState(params=params_struct, opt=opt_struct,
                              red=red_struct,
                              step=jax.ShapeDtypeStruct((), jnp.int32))
    state_shard = TrainState(params=p_shard,
                             opt={"m": p_shard, "v": p_shard, "count": rep},
                             red=red_shard, step=rep)
    fn = jax.jit(make_redundancy_step(engine),
                 in_shardings=(state_shard,), out_shardings=state_shard,
                 donate_argnums=(0,))
    t0 = time.time()
    with mesh:
        compiled = fn.lower(state_struct).compile()
    rec = {"arch": arch, "cell": "redundancy_step", "tag": tag,
           "stripe": stripe, "lanes_per_block": lanes,
           "compile_s": round(time.time() - t0, 1), "status": "ok"}
    ca = H.cost_analysis_dict(compiled)
    coll = H.parse_collectives(compiled.as_text())
    state_bytes = sum(
        int(np.prod(v.shape) or 1) * jnp.dtype(v.dtype).itemsize
        for v in prot.values()) / chips
    rl = H.roofline_terms(float(ca.get("flops", 0.0)),
                          float(ca.get("bytes accessed", 0.0)),
                          coll.total_bytes, chips, model_flops=0.0)
    rec["roofline"] = rl.as_dict()
    rec["collectives"] = coll.summary()
    rec["state_bytes_per_chip"] = int(state_bytes)
    # useful traffic = read dirty stripes once + write parity/checksums
    useful = state_bytes * dirty_frac * (1 + 1.0 / stripe)
    rec["useful_bytes_per_chip"] = int(useful)
    rec["memory_efficiency"] = useful / max(float(ca.get("bytes accessed", 1)), 1.0)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{arch}__redundancy__{tag}.json").write_text(
        json.dumps(rec, indent=2, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--mode", default="vilamb", choices=["none", "sync", "vilamb"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()

    archs = list_archs() if (args.all or args.arch == "all") else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape == "all") else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    out_dir = pathlib.Path(args.out)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "multi" if mp else "single"
                fname = out_dir / f"{arch}__{shape}__{mesh_name}{('__' + args.tag) if args.tag else ''}.json"
                if args.skip_existing and fname.exists():
                    print(f"[skip] {arch} {shape} {mesh_name} (cached)")
                    continue
                label = f"{arch:26s} {shape:12s} {mesh_name:6s}"
                try:
                    rec = run_cell(arch, shape, mp, mode=args.mode,
                                   out_dir=out_dir, tag=args.tag)
                    if rec["status"] != "ok":
                        print(f"[----] {label} {rec['status']}")
                        out_dir.mkdir(parents=True, exist_ok=True)
                        fname.write_text(json.dumps(rec, indent=2))
                        continue
                    rl = rec["roofline"]
                    print(f"[ ok ] {label} compile={rec['compile_s']}s "
                          f"accum={rec['accum_steps']} "
                          f"bottleneck={rl['bottleneck']} "
                          f"frac={rl['roofline_fraction']:.3f} "
                          f"fits16G={rec.get('fits_16g', '?')}", flush=True)
                except Exception as e:
                    failures += 1
                    print(f"[FAIL] {label} {type(e).__name__}: {e}")
                    traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
