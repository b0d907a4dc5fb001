import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init). 512 placeholder host devices back the production
meshes:

    single-pod : (data=16, model=16)           = 256 chips
    multi-pod  : (pod=2, data=16, model=16)    = 512 chips

Per cell this script builds ShapeDtypeStruct stand-ins for params /
optimizer state / inputs (``input_specs`` — zero allocation), jits the step
with explicit shardings, ``.lower().compile()``s it, and records:

    memory_analysis()  -> per-device bytes (proves it fits),
    cost_analysis()    -> HLO FLOPs / bytes for the roofline,
    compiled.as_text() -> collective bytes by kind (roofline collective
                          term; parsed by roofline/analysis.py).

Solver cells (--solver) lower the paper's distributed CG on the flattened
512-way block-row mesh at the paper's weak-scaled production size
(405^3 DOFs per device) — both the BCMGX-analog (ring halo) and the
Ginkgo-analog (allgather) layouts.

Usage:
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--out runs/dryrun]
    python -m repro.launch.dryrun --solver --all-solver
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_config
from repro.configs.base import ArchConfig, ShapeConfig

try:  # repro.dist is only needed for the LM cells, not the solver cells
    from repro.dist.sharding import (
        batch_specs,
        cache_specs,
        dp_axes,
        param_specs,
        shardings_of,
    )

    HAS_DIST = True
except ModuleNotFoundError:  # pragma: no cover - container without repro.dist
    HAS_DIST = False
from repro.launch.mesh import make_production_mesh
from repro.models import lm, transformer as tfm
from repro.roofline import analysis as ra
from repro.train.loop import make_train_step
from repro.train.optimizer import OptConfig, init_opt_state

P = jax.sharding.PartitionSpec


def skip_reason(cfg: ArchConfig, shape: ShapeConfig) -> str | None:
    if shape.kind == "decode" and cfg.is_encoder_only:
        return "encoder-only arch has no decode step"
    if shape.name == "long_500k" and not cfg.subquadratic:
        return "pure full-attention arch: 500k decode needs sub-quadratic attention"
    return None


# microbatch counts chosen so train activations fit 16 GB/chip (see DESIGN)
TRAIN_MICROBATCHES = {"default": 1}


def _cell_fns(cfg: ArchConfig, shape: ShapeConfig, mesh, microbatches: int = 1):
    """Build (jitted fn, example args as SDS) for one cell."""
    if not HAS_DIST:
        raise ModuleNotFoundError(
            "repro.dist is required for LM dry-run cells (solver cells via "
            "--solver / --solver-matfree work without it)"
        )
    specs = lm.input_specs(cfg, shape)
    params_sds = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.key(0)))
    p_sh = shardings_of(param_specs(params_sds, mesh), mesh)

    if shape.kind == "train":
        opt_sds = jax.eval_shape(
            lambda: init_opt_state(params_sds, OptConfig())
        )
        o_sh = {
            "mu": shardings_of(param_specs(opt_sds["mu"], mesh), mesh),
            "nu": shardings_of(param_specs(opt_sds["nu"], mesh), mesh),
            "step": jax.sharding.NamedSharding(mesh, P()),
            "skipped": jax.sharding.NamedSharding(mesh, P()),
        }
        b_sh = shardings_of(
            batch_specs(specs["batch"], mesh, shape.global_batch), mesh
        )
        step = make_train_step(cfg, OptConfig(), kv_chunk=1024, remat=True,
                               microbatches=microbatches)
        fn = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1),
        )
        return fn, (params_sds, opt_sds, specs["batch"])

    if shape.kind == "prefill":
        b_sh = shardings_of(
            batch_specs(specs["batch"], mesh, shape.global_batch), mesh
        )

        def pre_fn(params, batch):
            return lm.prefill(params, cfg, batch, kv_chunk=1024)

        fn = jax.jit(pre_fn, in_shardings=(p_sh, b_sh))
        return fn, (params_sds, specs["batch"])

    # decode
    c_sh = shardings_of(
        cache_specs(specs["cache"], mesh, shape.global_batch, shape.seq_len),
        mesh,
    )
    dp = dp_axes(mesh)
    dpn = 1
    for a in dp:
        dpn *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    t_spec = P(dp) if shape.global_batch % dpn == 0 and shape.global_batch > 1 else P()
    t_sh = jax.sharding.NamedSharding(mesh, t_spec)
    s_sh = jax.sharding.NamedSharding(mesh, P())

    def dec_fn(params, token, cache, pos):
        return lm.serve_step(params, cfg, token, cache, pos)

    fn = jax.jit(
        dec_fn,
        in_shardings=(p_sh, t_sh, c_sh, s_sh),
        out_shardings=(None, c_sh),
        donate_argnums=(2,),
    )
    return fn, (params_sds, specs["token"], specs["cache"], specs["pos"])


def _cost_dict(compiled) -> dict:
    return compiled.cost_analysis() or {}


def _analyze(compiled, chips: int, model_flops: float) -> dict:
    cost = _cost_dict(compiled)
    # cost_analysis is per-module (one device's program under SPMD)
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    colls = ra.collective_bytes(hlo)
    terms = ra.roofline(
        hlo_flops_per_device=flops,
        hlo_bytes_per_device=bytes_acc,
        collective_bytes_per_device=colls["total_bytes"],
        chips=chips,
        model_flops=model_flops,
    )
    mem = {}
    try:
        m = compiled.memory_analysis()
        if m is not None:
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            ):
                mem[k] = int(getattr(m, k, 0) or 0)
            mem["total_per_device"] = (
                mem.get("argument_size_in_bytes", 0)
                + mem.get("output_size_in_bytes", 0)
                + mem.get("temp_size_in_bytes", 0)
                - mem.get("alias_size_in_bytes", 0)
            )
    except Exception as e:  # pragma: no cover - backend-dependent
        mem["error"] = str(e)
    return {
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collectives": colls,
        "memory": mem,
        "roofline": {
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "step_s": terms.step_s,
            "model_flops": model_flops,
            "useful_ratio": terms.useful_ratio,
            "mfu": terms.mfu,
        },
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str | None,
             probe: bool = False, attn_bf16: bool = False, microbatches: int = 1,
             ssm_chunk: int = 0, tag: str = "", ssd_bf16: bool = False):
    """probe=True additionally compiles the cell with every static-length
    scan UNROLLED and replaces the roofline flops/bytes with the exact
    unrolled HLO costs (XLA cost analysis counts while bodies once — see
    models/flags.py). Memory + collective schedule always come from the
    rolled (deployable) module."""
    from repro.models import flags as mflags

    cfg = get_config(arch)
    if ssm_chunk and cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, chunk=ssm_chunk)
        )
    mflags.ATTN_SCORE_BF16 = attn_bf16
    mflags.SSD_BF16 = ssd_bf16
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    rec: dict = {"arch": arch + tag, "shape": shape_name, "mesh": mesh_name,
                 "perf_levers": {"attn_bf16": attn_bf16,
                                  "microbatches": microbatches,
                                  "ssm_chunk": ssm_chunk}}
    reason = skip_reason(cfg, shape)
    if reason:
        rec.update(status="skip", skip_reason=reason)
        _emit(rec, out_dir)
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh.devices.size
        t0 = time.perf_counter()
        fn, args = _cell_fns(cfg, shape, mesh, microbatches)
        lowered = fn.lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
        mf = {
            "train": ra.model_flops_train,
            "prefill": ra.model_flops_prefill,
            "decode": ra.model_flops_decode,
        }[shape.kind](cfg, shape)
        rec.update(
            status="ok",
            chips=chips,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            **_analyze(compiled, chips, mf),
        )
        if probe:
            try:
                mflags.UNROLL_SCANS = True
                t0 = time.perf_counter()
                fn_u, args_u = _cell_fns(cfg, shape, mesh, microbatches)
                compiled_u = fn_u.lower(*args_u).compile()
                cost_u = _cost_dict(compiled_u)
                rec["probe_compile_s"] = round(time.perf_counter() - t0, 2)
                flops_u = float(cost_u.get("flops", 0.0))
                bytes_u = float(cost_u.get("bytes accessed", 0.0))
                # collectives inside scan loops are also text-counted once in
                # the rolled module; the unrolled text has every instance.
                colls_u = ra.collective_bytes(compiled_u.as_text())
                rec["collectives_rolled"] = rec["collectives"]
                rec["collectives"] = colls_u
                rec["flops_per_device_rolled"] = rec["flops_per_device"]
                rec["bytes_per_device_rolled"] = rec["bytes_per_device"]
                rec["flops_per_device"] = flops_u
                rec["bytes_per_device"] = bytes_u
                terms = ra.roofline(
                    hlo_flops_per_device=flops_u,
                    hlo_bytes_per_device=bytes_u,
                    collective_bytes_per_device=colls_u["total_bytes"],
                    chips=chips,
                    model_flops=mf,
                )
                rec["roofline"] = {
                    "compute_s": terms.compute_s,
                    "memory_s": terms.memory_s,
                    "collective_s": terms.collective_s,
                    "dominant": terms.dominant,
                    "step_s": terms.step_s,
                    "model_flops": mf,
                    "useful_ratio": terms.useful_ratio,
                    "mfu": terms.mfu,
                }
                rec["cost_source"] = "unrolled-probe"
                if cfg.xlstm is not None:
                    rec["cost_note"] = (
                        "sLSTM time scan kept rolled (<1% of cell flops)"
                    )
            finally:
                mflags.UNROLL_SCANS = False
        mflags.ATTN_SCORE_BF16 = False
        mflags.SSD_BF16 = False
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    _emit(rec, out_dir)
    return rec


# ---------------------------------------------------------------------------
# Solver cells (the paper's technique at production scale)
# ---------------------------------------------------------------------------


def run_solver_cell(
    variant: str,
    stencil: str,
    dofs_per_device: int,
    out_dir: str | None,
    *,
    layout: str = "ring",
    maxiter: int = 100,
):
    """Lower distributed CG at the paper's weak-scaled production size."""
    from repro.core.cg import abstract_stencil_dist, make_solver_fn
    from repro.core.spmv import dist_specs
    from repro.matrices.poisson import PoissonProblem

    n_shards = len(jax.devices())
    mesh = jax.sharding.Mesh(jax.devices(), ("shards",))
    side = dofs_per_device
    p = PoissonProblem(side, side, side * n_shards, stencil)
    if layout != "ring":
        variant = "naive"  # allgather layout always runs the unfused body
    rec = {
        "arch": f"solver-cg-{variant}-{layout}",
        "shape": f"{stencil}-{side}^3x{n_shards}",
        "mesh": f"flat{n_shards}",
    }
    try:
        mat_sds = abstract_stencil_dist(p, n_shards)
        if layout == "allgather":
            mat_sds = dataclasses.replace(
                mat_sds,
                plan=dataclasses.replace(
                    mat_sds.plan, mode="allgather", shifts=(), widths=()
                ),
                data_ext=jax.ShapeDtypeStruct(
                    mat_sds.data_ext.shape, mat_sds.data_ext.dtype
                ),
            )
        R = mat_sds.n_own_pad
        vec = jax.ShapeDtypeStruct((n_shards, R), "float64")
        if layout == "ring":
            solve = make_solver_fn(mesh, mat_sds, variant=variant, maxiter=maxiter)
        else:
            # naive solver closes over the matrix; rebuild as arg-style
            from repro.core.cg import identity_precond
            from repro.core.baselines import _cg_unfused_body
            from repro.core.spmv import local_block

            pre = identity_precond()
            specs = dist_specs(mat_sds)

            def fn(m, b, x0):
                mb = local_block(m)
                x, iters, rr, bb = _cg_unfused_body(
                    mb, pre, (), b[0], x0[0], tol=1e-8, maxiter=maxiter,
                    axis="shards",
                )
                return x[None], iters, rr, bb

            mapped = jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=(specs, jax.sharding.PartitionSpec("shards", None),
                          jax.sharding.PartitionSpec("shards", None)),
                out_specs=(jax.sharding.PartitionSpec("shards", None),
                           jax.sharding.PartitionSpec(),
                           jax.sharding.PartitionSpec(),
                           jax.sharding.PartitionSpec()),
            )
            solve = jax.jit(lambda m, b, x0: mapped(m, b, x0))

        t0 = time.perf_counter()
        lowered = solve.lower(mat_sds, vec, vec)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
        # model flops: maxiter x (2nnz + vector ops ~ 10n) per device x chips
        nnz = p.n * p.k
        model_flops = maxiter * (2.0 * nnz + 10.0 * p.n)
        rec.update(
            status="ok",
            chips=n_shards,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            **_analyze(compiled, n_shards, model_flops),
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    _emit(rec, out_dir)
    return rec


def run_solver_matfree_cell(
    variant: str,
    stencil: str,
    dofs_per_device: int,
    out_dir: str | None,
    *,
    dtype: str = "float64",
    maxiter: int = 100,
):
    """Beyond-paper optimization (§Perf): matrix-free stencil CG."""
    from repro.core.stencil_solver import make_stencil_solver_fn
    from repro.matrices.poisson import PoissonProblem

    n_shards = len(jax.devices())
    mesh = jax.sharding.Mesh(jax.devices(), ("shards",))
    side = dofs_per_device
    p = PoissonProblem(side, side, side * n_shards, stencil)
    rec = {
        "arch": f"solver-cg-{variant}-matfree-{dtype[-2:]}",
        "shape": f"{stencil}-{side}^3x{n_shards}",
        "mesh": f"flat{n_shards}",
    }
    try:
        R = p.n // n_shards
        vec = jax.ShapeDtypeStruct((n_shards, R), dtype)
        solve = make_stencil_solver_fn(
            mesh, p, n_shards, variant=variant, maxiter=maxiter
        )
        t0 = time.perf_counter()
        lowered = solve.lower(vec, vec)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
        nnz = p.n * p.k
        model_flops = maxiter * (2.0 * nnz + 10.0 * p.n)
        rec.update(
            status="ok",
            chips=n_shards,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            **_analyze(compiled, n_shards, model_flops),
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    _emit(rec, out_dir)
    return rec


def _emit(rec: dict, out_dir: str | None):
    line = f"[{rec['status']:5s}] {rec['arch']:24s} {rec['shape']:22s} {rec['mesh']}"
    if rec["status"] == "ok":
        r = rec["roofline"]
        line += (
            f"  dom={r['dominant']:10s} step={r['step_s']:.4g}s"
            f" mfu={r['mfu']:.3f} compile={rec['compile_s']}s"
        )
    elif rec["status"] == "skip":
        line += f"  ({rec['skip_reason']})"
    else:
        line += f"  {rec['error'][:120]}"
    print(line, flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json".replace("/", "_")
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS))
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--solver", action="store_true")
    ap.add_argument("--all-solver", action="store_true")
    ap.add_argument("--solver-matfree", action="store_true")
    ap.add_argument("--dtype", default="float64")
    ap.add_argument("--variant", default="fcg")
    ap.add_argument("--layout", default="ring", choices=["ring", "allgather"])
    ap.add_argument("--stencil", default="7pt", choices=["7pt", "27pt"])
    ap.add_argument("--dofs", type=int, default=405)
    ap.add_argument("--out", default=None)
    ap.add_argument("--probe", action="store_true",
                    help="also compile unrolled cost probe per cell")
    ap.add_argument("--attn-bf16", action="store_true",
                    help="perf lever: bf16-operand attention matmuls")
    ap.add_argument("--ssd-bf16", action="store_true",
                    help="perf lever: bf16-operand SSD einsums")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ssm-chunk", type=int, default=0)
    ap.add_argument("--tag", default="", help="suffix for record names")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    if args.solver or args.all_solver or args.solver_matfree:
        # solver cells follow the paper's double precision (f32 is the
        # mixed-precision optimization variant, selected via --dtype)
        if args.dtype == "float64":
            jax.config.update("jax_enable_x64", True)

    if args.solver_matfree:
        run_solver_matfree_cell(
            args.variant, args.stencil, args.dofs, args.out, dtype=args.dtype
        )
        return

    if args.solver or args.all_solver:
        if args.all_solver:
            from repro.api import VARIANTS

            # the sweep covers every user-selectable CG body — a variant
            # added to the API without a dry-run cell fails loudly here
            sweep = ("hs", "fcg", "pipecg", "sstep")
            assert sweep == VARIANTS, (sweep, VARIANTS)
            for variant in sweep:
                run_solver_cell(variant, "7pt", args.dofs, args.out)
            run_solver_cell("fcg", "27pt", 260, args.out)
            # Ginkgo-analog (allgather) at full 405^3/device x 512 exceeds
            # int32 local addressing (512 * 66.4M = 3.4e10 columns) AND HBM
            # (272 GB gathered vector) — the paper's global->local compaction
            # point. Recorded at the largest size that fits (128^3/device).
            run_solver_cell("hs", "7pt", 128, args.out, layout="allgather")
            run_solver_cell("hs", "7pt", 128, args.out, layout="ring")
        else:
            run_solver_cell(
                args.variant, args.stencil, args.dofs, args.out, layout=args.layout
            )
        return

    if args.all:
        for arch in ARCHS:
            for shape_name in SHAPES:
                for mp in meshes:
                    run_cell(arch, shape_name, mp, args.out, probe=args.probe)
        return

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    for mp in meshes:
        run_cell(args.arch, args.shape, mp, args.out, probe=args.probe,
                 attn_bf16=args.attn_bf16, microbatches=args.microbatches,
                 ssm_chunk=args.ssm_chunk, tag=args.tag, ssd_bf16=args.ssd_bf16)


if __name__ == "__main__":
    main()
