import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This proves the distribution config is coherent without hardware: the 16x16
single-pod mesh AND the 4x8x16 multi-pod mesh (4 islands x 128 chips) must
compile for every applicable cell; memory_analysis() proves it fits,
cost_analysis() + the HLO static analyzer feed §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --plan auto   # autotuned config

``--plan auto`` replaces the hand-set collective flags for train cells: the
plan autotuner (``repro.plan``, DESIGN.md §9) picks mode / channel count /
bucket size / per-pod shares jointly by pricing the candidate space with the
α-β simulator on the mesh's modeled topology (``mesh.cluster_for_mesh``).
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import plan as plan_mod
from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.core.balance import uniform_plan
from repro.launch.mesh import (cluster_for_mesh, make_production_mesh,
                               mesh_axis_sizes, pod_size_of, resolve_stripes)
from repro.models import build
from repro.roofline.analysis import Roofline, analyze_hlo
from repro.serve.engine import make_serve_programs
from repro.train.trainer import make_train_program


def model_flops_spec(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Spec formula: 6·N·D (train) / 2·N·D (inference), N = active params
    excluding the embedding table, D = tokens in the step."""
    n = cfg.n_active_params() - cfg.vocab * cfg.d_model   # embed lookup isn't matmul
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch                    # decode: one token/seq


def _train_batch_sds(cfg: ModelConfig, shape: ShapeConfig, mesh, plan):
    sizes = mesh_axis_sizes(mesh)
    dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
    dp = int(np.prod([sizes[a] for a in dp_axes]))
    nm, gmb = plan.n_micro_max, plan.micro_batch * dp
    sds = {
        "tokens": jax.ShapeDtypeStruct((nm, gmb, shape.seq_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct((nm, gmb, shape.seq_len), jnp.int32),
    }
    extra_specs = {}
    dpa = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    if cfg.family == "encdec":
        sds["frames"] = jax.ShapeDtypeStruct(
            (nm, gmb, cfg.n_frames, cfg.d_model), jnp.bfloat16)
        extra_specs["frames"] = P(None, dpa, None, None)
    if cfg.family == "vlm":
        sds["mrope"] = jax.ShapeDtypeStruct((nm, 3, gmb, shape.seq_len), jnp.int32)
        extra_specs["mrope"] = P(None, None, dpa, None)
    return sds, extra_specs


def _serve_batch_sds(cfg: ModelConfig, shape: ShapeConfig, kind: str):
    B, S = shape.global_batch, shape.seq_len
    if kind == "prefill":
        sds = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if cfg.family == "encdec":
            sds["frames"] = jax.ShapeDtypeStruct((B, cfg.n_frames, cfg.d_model),
                                                 jnp.bfloat16)
        if cfg.family == "vlm":
            sds["mrope"] = jax.ShapeDtypeStruct((3, B, S), jnp.int32)
        return sds
    return jax.ShapeDtypeStruct((B, 1), jnp.int32)          # decode token


def run_cell(arch: str, shape_name: str, mesh_kind: str, zero: int = 3,
             verbose: bool = True, plan_mode: str = "manual",
             backend: str = "auto", stripes: str = "auto",
             policy: str = "auto", trace_out: str | None = None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "zero": zero,
           "policy": policy}
    if not shape.applicable(cfg):
        rec["status"] = "skipped"
        rec["reason"] = "long_500k requires sub-quadratic attention (DESIGN.md §4)"
        return rec
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_dev = int(np.prod(mesh.devices.shape))
    model = build(cfg)
    t0 = time.time()
    try:
        if shape.kind == "train":
            sizes = mesh_axis_sizes(mesh)
            n_pods = sizes.get("pod", 1)
            dp = int(np.prod([sizes.get(a, 1) for a in ("pod", "data")]))
            assert shape.global_batch % dp == 0, (shape.global_batch, dp)
            if plan_mode == "auto":
                # joint (shares, mode, backend, channels, bucket, stripes)
                # selection priced by the simulator on the mesh's modeled
                # topology (DESIGN.md §9; ring backends §10, transport §11);
                # --policy auto additionally emits the per-op, size-classed
                # policy table (repro.comm, DESIGN.md §12)
                import dataclasses as _dc
                req = plan_mod.plan_request(
                    cluster_for_mesh(mesh), cfg, shape.global_batch,
                    shape.seq_len, data_axis=sizes.get("data", 1),
                    zero_stage=zero)
                space = plan_mod.DEFAULT_SPACE
                if backend != "auto":
                    space = _dc.replace(space, backends=(backend,))
                if stripes != "auto":
                    space = _dc.replace(space,
                                        stripe_counts=(int(stripes),))
                if policy == "flat":
                    space = _dc.replace(space, modes=("flat",),
                                        backends=("xla",), per_op=False)
                elif policy == "legacy":
                    space = _dc.replace(space, per_op=False)
                tp = (plan_mod.autotune_policies(req, space)
                      if policy == "auto" else plan_mod.autotune(req, space))
                plan, rc = tp.plan, tp.run_config()
                rec["plan"] = tp.summary()   # includes the chosen table
                if verbose:
                    n_rows = (len(tp.policies.rows)
                              if tp.policies is not None else 0)
                    print(f"  plan auto: mode={tp.mode} backend={tp.backend} "
                          f"C={tp.n_channels} stripes={tp.n_stripes} "
                          f"bucket={tp.bucket_bytes >> 20}MiB "
                          f"policy_rows={n_rows} "
                          f"shares={tp.plan.micro_per_pod} "
                          f"modeled_step={tp.modeled_step_s:.4f}s")
            else:
                # micro-batch so each device sees ~8k tokens per micro-step
                # (keeps the remat activation stash inside v5e HBM); gradient
                # accumulation covers the rest of the global batch.
                import dataclasses as _dc
                per_dev = shape.global_batch // dp
                mb = max(1, min(per_dev, 8192 // shape.seq_len))
                n_micro = per_dev // mb
                plan = uniform_plan(n_pods, n_micro * n_pods, mb)
                rbackend = backend if backend != "auto" else "xla"
                rc = RunConfig(zero_stage=zero,
                               collective_mode="flat" if policy == "flat"
                               else ("hier" if multi else "flat"),
                               backend=rbackend,
                               n_stripes=resolve_stripes(stripes, rbackend,
                                                         mesh))
                if policy == "auto":
                    # hand-set shares, per-op policy table (DESIGN.md §12)
                    space = plan_mod.DEFAULT_SPACE
                    if backend != "auto":
                        space = _dc.replace(space, backends=(backend,))
                    if stripes != "auto":
                        space = _dc.replace(space,
                                            stripe_counts=(int(stripes),))
                    rc = _dc.replace(rc, policies=plan_mod.policy_table_for(
                        cluster_for_mesh(mesh), space,
                        bucket_bytes=rc.bucket_bytes, zero_stage=zero))
                    rec["policy_table"] = rc.policies.summary()
            if trace_out is not None:
                # modeled Chrome trace of this cell: one span per policy-
                # table row priced by the simulator (repro.obs, DESIGN.md
                # §16) — nothing dispatches in a dryrun, so the trace is the
                # plan, residual 1.0 by construction
                from repro import obs
                cl = cluster_for_mesh(mesh)
                table = (rc.policies if rc.policies is not None
                         else plan_mod.policy_table_for(cl))
                spans = obs.modeled_spans(table, cl)
                obs.write_chrome_trace(trace_out, obs.chrome_trace(spans))
                rec["trace"] = trace_out
            batch_sds, extra_specs = _train_batch_sds(cfg, shape, mesh, plan)
            prog = make_train_program(model, mesh, rc, plan,
                                      extra_batch_specs=extra_specs)
            key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
            state_sds = jax.eval_shape(prog.init_fn, key_sds)
            lowered = prog.step_fn.lower(state_sds, batch_sds)
        else:
            progs = make_serve_programs(model, mesh, shape.global_batch,
                                        shape.seq_len)
            pspecs = model.param_specs(progs.rules)
            params_sds = jax.tree.map(
                lambda m, s: jax.ShapeDtypeStruct(
                    m.shape, jnp.dtype(cfg.dtype),
                    sharding=NamedSharding(mesh, s)),
                model.abstract_params(), pspecs,
                is_leaf=lambda x: hasattr(x, "axes"))
            if shape.kind == "prefill":
                batch_sds = _serve_batch_sds(cfg, shape, "prefill")
                lowered = progs.prefill_fn.lower(params_sds, batch_sds)
            else:
                from repro.models.common import spec_tree
                cmetas = model.cache_metas(shape.global_batch, shape.seq_len)
                cspecs = spec_tree(cmetas, progs.rules)
                cache_sds = jax.tree.map(
                    lambda m, s: jax.ShapeDtypeStruct(
                        m.shape,
                        jnp.dtype(cfg.dtype) if len(m.shape) else jnp.int32,
                        sharding=NamedSharding(mesh, s)),
                    cmetas, cspecs, is_leaf=lambda x: hasattr(x, "axes"))
                tok_sds = _serve_batch_sds(cfg, shape, "decode")
                lowered = progs.decode_fn.lower(params_sds, cache_sds, tok_sds)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        if verbose:
            print(f"  memory_analysis: {ma}")
            print(f"  cost_analysis: flops={ca.get('flops')} "
                  f"bytes={ca.get('bytes accessed')}")
        hlo = compiled.as_text()
        stats = analyze_hlo(hlo, n_dev, pod_size=pod_size_of(mesh))
        roof = Roofline(
            arch=arch, shape=shape_name, mesh=mesh_kind, n_devices=n_dev,
            model_flops_per_step=model_flops_spec(cfg, shape),
            stats=stats,
            xla_flops=float(ca.get("flops", 0) or 0),
            xla_bytes=float(ca.get("bytes accessed", 0) or 0),
            memory_per_device={
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            })
        rec.update(status="ok", lower_s=round(t_lower, 1),
                   compile_s=round(t_compile, 1), **_jsonable(roof.row()))
        if verbose:
            print(f"  roofline: compute={roof.compute_s:.4f}s "
                  f"memory={roof.memory_s:.4f}s collective={roof.collective_s:.4f}s "
                  f"dominant={roof.dominant} useful={roof.useful_flops_fraction:.2f} "
                  f"roofline_frac={roof.roofline_fraction:.3f}")
    except Exception as e:
        rec["status"] = "FAILED"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=12)
    return rec


def _jsonable(d):
    def conv(v):
        if isinstance(v, (np.floating, np.integer)):
            return float(v)
        if isinstance(v, dict):
            return {k: conv(x) for k, x in v.items()}
        return v
    return {k: conv(v) for k, v in d.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--zero", type=int, default=3)
    ap.add_argument("--plan", default="manual", choices=["manual", "auto"],
                    help="auto: the repro.plan autotuner picks collective "
                         "mode/backend/channels/bucket/shares (train cells)")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "xla", "pallas"],
                    help="pin the collective ring backend (DESIGN.md §10); "
                         "auto lets --plan auto search it (manual plans "
                         "default to xla).  Pinned runs get a __<backend> "
                         "file suffix so baselines can be kept side by side")
    ap.add_argument("--stripes", default="auto",
                    help="multi-NIC stripe count of the DMA rings "
                         "(transport layer, DESIGN.md §11; pallas backend "
                         "only).  auto = planner-chosen (--plan auto "
                         "searches SearchSpace.stripe_counts; manual pallas "
                         "plans ask transport.plan_stripes); an integer "
                         "pins it")
    ap.add_argument("--policy", default="auto",
                    choices=["auto", "flat", "legacy"],
                    help="collective policy source (repro.comm, DESIGN.md "
                         "§12): auto = per-op, size-classed PolicyTable "
                         "(searched by --plan auto, priced on the mesh's "
                         "modeled topology for manual plans); legacy = the "
                         "single-policy facade of the flags above (PR-4 "
                         "behavior); flat = force the flat single-stage "
                         "policy everywhere")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--trace", action="store_true",
                    help="also write a modeled Chrome trace per train cell "
                         "(<out>/<tag>.trace.json; repro.obs, DESIGN.md §16)"
                         ": one span per policy-table row priced by the "
                         "simulator")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append one unified-schema metric line per cell "
                         "(kind=dryrun_cell) to this JSONL file")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"{arch}__{shape}__{mesh_kind}"
                if args.backend != "auto":
                    tag += f"__{args.backend}"
                print(f"=== {tag} ===", flush=True)
                trace_out = (os.path.join(args.out, tag + ".trace.json")
                             if args.trace else None)
                rec = run_cell(arch, shape, mesh_kind, args.zero,
                               plan_mode=args.plan, backend=args.backend,
                               stripes=args.stripes, policy=args.policy,
                               trace_out=trace_out)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
                if args.metrics_out:
                    from repro.obs import append_metric_line, metric_line
                    append_metric_line(args.metrics_out, metric_line(
                        "dryrun_cell",
                        labels={"arch": arch, "shape": shape,
                                "mesh": mesh_kind, "zero": args.zero,
                                "policy": args.policy},
                        metrics={k: v for k, v in rec.items()
                                 if isinstance(v, (int, float))},
                        meta={"status": rec["status"]}))
                print(f"  -> {rec['status']} "
                      f"({rec.get('compile_s', '-')}s compile)", flush=True)
                if rec["status"] == "FAILED":
                    failures += 1
                    print(rec.get("traceback", rec.get("error")), flush=True)
    print(f"DONE failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
