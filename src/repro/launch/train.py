"""Training launcher: any assigned architecture on any mesh.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        [--steps 50] [--zero 1|3] [--mode flat|hier|auto] [--seq 128] \
        [--plan manual|auto] [--reduced] [--mesh-shape 2,2,2] \
        [--ckpt-dir DIR] [--resume]

Defaults run the reduced config on an 8-host-device (2,2,2) mesh so the
launcher is exercisable on CPU; on a real fleet pass the production mesh and
drop --reduced.  Cluster launchers (SLURM/GKE) invoke exactly this module on
every host (JAX multi-controller picks up the process set).

``--plan auto`` hands the collective configuration (mode, channels, bucket,
ZeRO stage kept as given, per-pod shares) to the plan autotuner
(``repro.plan``, DESIGN.md §9) instead of ``--mode``/``--zero`` hand-tuning;
the batch contract (micro-batch size × micro-steps) is preserved.
"""
import argparse
import os


def main(argv=None):
    """Parse ``argv`` (default: the command line), train, and return the
    per-step metric history (``ft.run_supervised``'s list of dicts)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--zero", type=int, default=1)
    ap.add_argument("--mode", default="hier")
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas"],
                    help="collective ring backend (DESIGN.md §10); "
                         "--plan auto searches it jointly and overrides this")
    ap.add_argument("--stripes", default="auto",
                    help="multi-NIC stripe count of the DMA rings "
                         "(transport layer, DESIGN.md §11; pallas only). "
                         "auto = planner-chosen: --plan auto searches it, "
                         "manual pallas runs ask transport.plan_stripes; "
                         "an integer pins it")
    ap.add_argument("--plan", default="manual", choices=["manual", "auto"],
                    help="auto: repro.plan picks mode/channels/bucket/shares")
    ap.add_argument("--policy", default="auto",
                    choices=["auto", "flat", "legacy"],
                    help="collective policy source (repro.comm, DESIGN.md "
                         "§12): auto = per-op, size-classed PolicyTable; "
                         "legacy = the single-policy facade of "
                         "--mode/--backend/--stripes; flat = force flat "
                         "everywhere")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--micro-batch", type=int, default=1)
    ap.add_argument("--n-micro", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full-size", dest="reduced", action="store_false")
    ap.add_argument("--mesh-shape", default="2,2,2",
                    help="pod,data,model (pod omitted if 2 values)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--elastic", action="store_true",
                    help="run under the elastic control plane "
                         "(repro.elastic, DESIGN.md §13): failure detection "
                         "armed, pod loss survived by communicator rebuild "
                         "+ checkpointless ZeRO recovery instead of a job "
                         "restart")
    ap.add_argument("--chaos", default=None,
                    help="deterministic fault script (implies --elastic), "
                         "e.g. 'degrade:pod0.1x0.25@2;kill:pod1@4;"
                         "revive:pod1@8' or the gray-failure ops "
                         "'slow:pod1x2.5@3-10;hang:pod0@12' "
                         "(DESIGN.md §15) — see elastic.parse_script")
    ap.add_argument("--watchdog", action="store_true",
                    help="arm the collective hang watchdog (implies "
                         "--elastic): per-(op, size class) deadlines derived "
                         "from the simulator's modeled times, calibrated by "
                         "the committed BENCH_comm.json; breaches escalate "
                         "retry -> communicator rebuild -> evict "
                         "(DESIGN.md §15)")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="telemetry plane (repro.obs, DESIGN.md §16): record "
                         "every eager collective dispatch as a policy-tagged "
                         "span with its modeled-vs-measured residual, run "
                         "per-cell eager probes between steps, and write "
                         "trace.json (chrome://tracing), metrics.json, "
                         "report.txt and post-mortem flight dumps to DIR")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append a unified-schema metric line (the fleet "
                         "snapshot) to this JSONL file at the end of the run")
    args = ap.parse_args(argv)

    shape = tuple(int(x) for x in args.mesh_shape.split(","))
    n_dev = 1
    for s in shape:
        n_dev *= s
    os.environ.setdefault("XLA_FLAGS",
                          f"--xla_force_host_platform_device_count={n_dev}")

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.core.balance import uniform_plan
    from repro.data.pipeline import DataPipeline
    from repro.launch.cache import enable_compile_cache
    from repro.models import build
    from repro.train import checkpoint as ck
    from repro.train import ft
    from repro.train.trainer import make_train_program

    from repro.core import compat
    enable_compile_cache()
    axes = ("pod", "data", "model")[-len(shape):]
    mesh = compat.make_mesh(shape, axes)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    sizes = dict(zip(axes, shape))
    n_pods = sizes.get("pod", 1)
    import dataclasses as _dc
    from repro.launch.mesh import resolve_stripes
    rc = RunConfig(zero_stage=args.zero,
                   collective_mode="flat" if args.policy == "flat"
                   else args.mode,
                   backend=args.backend, learning_rate=args.lr,
                   # --plan auto searches the count below and replaces this
                   n_stripes=resolve_stripes(args.stripes, args.backend,
                                             mesh),
                   param_dtype="float32" if args.reduced else "bfloat16")
    tp = None
    if args.plan == "auto":
        from repro import plan as plan_mod
        from repro.launch.mesh import cluster_for_mesh
        data_axis = sizes.get("data", 1)
        req = plan_mod.plan_request(
            cluster_for_mesh(mesh), cfg,
            global_batch=args.n_micro * n_pods * args.micro_batch * data_axis,
            seq_len=args.seq, data_axis=data_axis, zero_stage=args.zero,
            micro_tokens=args.micro_batch * args.seq)
        space = plan_mod.DEFAULT_SPACE
        if args.stripes != "auto":
            space = _dc.replace(space, stripe_counts=(int(args.stripes),))
        if args.policy == "flat":
            space = _dc.replace(space, modes=("flat",), backends=("xla",),
                                per_op=False)
        elif args.policy == "legacy":
            space = _dc.replace(space, per_op=False)
        tp = (plan_mod.autotune_policies(req, space)
              if args.policy == "auto" else plan_mod.autotune(req, space))
        plan, rc = tp.plan, tp.run_config(rc)
        n_rows = len(tp.policies.rows) if tp.policies is not None else 0
        print(f"plan auto: mode={tp.mode} backend={tp.backend} "
              f"C={tp.n_channels} stripes={tp.n_stripes} "
              f"bucket={tp.bucket_bytes >> 20}MiB policy_rows={n_rows} "
              f"shares={plan.micro_per_pod} "
              f"modeled_step={tp.modeled_step_s:.4f}s")
    else:
        plan = uniform_plan(n_pods, args.n_micro * n_pods, args.micro_batch)
        if args.policy == "auto":
            # hand-set shares, per-op policy table (repro.comm, DESIGN.md
            # §12); an explicit --stripes pin narrows the table search the
            # same way --plan auto narrows its space
            from repro import plan as plan_mod
            from repro.launch.mesh import cluster_for_mesh
            space = plan_mod.DEFAULT_SPACE
            if args.stripes != "auto":
                space = _dc.replace(space,
                                    stripe_counts=(int(args.stripes),))
            rc = _dc.replace(rc, policies=plan_mod.policy_table_for(
                cluster_for_mesh(mesh), space, bucket_bytes=rc.bucket_bytes,
                zero_stage=args.zero))
    prog = make_train_program(model, mesh, rc, plan)
    print(f"arch={cfg.name} params={model.n_params():,} mesh={sizes} "
          f"zero={args.zero} mode={prog.hcfg.resolved_mode()}")
    state = prog.init_fn(jax.random.PRNGKey(args.seed))
    pipe = DataPipeline(seed=args.seed, plan=plan, dp_world=prog.dp_world(),
                        seq_len=args.seq, vocab=cfg.vocab)
    os.makedirs(args.ckpt_dir, exist_ok=True)
    ck.save(args.ckpt_dir, 0, state)

    def batches(step):
        return {k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}

    def log(step, m):
        if step % 10 == 0:
            print(f"step {step:4d}  loss {m['loss']:.4f}  "
                  f"grad_norm {m['grad_norm']:.3f}", flush=True)

    telemetry = None
    if args.trace or args.metrics_out:
        from repro import obs
        from repro.launch.mesh import cluster_for_mesh
        telemetry = obs.Telemetry(cluster=cluster_for_mesh(mesh),
                                  out_dir=args.trace)

    if args.elastic or args.chaos or args.watchdog:
        from repro import elastic
        from repro.launch.mesh import cluster_for_mesh
        cluster = cluster_for_mesh(mesh)
        script = elastic.parse_script(args.chaos) if args.chaos else None
        # detection armed for the gray middle too: per-pod step attribution
        # feeding the quarantine ladder (DESIGN.md §15)
        detector = elastic.FailureDetector(
            cluster, straggler=elastic.StragglerTracker())
        watchdog = None
        if args.watchdog:
            watchdog = elastic.CollectiveWatchdog(elastic.derive_deadlines(
                cluster, prog.comm.table, elastic.load_bench()))
            print(f"watchdog armed: {len(watchdog.deadlines.rows)} derived "
                  f"deadlines, tolerance {watchdog.deadlines.tolerance}x")
        state_bytes = float(sum(l.nbytes for l in jax.tree.leaves(state)))

        def make_batches(p):
            pipe_p = DataPipeline(seed=args.seed, plan=p.plan,
                                  dp_world=p.dp_world(), seq_len=args.seq,
                                  vocab=cfg.vocab)
            return lambda s: {k: jnp.asarray(v)
                              for k, v in pipe_p.batch_at(s).items()}

        state, report = elastic.run_elastic(
            prog, state, make_batches, cluster=cluster,
            ckpt_dir=args.ckpt_dir, n_steps=args.steps, script=script,
            train_plan=tp, detector=detector, watchdog=watchdog,
            telemetry=telemetry,
            ckpt_every=args.ckpt_every, state_bytes=state_bytes)
        for h in report.history:
            log(h["step"], h)
        for ev in report.hang_events:
            print(f"hang: {ev.op}/{ev.size_class} at step {ev.step} "
                  f"(pod={ev.pod}) breach #{ev.breaches} -> {ev.action}")
        for r in report.rebuilds:
            print(f"epoch {r.epoch}: {r.event.kind}:{r.event.pod} at step "
                  f"{r.event.step} -> pods={[p.name for p in r.cluster.pods]}"
                  f" shares={r.plan.micro_per_pod} "
                  f"modeled {r.modeled_checkpointless_s:.2f}s vs ckpt "
                  f"{r.modeled_checkpoint_s:.2f}s")
        for rec in report.recoveries:
            print(f"recovery: {rec.method}@{rec.step}")
        hist = report.history
    else:
        cb = log
        if telemetry is not None:
            telemetry.bind(comm=prog.comm)
            telemetry.install()

            def cb(step, m, _log=log):
                telemetry.on_step(step, m, dur_s=m.get("step_s"))
                telemetry.probe_step(step)
                _log(step, m)
        try:
            state, hist = ft.run_supervised(
                prog.step_fn, state, batches, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, n_steps=args.steps,
                state_shardings=prog.state_shardings,
                monitor=ft.StragglerMonitor(), metrics_cb=cb)
        finally:
            if telemetry is not None:
                telemetry.uninstall()
    if telemetry is not None:
        paths = telemetry.write(metrics_out=args.metrics_out)
        print(telemetry.step_report())
        for k, p in paths.items():
            print(f"telemetry {k}: {p}")
    print(f"done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")
    return hist


if __name__ == "__main__":
    main()
