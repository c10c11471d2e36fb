"""The training step and loop.

Step architecture (validated in DESIGN.md §3): one ``jax.shard_map`` whose
*manual* axes are the data-parallel ('pod', 'data') axes — every DP collective
inside is an explicit HetCCL call (the paper's library layer) — while the
'model' axis stays *auto* (XLA shards the TP einsums natively, the analogue of
delegating to the vendor's own library).

Gradient accumulation runs the balancer's plan: every pod executes the same
``n_micro_max`` micro-steps (SPMD), pods with a smaller share have trailing
micro-steps masked; gradients are weighted by true token counts so the math
equals the paper's proportional micro-batching (§4.5).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import comm as comm_mod
from repro.configs.base import ModelConfig, RunConfig
from repro.core import compat, hetccl
from repro.core.balance import HetPlan
from repro.models import Ctx, Model
from repro.models import moe as moe_mod
from repro.models.common import is_meta, make_rules, manual_only, spec_tree
from repro.train import optim

# Name of the step's token-count and loss reductions in the compiled program
# (DESIGN.md §16); the model's layers and the optimizer name their own ops.
STEP_STATS_SCOPE = "step_stats"


@dataclasses.dataclass
class TrainProgram:
    """A compiled training program bound to (model, mesh, plan, run config).

    ``comm`` is the program's :class:`repro.comm.Communicator` (DESIGN.md
    §12): built from ``rc.policies`` when the planner emitted a per-op
    table, else the one-row facade compile of ``hcfg`` — every collective
    in the step dispatches through it.
    """

    model: Model
    mesh: Any
    rc: RunConfig
    plan: HetPlan
    hcfg: hetccl.HetCCLConfig
    comm: comm_mod.Communicator
    rules: dict
    step_fn: Callable          # jitted: (state, batch) -> (state, metrics)
    init_fn: Callable          # jitted: (key,) -> state
    state_shardings: Any
    batch_sharding: Any

    def batch_shape(self, seq_len: int) -> tuple[int, int, int]:
        dp = self.dp_world()
        return (self.plan.n_micro_max, self.plan.micro_batch * dp, seq_len)

    def dp_world(self) -> int:
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        w = sizes.get("data", 1)
        if self.hcfg.pod_axis:
            w *= sizes.get(self.hcfg.pod_axis, 1)
        return w

    def shard_coverage(self):
        """Per-leaf pod-loss survivability: the optim shard coverage map the
        elastic recovery path consults (``repro.elastic.recover``,
        DESIGN.md §13).

        A leaf survives losing one pod iff its sharding never splits over
        the pod axis — every shard then has a replica on each surviving
        pod.  ZeRO-3 state (params/m/v/master sharded over 'data' only,
        replicated across pods) is fully covered; ZeRO-1 optimizer shards
        (flat 1/W over ('pod','data')) are not — pod loss there must fall
        back to a checkpoint.

        Returns:
            (mask_tree, all_covered): a bool tree matching the state and
            its conjunction.
        """
        pod = self.hcfg.pod_axis

        def covered(sharding) -> bool:
            if pod is None:
                return True                     # no pod axis, nothing to lose
            for entry in tuple(sharding.spec):
                axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
                if pod in axes:
                    return False
            return True

        mask = jax.tree.map(covered, self.state_shardings)
        return mask, all(jax.tree.leaves(mask))

    def abstract_state(self):
        """Shape/dtype skeleton of the train state (no allocation) — the
        ``state_like`` of resharding restores onto this program's mesh."""
        return jax.eval_shape(self.init_fn, jax.random.PRNGKey(0))


def _dp_axes_of(mesh) -> tuple[tuple[str, ...], str | None]:
    names = set(mesh.axis_names)
    pod = "pod" if "pod" in names else None
    return (("data",) if "data" in names else ()), pod


def _manual_axes(local_axes, pod_axis) -> tuple[str, ...]:
    """Pod-major ordering everywhere (rank = pod*D + data)."""
    return ((pod_axis,) if pod_axis else ()) + local_axes


def make_train_program(model: Model, mesh, rc: RunConfig, plan: HetPlan,
                       extra_batch_specs: dict[str, P] | None = None) -> TrainProgram:
    """extra_batch_specs: manual-axis PartitionSpecs for additional batch keys
    (e.g. whisper 'frames' (n_micro,B,F,D) or vlm 'mrope' (n_micro,3,B,S)),
    specs given for the stacked (leading n_micro) layout."""
    extra_batch_specs = extra_batch_specs or {}
    cfg = model.cfg
    local_axes, pod_axis = _dp_axes_of(mesh)
    hcfg = hetccl.HetCCLConfig(
        mode=rc.collective_mode, local_axes=local_axes, pod_axis=pod_axis,
        cross_dtype=jnp.dtype(rc.cross_dtype) if rc.cross_dtype else None,
        bucket_bytes=rc.bucket_bytes,
        n_channels=rc.n_channels,
        pipeline_chunk_bytes=rc.pipeline_chunk_bytes,
        backend=rc.backend, n_stripes=rc.n_stripes,
        wire_quant=rc.wire_quant)
    hcfg.resolved_mode()        # eager mode/backend/stripe validation (typos
    hcfg.resolved_stripes()     # fail at build, not inside the compiled step)
    if rc.policies is not None:
        # planner-emitted per-op policy table (DESIGN.md §12); the table
        # doesn't tune compression, so a run-level cross_dtype fills every
        # row that leaves it unset
        table = rc.policies
        if rc.cross_dtype:
            table = table.with_cross_dtype(jnp.dtype(rc.cross_dtype))
        table = table.with_wire_quant(rc.wire_quant)
        comm = comm_mod.create(
            local_axes, pod_axis, table=table,
            bucket_bytes=rc.bucket_bytes,
            pipeline_chunk_bytes=rc.pipeline_chunk_bytes)
    else:
        comm = comm_mod.from_config(hcfg)   # legacy single-policy facade
    manual_axes = _manual_axes(local_axes, pod_axis)
    rules = make_rules(cfg, mesh, rc.zero_stage)
    ctx = Ctx(rules=rules, manual=True, dp_axes=manual_axes)
    metas = model.abstract_params()
    pspecs = model.param_specs(rules)
    pspecs_manual = jax.tree.map(lambda s: manual_only(s, manual_axes), pspecs)
    fsdp_mask = jax.tree.map(
        lambda s: any("data" in ((e,) if isinstance(e, str) else tuple(e or ()))
                      for e in s), pspecs)
    dp_world = int(np.prod([dict(zip(mesh.axis_names, mesh.devices.shape))[a]
                            for a in manual_axes]))
    decay_mask = jax.tree.map(_decays, metas, is_leaf=is_meta)
    counter_names = model.counters()
    live_mask = jnp.asarray(plan.live_mask())          # (n_pods, n_micro_max)

    # ---- the shard_map body -------------------------------------------------
    def step_body(state, batch):
        params, opt, step = state["params"], state["opt"], state["step"]
        pod_idx = lax.axis_index(pod_axis) if pod_axis else 0
        live = live_mask[pod_idx] if pod_axis else live_mask[0]   # (n_micro,)

        def loss_fn(p, mb, w):
            loss_sum, count, aux = model.loss(p, mb, ctx)
            objective = (loss_sum + aux["loss"] * count) * w
            counters = {k: jnp.where(w > 0, aux[k], 0) for k in counter_names}
            return objective, (loss_sum * w, count * w, counters)

        def micro(carry, inp):
            g_acc, l_acc, c_acc = carry
            mb, w = inp
            (_, (ls, cnt, n)), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb, w)
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            return (g_acc, l_acc + ls, c_acc + cnt), n

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        mb_tree = {k: batch[k] for k in
                   ("tokens", "labels", *extra_batch_specs) if k in batch}
        init = (g0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
        # A lone micro-step is called, not scanned: through a length-1 scan
        # XLA's loop-invariant code motion leaves the layer loops inside it
        # twice in the compiled step (tests/test_chip_compile.py).
        if plan.n_micro_max == 1:
            (grads, loss_sum, count), counters = micro(
                init, jax.tree.map(lambda a: a[0], (mb_tree, live)))
            counters = jax.tree.map(lambda a: a[None], counters)
        else:
            (grads, loss_sum, count), counters = lax.scan(micro, init, (mb_tree, live))
        counters = moe_mod.reduce_counters(counters)

        with jax.named_scope(STEP_STATS_SCOPE):
            total_tokens = lax.psum(count, manual_axes)
            loss_total = lax.psum(loss_sum, manual_axes)
            counters = {k: (lax.psum if moe_mod.COUNTERS[k] == "sum" else lax.pmax)(
                v, manual_axes) for k, v in counters.items()}
        inv = 1.0 / jnp.maximum(total_tokens, 1.0)
        grads = jax.tree.map(lambda g: g * inv, grads)

        if rc.zero_stage >= 3:
            new_params, new_opt, gnorm = optim.zero3_step(
                params, grads, opt, step, rc, comm, fsdp_mask, decay_mask)
        else:
            new_params, new_opt, gnorm = optim.zero1_step(
                params, grads, opt, step, rc, comm, decay_mask)
        metrics = {"loss": loss_total * inv, "grad_norm": gnorm,
                   "tokens": total_tokens, **counters}
        return ({"params": new_params, "opt": new_opt, "step": step + 1}, metrics)

    # ---- specs --------------------------------------------------------------
    opt_manual_specs = _opt_specs(rc, pspecs_manual, manual_axes)
    state_manual_specs = {"params": pspecs_manual, "opt": opt_manual_specs,
                          "step": P()}
    batch_manual = P(None, manual_axes if len(manual_axes) > 1 else manual_axes[0], None)
    batch_spec_tree = {"tokens": batch_manual, "labels": batch_manual,
                       **extra_batch_specs}
    metric_specs = {"loss": P(), "grad_norm": P(), "tokens": P(),
                    **{k: P() for k in counter_names}}

    def step_body_installed(state, batch):
        # hetccl.current() must reflect this program's communicator while
        # the body traces: cfg-free call sites deep in the model
        # (fsdp_all_gather's adjoint resolves its ring policy at trace time,
        # DESIGN.md §10/§12) read the installed communicator, not the
        # trainer's explicit argument.
        with hetccl.use(comm):
            return step_body(state, batch)

    sm_step = compat.shard_map(
        step_body_installed, mesh=mesh,
        in_specs=(state_manual_specs, batch_spec_tree),
        out_specs=(state_manual_specs, metric_specs),
        axis_names=set(manual_axes), check_vma=False)

    # jit-level shardings (manual + auto axes combined)
    def named(spec_tree_):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree_)

    opt_full_specs = _opt_specs(rc, pspecs, manual_axes)
    state_shardings = named({"params": pspecs, "opt": opt_full_specs, "step": P()})
    batch_shardings = named(batch_spec_tree)

    step_jit = jax.jit(sm_step, in_shardings=(state_shardings, batch_shardings),
                       out_shardings=(state_shardings, named(metric_specs)),
                       donate_argnums=(0,))

    # ---- init ---------------------------------------------------------------
    def init_body(key):
        params = model.init(key, dtype=rc.param_dtype)
        if rc.zero_stage >= 3:
            # slice this rank's fsdp shards out of the full init
            def shard_leaf(p, spec):
                for dim, ent in enumerate(spec):
                    axes = (ent,) if isinstance(ent, str) else tuple(ent or ())
                    if "data" in axes:
                        n = lax.axis_size("data")
                        idx = lax.axis_index("data")
                        size = p.shape[dim] // n
                        return lax.dynamic_slice_in_dim(p, idx * size, size, dim)
                return p
            params = jax.tree.map(shard_leaf, params, pspecs_manual)
            opt = optim.zero3_init_opt(params)
        else:
            opt = optim.zero1_init_opt(params, dp_world)
            opt["master"] = optim.zero1_master_from_params(params, manual_axes)
        if optim.ef_codec(rc):
            opt["ef"] = optim.ef_init(params)
        return {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}

    sm_init = compat.shard_map(init_body, mesh=mesh, in_specs=P(),
                               out_specs=state_manual_specs,
                               axis_names=set(manual_axes), check_vma=False)
    init_jit = jax.jit(sm_init, out_shardings=state_shardings)

    return TrainProgram(model=model, mesh=mesh, rc=rc, plan=plan, hcfg=hcfg,
                        comm=comm, rules=rules, step_fn=step_jit,
                        init_fn=init_jit, state_shardings=state_shardings,
                        batch_sharding=batch_shardings)


def rebuild_program(prog: TrainProgram, mesh, rc: RunConfig | None = None,
                    plan: HetPlan | None = None,
                    extra_batch_specs: dict[str, P] | None = None) -> TrainProgram:
    """Rebuild a program on a new mesh — the elastic membership-change path
    (``repro.elastic.membership``, DESIGN.md §13).

    Model and non-planned run knobs carry over from ``prog``; pass the
    re-planned ``rc``/``plan`` from ``ft.replan_auto`` (fresh shares and
    policy table for the surviving topology).  The new program's collective
    axes come from the new mesh, so a 1-pod survivor mesh compiles with no
    pod axis and the communicator degrades to flat exactly as ``comm.create``
    resolves it.
    """
    return make_train_program(prog.model, mesh, rc or prog.rc,
                              plan or prog.plan,
                              extra_batch_specs=extra_batch_specs)


def _decays(meta) -> bool:
    """Weight decay applies to matrices: a leaf with more than one axis
    besides its stacked layer axes (norm scales, biases and other vectors
    are not decayed, stacked or not)."""
    return sum(a not in ("layers", "group") for a in meta.axes) > 1


def _opt_specs(rc: RunConfig, pspecs, manual_axes):
    dp = manual_axes if len(manual_axes) > 1 else manual_axes[0]
    # EF residuals (DESIGN.md §17) are rank-local flat arrays under both
    # stages: sharded over the full DP axes, never replicated — each rank
    # owns the quantization error of its own gradient contribution.
    ef = ({"ef": jax.tree.map(lambda _: P(dp), pspecs)}
          if optim.ef_codec(rc) else {})
    if rc.zero_stage >= 3:
        f32specs = pspecs
        return {"m": f32specs, "v": f32specs, "master": f32specs, **ef}
    flat = jax.tree.map(lambda _: P(dp), pspecs)
    return {"m": flat, "v": flat, "master": flat, **ef}
