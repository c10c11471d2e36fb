"""The benchmark's one door into the program under test (``src/repro``).

Every call the harness makes into the system is in this module or in a
family module's ``model_config`` (``bench/families/``), which this module
calls, and the benchmark depends on these program entry points only:

- ``repro.configs.base.ModelConfig`` (a family's ``model_config``, the only
  program entry point a family module uses) and ``RunConfig``;
- ``repro.models.build``;
- ``repro.core.balance.uniform_plan``;
- ``repro.core.compat.make_mesh``;
- ``repro.train.trainer.make_train_program``: its ``step_fn`` (the timed
  call), ``init_fn`` (only through ``jax.eval_shape``, to check the state
  layout), ``state_shardings`` and ``batch_sharding``;
- the ZeRO-1 state layout of ``repro.train.optim``: ``{"params", "opt":
  {"m", "v", "master"}, "step"}``, optimizer leaves flat f32, padded to a
  multiple of the data-parallel world; the params at the paths the
  family's ``PATHS`` names;
- ``repro.launch.cache.enable_compile_cache``;
- ``repro.core.hetccl.tree_all_reduce`` (planted faults only).
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from bench import families, weights

SRC = Path(__file__).resolve().parents[1] / "src"


def _import_path():
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def enable_compile_cache() -> str:
    _import_path()
    from repro.launch.cache import enable_compile_cache as enable
    return enable()


def model_config(arch: dict):
    """The program's ModelConfig of a configuration, as its family maps it."""
    _import_path()
    return families.of(arch).model_config(arch)


def _set(tree: dict, path: tuple, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _get(tree: dict, path: tuple):
    for k in path:
        tree = tree[k]
    return tree


@dataclasses.dataclass
class Program:
    arch: dict
    job: dict
    prog: Any                 # repro.train.trainer.TrainProgram
    dp_world: int

    @property
    def mesh(self):
        return self.prog.mesh

    def step(self, state, batch):
        """The timed call: one optimizer step, state donated."""
        return self.prog.step_fn(state, batch)

    def put(self, batch: dict) -> dict:
        return jax.device_put(batch, self.prog.batch_sharding)

    @property
    def paths(self) -> dict:
        """Canonical leaf name -> path in the program's params."""
        return families.of(self.arch).PATHS

    # ---- state ------------------------------------------------------------
    def _state_from_weights(self, w: dict) -> dict:
        params: dict = {}
        master, zeros = {}, {}
        for name, path in self.paths.items():
            _set(params, path, w[name])
            flat = w[name].reshape(-1).astype(jnp.float32)
            pad = -flat.size % self.dp_world
            _set(master, path, jnp.pad(flat, (0, pad)))
            _set(zeros, path, jnp.zeros(flat.size + pad, jnp.float32))
        return {"params": params,
                "opt": {"m": zeros, "v": jax.tree.map(jnp.zeros_like, zeros),
                        "master": master},
                "step": jnp.zeros((), jnp.int32)}

    def _initial_state(self, lo, hi) -> dict:
        return self._state_from_weights(weights.make(
            self.arch, weights.seed_key(lo, hi), jnp.bfloat16))

    def abstract_state(self):
        return jax.eval_shape(self._initial_state, *weights.seed_words(0))

    def check_layout(self):
        """The state this module builds has the program's own layout."""
        ours = self.abstract_state()
        theirs = jax.eval_shape(self.prog.init_fn, jax.random.PRNGKey(0))
        a = jax.tree.map(lambda x: (x.shape, x.dtype), ours)
        b = jax.tree.map(lambda x: (x.shape, x.dtype), theirs)
        if a != b:
            raise ValueError(f"state layout differs from the program's:\n{a}\n{b}")

    def make_state(self, seed: int):
        """The train state from the benchmark's weights, made on the
        device in one jitted call: bf16 params, f32 master copy, zero
        moments, step 0."""
        fn = jax.jit(self._initial_state, out_shardings=self.prog.state_shardings)
        return fn(*weights.seed_words(seed))

    # ---- readouts for the comparison ----------------------------------------
    def _per_leaf(self, fn, tree_a, tree_b=None) -> dict[str, jax.Array]:
        """``fn(leaf, other, shape, stacked)`` over the program-layout tree
        ``tree_a`` (flat, padded optimizer leaves cut to their size) and the
        canonical tree ``tree_b``, keyed by canonical name."""
        shapes = weights.shapes(self.arch)
        stacked = weights.stacked(self.arch)
        out = {}
        for name, path in self.paths.items():
            n = int(np.prod(shapes[name]))
            a = _get(tree_a, path)[:n]
            b = None if tree_b is None else tree_b[name]
            out[name] = fn(a, b, shapes[name], name in stacked)
        return out

    def grad_norms(self, state) -> dict[str, np.ndarray]:
        """Per-leaf norms of the gradient the optimizer got at its first
        step, worked out from the first moment: g = m / (1 - beta1)."""
        b1 = self.job["optimizer"]["beta1"]

        def norms(opt):
            return self._per_leaf(
                lambda a, _, s, st: _row_norms(a, s, st) / (1.0 - b1), opt["m"])
        return _host(jax.jit(norms)(state["opt"]))

    def delta_norms(self, state, seed: int) -> dict[str, np.ndarray]:
        """Per-leaf norms of the master weights' change since step 0."""
        return self._change_norms(state["opt"]["master"], seed)

    def param_norms(self, state, seed: int) -> dict[str, np.ndarray]:
        """Per-leaf norms of the parameters' change since step 0: the
        parameters the next step computes with, as the ZeRO-1 all-gather
        left them."""
        return self._change_norms(state["params"], seed)

    def _change_norms(self, tree, seed: int) -> dict[str, np.ndarray]:
        def norms(tree, lo, hi):
            w0 = weights.make(self.arch, weights.seed_key(lo, hi), jnp.bfloat16)
            w0 = {k: v.reshape(-1).astype(jnp.float32) for k, v in w0.items()}
            flat = jax.tree.map(lambda x: x.reshape(-1), tree)
            return self._per_leaf(
                lambda a, b, s, st: _row_norms(a.astype(jnp.float32) - b, s, st),
                flat, w0)
        return _host(jax.jit(norms)(tree, *weights.seed_words(seed)))

    def hlo_text(self, abstract_state, abstract_batch) -> str:
        return self.prog.step_fn.lower(abstract_state, abstract_batch).compile().as_text()


def _row_norms(flat, shape, stacked: bool):
    """Norm of each layer's slice of a stacked leaf, or of the whole leaf,
    as a vector."""
    if stacked:
        return jnp.sqrt(jnp.sum(jnp.square(flat.reshape(shape[0], -1)), axis=1))
    return jnp.sqrt(jnp.sum(jnp.square(flat)))[None]


def _host(tree) -> dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float64) for k, v in jax.device_get(tree).items()}


def build(arch: dict, job: dict, devices) -> Program:
    """The training program of one cell, built as the launcher builds it
    (``repro.launch.train.main``): the full configuration, a bf16 ZeRO-1
    RunConfig, a uniform micro-batch plan, on a mesh of ``devices``."""
    _import_path()
    from repro.configs.base import RunConfig
    from repro.core import compat
    from repro.core.balance import uniform_plan
    from repro.models import build as build_model
    from repro.train.trainer import make_train_program
    axes = tuple(a for a in ("pod", "data", "model") if a in job["mesh"])
    shape = tuple(job["mesh"][a] for a in axes)
    mesh = compat.make_mesh(shape, axes, devices=devices[:int(np.prod(shape))])
    opt = job["optimizer"]
    rc = RunConfig(
        zero_stage=job["zero_stage"], collective_mode=job["collective_mode"],
        backend=job["backend"], policies=None, n_micro=job["n_micro"],
        learning_rate=opt["learning_rate"], weight_decay=opt["weight_decay"],
        beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["eps"],
        grad_clip=opt["grad_clip"], param_dtype=arch["torch_dtype"])
    n_pods = job["mesh"].get("pod", 1)
    plan = uniform_plan(n_pods, job["n_micro"] * n_pods, job["micro_batch"])
    prog = make_train_program(build_model(model_config(arch)), mesh, rc, plan)
    dp = job["mesh"].get("pod", 1) * job["mesh"].get("data", 1)
    if prog.batch_shape(job["seq"]) != (job["n_micro"], job["micro_batch"] * dp, job["seq"]):
        raise ValueError(f"the program's batch {prog.batch_shape(job['seq'])} "
                         f"is not the job's")
    return Program(arch=arch, job=job, prog=prog, dp_world=dp)


def plant_no_exchange():
    """A planted fault: the gradient all-reduce returns each rank's own
    gradient.  Affects programs built after the call."""
    _import_path()
    from repro.core import hetccl
    hetccl.tree_all_reduce = lambda tree, cfg=None, **_: tree
