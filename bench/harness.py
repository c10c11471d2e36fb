"""Run one cell of the benchmark once.

Everything about a cell is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic; the configuration file
(``bench/configs/<config>.json``) holds the model's published sizes and
names its block family (``bench/families/<family>.py``: the weights'
layout, their paths in the program, the program's model config and the
FLOP count) and its plain reference (``bench/references/<reference>.py``);
the traffic file (``bench/traffic/<traffic>.json``) is the training job; each
metric is read by ``bench/metrics/<metric>.py``; the limits of the
comparison are in ``bench/limits/<cell>.json``.

A run: build the program, make the state from the seed, drive it through
the check steps (these also warm up, and compile on a cold cache), then
train back to back for ``--seconds`` (the measured window), then, with
``--trace 1``, trace a few more steps.  After the window the program's
state is freed and the reference trains from the same weights on the same
batches; the comparison decides ``correct``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

from bench import feed

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, spec: dict | None = None) -> dict:
    """The cell's workload entry, configuration, traffic, metric names and
    limits, found by name: the configuration at its ``file``, the traffic
    at ``bench/traffic/<traffic>.json``, the limits at
    ``bench/limits/<cell>.json``.  The configuration names its family and
    reference modules, found when they are used."""
    spec = spec or json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    arch = json.loads((ROOT / conf["file"]).read_text())
    job = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    limits_path = BENCH / "limits" / f"{name}.json"
    return {"name": name, "chips": w["chips"], "arch": arch, "job": job,
            "end_to_end": [m["name"] for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m["name"] for m in spec["per_layer"] if applies(m)],
            "units": {m["name"]: m["unit"]
                      for m in spec["end_to_end"] + spec["per_layer"]},
            "limits": (json.loads(limits_path.read_text())["limits"]
                       if limits_path.exists() else None)}


def read_metric(name: str, run) -> float | None:
    return _module(BENCH / "metrics" / f"{name}.py").read(run)


def reference_module(arch: dict):
    """The plain reference that the configuration ``arch`` names."""
    return importlib.import_module(f"bench.references.{arch['reference']}")


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read it."""
    arch: dict
    job: dict
    chips: int
    device_kind: str
    peak: dict
    setup_s: float = math.nan
    compile_s: float = 0.0
    window_s: float = math.nan
    window_tokens: int = 0
    memory: list = dataclasses.field(default_factory=list)    # memory_stats() per device
    reduction: object = None      # xplane.Reduction of the traced steps
    instrs: dict = dataclasses.field(default_factory=dict)   # (module, op) -> hlo.Instr

    @property
    def tokens_per_s(self) -> float:
        return self.window_tokens / self.window_s


class CompileClock:
    """Sums JAX's backend-compile events and counts them."""

    def __init__(self):
        self.total = 0.0
        self.count = 0

    def __call__(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.total += secs
            self.count += 1

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)


def train_loop(program, state, seed: int, first: int, *, seconds: float | None = None,
               steps: int | None = None, losses: list | None = None):
    """The timed loop: batch, step, block, back to back, from step
    ``first``, for ``seconds`` (at least one step) or ``steps`` steps.
    Returns (state, steps done, elapsed seconds, non-finite losses)."""
    import jax
    arch, job = program.arch, program.job
    n, bad = 0, 0
    t0 = time.perf_counter()
    while True:
        k = first + n
        with jax.profiler.StepTraceAnnotation("train_step", step_num=k):
            with jax.profiler.TraceAnnotation("batch"):
                batch = program.put(feed.job_batch(seed, k, arch, job))
            with jax.profiler.TraceAnnotation("dispatch"):
                state, metrics = program.step(state, batch)
            with jax.profiler.TraceAnnotation("block"):
                jax.block_until_ready((state, metrics))
        loss = float(metrics["loss"])
        if losses is not None:
            losses.append(loss)
        bad += not math.isfinite(loss)
        n += 1
        elapsed = time.perf_counter() - t0
        if (steps is not None and n >= steps) or (seconds is not None and elapsed >= seconds):
            return state, n, elapsed, bad


def program_readings(program, seed: int, check_steps: int):
    """Make the state and drive it through the check steps with the
    window's own loop.  Returns (state, readings, seconds spent reading)."""
    state = program.make_state(seed)
    losses: list = []
    read_s = 0.0
    readings: dict = {"loss": losses}
    for k in range(check_steps):
        state, _, _, _ = train_loop(program, state, seed, k, steps=1, losses=losses)
        t = time.perf_counter()
        if k == 0:
            readings["grad"] = program.grad_norms(state)
        if k == check_steps - 1:
            readings["delta"] = program.delta_norms(state, seed)
            readings["param"] = program.param_norms(state, seed)
        read_s += time.perf_counter() - t
    return state, readings, read_s


def reference_readings(arch: dict, job: dict, seed: int, *, precision: str = "f32") -> dict:
    import jax
    import jax.numpy as jnp
    from bench import weights
    w0 = jax.jit(lambda lo, hi: weights.make(arch, weights.seed_key(lo, hi), jnp.bfloat16))(
        *weights.seed_words(seed))
    batches = [feed.job_batch(seed, k, arch, job) for k in range(job["check_steps"])]
    return reference_module(arch).train_readings(
        w0, batches, arch, job["optimizer"], precision=precision,
        rows=job["reference_rows"])


def free(tree):
    import jax
    for x in jax.tree.leaves(tree):
        x.delete()


def execute(cell: dict, seed: int, seconds: float, trace: bool, devices, *,
            t_start: float, clock: CompileClock | None = None,
            program=None, cache: bool = True) -> dict:
    """One run of ``cell``; returns the result object (not yet printed)."""
    import jax
    from bench import check, program as program_mod, weights, work
    arch, job = cell["arch"], cell["job"]
    devices = list(devices)[:cell["chips"]]
    kind = devices[0].device_kind
    run = Run(arch=arch, job=job, chips=cell["chips"], device_kind=kind,
              peak=work.peak(kind) if devices[0].platform == "tpu" else {})
    clock = clock or CompileClock()
    if cache:
        program_mod.enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    program = program or program_mod.build(arch, job, devices)
    program.check_layout()
    state, readings, read_s = program_readings(program, seed, job["check_steps"])
    run.setup_s = time.perf_counter() - t_start - read_s
    run.compile_s = clock.total
    compiles_before = clock.count

    first = job["check_steps"]
    state, n, run.window_s, failed = train_loop(program, state, seed, first,
                                                seconds=seconds)
    run.window_tokens = n * feed.tokens_per_step(job)
    window_compiles = clock.count - compiles_before

    abstract = None
    if trace:
        abstract = (jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), state),
                    jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
                                 program.put(feed.job_batch(seed, 0, arch, job))))
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
        try:
            state, _, _, bad = train_loop(program, state, seed, first + n,
                                          steps=job["trace_steps"])
        finally:
            jax.profiler.stop_trace()
        failed += bad
    mesh_devices = list(program.mesh.devices.flat)
    run.memory = [d.memory_stats() or {} for d in mesh_devices]
    free(state)

    if trace:
        from bench import hlo, xplane
        module, instrs = hlo.parse(program.hlo_text(*abstract))
        run.instrs = {(module, op): ins for op, ins in instrs.items()}
        try:
            tr = xplane.load(xplane.find(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

        def classify(mod, op):
            return instrs.get(op) if mod == module else None

        run.reduction = xplane.reduce(tr, classify, devices=[d.id for d in mesh_devices])

    ref = reference_readings(arch, job, seed)
    found = check.gaps(readings, ref, weights.stacked(arch))
    correct, shown = check.verdict(found, cell["limits"])
    correct = correct and failed == 0

    names = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for name in names:
        value = read_metric(name, run)
        if value is not None:
            metrics[name] = {"value": value, "unit": cell["units"][name]}
    result = {"correct": correct, "attempted": n + (job["trace_steps"] if trace else 0),
              "failed": failed, "metrics": metrics,
              "device": {"platform": devices[0].platform, "kind": kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": max(st.get("peak_bytes_in_use", 0)
                                                  for st in run.memory)}}
    if trace:
        red = run.reduction
        result["device"]["busy_s"] = sum(red.busy_ns) / red.n_devices * 1e-9
        result["device"]["window_s"] = red.window_ns * 1e-9
        result["breakdown"] = breakdown(run)
    result["notes"] = {"window_compiles": window_compiles, "steps": n,
                       "where": {k: v[1] for k, v in found.items()},
                       "program_loss": readings["loss"], "reference_loss": ref["loss"],
                       "reference_gnorm": ref["gnorm"], "memory_stats": run.memory[0]}
    result["check"] = shown
    return result


def breakdown(run) -> dict:
    """The ten device operations that took most time (by the name the
    reduction gives them, seconds per device over the traced steps) and
    the ten longest idle gaps of the first device, by host span."""
    red = run.reduction
    by_label: dict = {}
    for key, rec in red.ops.items():
        ins = run.instrs.get(key)
        label = ins.label if ins is not None else f"{key[1]} ({key[0] or 'no module'})"
        by_label[label] = by_label.get(label, 0.0) + rec["ns"] * 1e-9 / red.n_devices
    ops = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[name, s] for s, name in red.gaps[:10]]}


def main(argv, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    clock = CompileClock()
    import jax
    clock.install()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX sees {devices}", file=sys.stderr)
        return 2
    result = execute(cell, args.seed, args.seconds, bool(args.trace), devices,
                     t_start=t_start, clock=clock)
    for name, rec in result["check"].items():
        print(f"check {name} {rec['value']!r} limit {rec['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
