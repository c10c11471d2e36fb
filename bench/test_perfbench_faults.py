"""The comparison that decides ``correct`` fails what it must, on the CPU
at a size a test run can hold: a run of each cell with its timed path
broken underneath comes out not correct under the cell's own limits, and
the control (the reference computed with float8 matrix products) reads
well above the program.

The faults are those a training cell can have: a step that returns its
state unchanged; half of the batch left out, the mean taken over the rest;
and, on more than one chip, the exchange between chips left out, of the
gradients (all-reduce) or of the new parameters (all-gather).  (A token
altered where it is produced is a fault of served cells.)
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import json  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def tiny_cell(name):
    """The cell at its family's CPU size (``TINY``), seq 64."""
    from bench import families, harness
    cell = harness.load_cell(name)
    cell["arch"].update(families.of(cell["arch"]).TINY)
    cell["job"].update(seq=64)
    cell["job"]["backend"] = "xla"          # the DMA rings run only on a TPU
    return cell


def run(cell, program=None, seed=2**31 + 11):
    import time
    import jax
    from bench import harness
    return harness.execute(cell, seed, 0.05, False, jax.devices(), t_start=time.perf_counter(),
                           program=program, cache=False)


class Broken:
    """The cell's program with one call replaced."""

    def __init__(self, program):
        self._p = program

    def __getattr__(self, name):
        return getattr(self._p, name)


class Unchanged(Broken):
    def step(self, state, batch):
        import jax
        import jax.numpy as jnp
        from bench import harness
        new, metrics = self._p.step(jax.tree.map(jnp.copy, state), batch)
        jax.block_until_ready(metrics)
        harness.free(new)
        return state, metrics


def _program(cell):
    import jax
    from bench import program
    return program.build(cell["arch"], cell["job"], jax.devices())


@pytest.fixture(scope="module")
def built():
    """One tiny program per cell, built and compiled once for the module."""
    cache = {}

    def get(name):
        if name not in cache:
            cell = tiny_cell(name)
            cache[name] = (cell, _program(cell))
        return cache[name]
    return get


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, built):
    cell, program = built(name)
    res = run(cell, program)
    assert res["correct"], res["check"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_step_is_not_correct(name, fault, built):
    cell, program = built(name)
    from bench.control import HalfBatch
    res = run(cell, {"unchanged": Unchanged, "half_batch": HalfBatch}[fault](program))
    assert not res["correct"], res["check"]


ACROSS_CHIPS = [c for c in CELLS if "pod" in tiny_cell(c)["job"]["mesh"]]


@pytest.mark.parametrize("name", ACROSS_CHIPS)
def test_stale_params_are_not_correct(name, built):
    """The parameter all-gather left out: every step computes with the
    parameters it started from."""
    from bench.control import StaleParams
    cell, program = built(name)
    res = run(cell, StaleParams(program))
    assert not res["correct"], res["check"]
    assert res["check"]["param_gap"]["value"] > cell["limits"]["param"], res["check"]


@pytest.mark.parametrize("name", ACROSS_CHIPS)
def test_no_exchange_is_not_correct(name, monkeypatch):
    from bench import program
    program._import_path()
    from repro.core import hetccl
    monkeypatch.setattr(hetccl, "tree_all_reduce", lambda tree, cfg=None, **_: tree)
    cell = tiny_cell(name)
    res = run(cell, _program(cell))
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_above_the_program(name, built):
    """The float8 control, put in the program's place, reads at least three
    times the program's worst-leaf gradient gap.  This is a proxy at a size
    a test run can hold: the cell's limits hold at the cell's own size,
    where ``bench/control.py`` requires the control to come out not
    correct."""
    from bench import check, harness, weights
    cell, program = built(name)
    seed = 2**32 + 5
    state, prog, _ = harness.program_readings(program, seed, cell["job"]["check_steps"])
    harness.free(state)
    ref = harness.reference_readings(cell["arch"], cell["job"], seed)
    ctl = harness.reference_readings(cell["arch"], cell["job"], seed, precision="fp8")
    stacked = weights.stacked(cell["arch"])
    assert (check.gaps(ctl, ref, stacked)["grad"][0]
            >= 3 * check.gaps(prog, ref, stacked)["grad"][0])
