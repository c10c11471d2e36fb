"""Readings that set a cell's limits: the program, the control and the
planted faults, each against the plain reference, on many seeds in one
process.  The benchmark's own runs never run this.

    python bench/control.py --workload <cell> --seeds 1,2,3 --out <file.json>
        [--against control,half_batch,stale_params,no_exchange] [--against-seeds N]

For each seed: the program's readings through the check steps (as a run
takes them) and the float32 reference's; on the first ``--against-seeds``
seeds (all by default), also each reading named by ``--against``:

- ``control``: the reference with every matrix product in float8 e4m3
  (``bench/references/``), put in the program's place;
- ``half_batch``: the second half of every batch's rows replaced by the
  first half, so the step's mean is taken over half the batch;
- ``stale_params``: every step's new parameters thrown away and the old
  ones kept, as when the ZeRO-1 parameter all-gather is left out (cells on
  more than one chip);
- ``no_exchange``: the gradient all-reduce returns each rank's own
  gradient (cells on more than one chip).

A step that returns its state unchanged reads 1 on ``grad``, ``delta`` and
``param`` by construction and needs no run.  Every reading is judged under
the cell's limits (``bench/limits/<cell>.json``) as a run judges it.
Writes every gap (``bench/check.py``), each verdict and the time each
reading took; exits 1 if the program reads not correct on a seed, or the
control or a fault reads correct.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


class HalfBatch:
    """A program whose batches carry only their first half of rows, twice."""

    def __init__(self, program):
        self._p = program

    def __getattr__(self, name):
        return getattr(self._p, name)

    def put(self, batch):
        out = {}
        for k, v in batch.items():
            v = v.copy()
            half = v.shape[1] // 2
            v[:, half:] = v[:, :half]
            out[k] = v
        return self._p.put(out)


class StaleParams:
    """A program whose steps keep the parameters they were given."""

    def __init__(self, program):
        import jax
        self._p = program
        self._copy = jax.jit(lambda p: jax.tree.map(lambda x: x.copy(), p),
                             out_shardings=program.prog.state_shardings["params"])

    def __getattr__(self, name):
        return getattr(self._p, name)

    def step(self, state, batch):
        from bench import harness
        keep = self._copy(state["params"])
        new, metrics = self._p.step(state, batch)
        harness.free(new["params"])
        return {**new, "params": keep}, metrics


def readings_of(program, seed, steps):
    from bench import harness
    t = time.perf_counter()
    state, readings, _ = harness.program_readings(program, seed, steps)
    harness.free(state)
    return readings, time.perf_counter() - t


def _judged(found, limits):
    """The gaps with their leaves, and the verdict a run would give."""
    from bench import check
    return {"correct": check.verdict(found, limits)[0],
            **{k: {"value": v[0], "where": v[1]} for k, v in found.items()}}


def _fault(readings, ref, stacked, limits):
    """A fault's judged gaps; a fault that crashes has failed."""
    from bench import check
    try:
        return _judged(check.gaps(readings(), ref, stacked), limits)
    except Exception as e:  # noqa: BLE001 -- any crash is the fault failing
        return {"correct": False, "error": repr(e)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--against", default="control")
    ap.add_argument("--against-seeds", type=int, default=None)
    args = ap.parse_args(argv)
    import jax
    from bench import check, harness, program as program_mod, weights

    cell = harness.load_cell(args.workload)
    arch, job, limits = cell["arch"], cell["job"], cell["limits"]
    stacked = weights.stacked(arch)
    seeds = [int(s) for s in args.seeds.split(",")]
    against = [f for f in args.against.split(",") if f]
    others = seeds[:args.against_seeds]
    program_mod.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()[:cell["chips"]]
    program = program_mod.build(arch, job, devices)
    steps = job["check_steps"]
    out = {"workload": cell["name"], "device": devices[0].device_kind,
           "limits": limits, "seeds": {}}
    wrapped = {kind: cls(program) for kind, cls in
               (("half_batch", HalfBatch), ("stale_params", StaleParams)) if kind in against}

    def save():
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))

    refs = {}
    for seed in seeds:
        rec = out["seeds"][str(seed)] = {}
        prog, rec["program_s"] = readings_of(program, seed, steps)
        t = time.perf_counter()
        refs[seed] = ref = harness.reference_readings(arch, job, seed)
        rec["reference_s"] = time.perf_counter() - t
        rec["program"] = _judged(check.gaps(prog, ref, stacked), limits)
        rec["loss"] = {"program": prog["loss"], "reference": ref["loss"]}
        rec["gnorm_reference"] = ref["gnorm"]
        if seed in others and "control" in against:
            t = time.perf_counter()
            ctl = harness.reference_readings(arch, job, seed, precision="fp8")
            rec["control_s"] = time.perf_counter() - t
            rec["control"] = _judged(check.gaps(ctl, ref, stacked), limits)
            rec["loss"]["control"] = ctl["loss"]
        for kind, broken in wrapped.items():
            if seed in others:
                rec[kind] = _fault(lambda: readings_of(broken, seed, steps)[0], ref, stacked,
                                   limits)
        print(json.dumps({"seed": seed, **rec}), flush=True)
        save()
    if "no_exchange" in against:
        program_mod.plant_no_exchange()
        broken = program_mod.build(arch, job, devices)
        for seed in others:
            out["seeds"][str(seed)]["no_exchange"] = _fault(
                lambda: readings_of(broken, seed, steps)[0], refs[seed], stacked, limits)
            print(json.dumps({"seed": seed, "no_exchange": out["seeds"][str(seed)]["no_exchange"]}),
                  flush=True)
        save()
    wrong = [(seed, kind) for seed, rec in out["seeds"].items() for kind, r in rec.items()
             if isinstance(r, dict) and "correct" in r and r["correct"] != (kind == "program")]
    out["wrong_verdicts"] = wrong
    save()
    print(f"verdicts: {'all as they must be' if not wrong else wrong}", file=sys.stderr)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
