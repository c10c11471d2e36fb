"""Chip smoke test: train smollm-135m at full width on a TPU through the
launcher's own entry point (``repro.launch.train.main``), in this process.

    python chip_smoke.py                # one chip, (data=1, model=1) mesh
    python chip_smoke.py --four-chips   # one four-chip host, (pod=2, data=2,
                                        # model=1): hier rings, xla vs pallas

One chip: 30 layers, d_model 576, bf16 params, seq 2048, micro-batch 4, two
micro-steps, the default per-op collective policy.  Four chips: the same
model twice from the same seed, hierarchical all-reduce with the cross-pod
stage on XLA's ring and then on the Pallas DMA rings; the loss curves must
agree to bf16 resolution.

Exits non-zero, with no result line, when JAX finds no TPU, when the repo's
``src/`` is not next to this file, on a non-finite or non-falling loss, or
when a kernel entry resolves to anything but its TPU variant.  On success
the last line of output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Step times are host-clock seconds around ``block_until_ready`` (batch feed
included); step 0 carries the compile and is left out of the steady median.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CKPT_DIR = ROOT / ".chip_smoke_ckpt"      # gitignored, wiped around each run

MODEL = ["--arch", "smollm-135m", "--full-size", "--seq", "2048",
         "--micro-batch", "4", "--n-micro", "2", "--seed", "0"]
ONE_CHIP = ["--mesh-shape", "1,1", "--steps", "8"]
FOUR_CHIPS = ["--mesh-shape", "2,2,1", "--mode", "hier", "--policy", "legacy",
              "--steps", "8"]
KERNEL_OPS = ("attention", "collective_reduce")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


def _tpu_devices():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's devices are {devs}")
    return devs


def train(argv: list[str]) -> tuple[list[dict], float, int]:
    """One launcher run in this process from a fresh checkpoint directory.
    Returns (per-step history, backend compile seconds, programs compiled)."""
    import jax
    from repro.launch import train as launch_train

    compiles: list[float] = []

    def on_duration(event, secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            compiles.append(secs)

    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        hist = launch_train.main(argv + ["--ckpt-dir", str(CKPT_DIR)])
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        shutil.rmtree(CKPT_DIR, ignore_errors=True)
    return hist, sum(compiles), len(compiles)


def report(name: str, hist: list[dict], compile_s: float, n_programs: int,
           tokens_per_step: int, devices) -> list[float]:
    """Print a run's losses and times; fail on a non-finite or non-falling
    loss.  Returns the loss curve."""
    losses = [h["loss"] for h in hist]
    print(f"[{name}] backend compile: {compile_s} s over {n_programs} programs")
    for h in hist:
        print(f"[{name}] step {h['step']}  loss {h['loss']}  "
              f"grad_norm {h['grad_norm']}  step_s {h['step_s']}")
    steady = [h["step_s"] for h in hist[1:]]
    med = statistics.median(steady)
    print(f"[{name}] steady step_s (steps 1..{len(hist) - 1}): median {med} "
          f"min {min(steady)} max {max(steady)}; "
          f"tokens/s {tokens_per_step / med}")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print(f"[{name}] peak_bytes_in_use per device: {peaks}")
    if not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{name}: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"{name}: loss did not fall: {losses}")
    return losses


def tokens_per_step(argv: list[str], dp: int) -> int:
    def arg(flag):
        return int(argv[argv.index(flag) + 1])
    return arg("--n-micro") * arg("--micro-batch") * dp * arg("--seq")


def one_chip(devices) -> None:
    argv = MODEL + ONE_CHIP
    hist, compile_s, n = train(argv)
    report("1chip", hist, compile_s, n, tokens_per_step(argv, 1), devices[:1])


def four_chips(devices) -> None:
    import jax.numpy as jnp
    import numpy as np
    curves = {}
    for backend in ("xla", "pallas"):
        argv = MODEL + FOUR_CHIPS + ["--backend", backend]
        hist, compile_s, n = train(argv)
        curves[backend] = report(f"4chip-hier-{backend}", hist, compile_s, n,
                                 tokens_per_step(argv, 4), devices[:4])
    xla, pallas = np.array(curves["xla"]), np.array(curves["pallas"])
    rtol = float(jnp.finfo(jnp.bfloat16).eps)
    print(f"[4chip] |pallas - xla| / |xla| per step: "
          f"{(np.abs(pallas - xla) / np.abs(xla)).tolist()} (rtol {rtol})")
    if not np.allclose(pallas, xla, rtol=rtol, atol=0.0):
        raise SmokeFailure(f"pallas and xla loss curves differ: "
                           f"{pallas.tolist()} vs {xla.tolist()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip hier xla-vs-pallas phase")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SmokeFailure(f"the repo's src/repro is not next to {__file__}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    devices = _tpu_devices()
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        raise SmokeFailure(f"need {want} chips, JAX sees {len(devices)}")
    d0 = devices[0]
    print(f"device: {d0.platform} {d0.device_kind} x{len(devices)}; "
          f"compile cache {cache}")

    from repro.core import tacc
    from repro.kernels import ops  # noqa: F401  (registers the kernel entries)
    variants = {op: tacc.resolve_variant(op) for op in KERNEL_OPS}
    print(f"TACC variants: {variants}")
    if set(variants.values()) != {"tpu"}:
        raise SmokeFailure(f"kernel entries not on their tpu variant: "
                           f"{variants}")

    (four_chips if args.four_chips else one_chip)(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        sys.exit(1)
