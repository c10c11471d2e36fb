"""Serving launcher: prefill/decode any assigned architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        [--batch 4] [--prompt-len 32] [--max-new 16] [--reduced] \
        [--mesh-shape 2,2,2]
"""
import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full-size", dest="reduced", action="store_false")
    ap.add_argument("--mesh-shape", default="2,2,2")
    args = ap.parse_args()

    shape = tuple(int(x) for x in args.mesh_shape.split(","))
    n_dev = 1
    for s in shape:
        n_dev *= s
    os.environ.setdefault("XLA_FLAGS",
                          f"--xla_force_host_platform_device_count={n_dev}")

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import compat
    from repro.launch.cache import enable_compile_cache
    from repro.models import build
    from repro.serve.engine import Batcher, Request, make_serve_programs

    enable_compile_cache()
    axes = ("pod", "data", "model")[-len(shape):]
    mesh = compat.make_mesh(shape, axes)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    max_len = args.prompt_len + args.max_new
    progs = make_serve_programs(model, mesh, batch=args.batch,
                                seq_len=args.prompt_len, max_len=max_len)
    with compat.set_mesh(mesh):
        params = jax.jit(lambda k: model.init(k),
                         out_shardings=progs.param_shardings)(
            jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        reqs = [Request(i, rng.randint(0, cfg.vocab, args.prompt_len // 2)
                        .astype(np.int32), args.max_new)
                for i in range(args.batch)]
        b = Batcher(progs, params, batch_slots=args.batch,
                    prompt_len=args.prompt_len, max_len=max_len)
        t0 = time.perf_counter()
        done = b.run(reqs)
        dt = time.perf_counter() - t0
    tok = sum(len(r.out) for r in done)
    print(f"arch={cfg.name}: served {len(done)} reqs, {tok} tokens "
          f"in {dt:.2f}s ({tok / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
