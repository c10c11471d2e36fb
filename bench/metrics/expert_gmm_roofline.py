"""The ragged grouped-matmul kernels' share of their roofline, in %: for
each device event of ``_gmm_ragged_kernel`` (the experts' forward products
and dX) and ``_tgmm_ragged_kernel`` (dW), the least time the chip could
take for the call (the larger of its required FLOPs over the bf16 peak and
its required bytes over the HBM bandwidth), summed, over the events'
summed duration.  Nothing is returned when the traced steps ran no such
kernel.

A call's required work counts the routed rows the algorithm needs, not the
padded buffer it runs over: ``rows = T * top_k * held / router width`` a
layer, for T the tokens of one micro-step on a chip (the FLOP count's
convention, ``bench/families/deepseek_v3.py``).  FLOPs are ``2 * rows * K
* N``; bytes are the rows of the two row operands (x and out, or x and dY)
and the expert weights (or dW), each read or written once.
"""
import math

from bench import hlo

KERNELS = ("_gmm_ragged_kernel", "_tgmm_ragged_kernel")


def routed_rows(arch: dict, job: dict) -> float:
    """Rows a layer's expert products need in one micro-step on a chip."""
    tokens = job["micro_batch"] * job["seq"]
    return (tokens * arch["num_experts_per_tok"] * arch["n_routed_experts"]
            / arch["n_routed_experts_published"])


def gmm_work(ins, rows: float) -> tuple[float, float]:
    """(FLOPs, bytes) one call of either kernel requires, from its operand
    and result shapes in the compiled program: x (R, K) and w (G, ., .) ->
    out (R, N) for the forward kernel; x (R, K) and dY (R, N) -> dW (G, K,
    N) for the dW kernel.  The integer operands (the group metadata) are
    left out."""
    (xd, x), (yd, y) = [op for op in ins.operands if not op[0].startswith(("s", "u"))][:2]
    od, out = ins.shapes[0]
    nb = hlo.DTYPE_BYTES
    if ins.kernel == KERNELS[0]:
        k, n = x[1], out[1]
        nbytes = rows * k * nb[xd] + math.prod(y) * nb[yd] + rows * n * nb[od]
    else:
        k, n = x[1], y[1]
        nbytes = rows * k * nb[xd] + rows * n * nb[yd] + math.prod(out) * nb[od]
    return 2.0 * rows * k * n, float(nbytes)


def read(run):
    if run.reduction is None or not run.peak:
        return None
    rows = routed_rows(run.arch, run.job) if "n_routed_experts" in run.arch else 0.0
    need = took = 0.0
    for key, rec in run.reduction.ops.items():
        ins = run.instrs.get(key)
        if ins is None or ins.kernel not in KERNELS:
            continue
        flops, nbytes = gmm_work(ins, rows)
        per_call = max(flops / run.peak["bf16_flops"], nbytes / run.peak["hbm_bytes_per_s"])
        need += rec["count"] * per_call
        took += rec["ns"] * 1e-9
    return 100.0 * need / took if took else None
