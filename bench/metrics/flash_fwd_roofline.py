"""The Pallas flash-attention forward's share of its roofline, in %: for
each of the kernel's device events, the least time the chip could take for
the call (the larger of its required FLOPs over the bf16 peak and its
required bytes over the HBM bandwidth, ``bench/work.py``, from the call's
shapes in the compiled program), summed, over the events' summed duration.
Nothing is returned when the traced steps ran no such kernel."""
from bench import hlo, work

KERNEL = "_flash_kernel"


def read(run):
    if run.reduction is None or not run.peak:
        return None
    need = took = 0.0
    for key, rec in run.reduction.ops.items():
        ins = run.instrs.get(key)
        if ins is None or ins.kernel != KERNEL:
            continue
        (qd, q), (kd, k), _ = ins.operands
        od = ins.shapes[0][0]
        flops, nbytes = work.flash_fwd_work(q, k, hlo.DTYPE_BYTES[qd],
                                            hlo.DTYPE_BYTES[kd], hlo.DTYPE_BYTES[od])
        per_call = max(flops / run.peak["bf16_flops"], nbytes / run.peak["hbm_bytes_per_s"])
        need += rec["count"] * per_call
        took += rec["ns"] * 1e-9
    return 100.0 * need / took if took else None
