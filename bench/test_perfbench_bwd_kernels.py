"""The attention backward's kernel share, on the CPU: read over a
hand-built reduction of a program whose backward is the Pallas kernels,
and nothing over one whose backward is XLA's operations (the parent of
the kernels) or over a run without a trace."""
import pytest

from bench import hlo, xplane
from bench.harness import Run, read_metric

BWD = "jit(step)/while/body/transpose(jvp(attention_bwd))"


def _ins(op_name, kernel=None):
    return hlo.Instr(opcode="custom-call" if kernel else "fusion",
                     kind="kernel" if kernel else "other", kernel=kernel,
                     shapes=(), operands=(), op_name=op_name)


def _run(instrs, ops, devices=1, steps=3):
    run = Run(arch={}, job={}, chips=devices, device_kind="x", peak={})
    run.instrs = {("m", k): v for k, v in instrs.items()}
    run.reduction = xplane.Reduction(
        window_ns=1e9, steps=steps, busy_ns=[5e8] * devices,
        collective_ns=[0.0] * devices, exposed_ns=[0.0] * devices,
        ops={("m", k): {"count": 1, "ns": ns} for k, ns in ops.items()}, gaps=[])
    return run


KERNELS = {
    "dkv": _ins(f"{BWD}/_flash_bwd_dkv_kernel/pallas_call", "_flash_bwd_dkv_kernel"),
    "dq": _ins(f"{BWD}/_flash_bwd_dq_kernel/pallas_call", "_flash_bwd_dq_kernel"),
    "di": _ins(f"{BWD}/reduce_sum"),
    "fwd": _ins("jit(step)/while/body/attention/_flash_kernel/pallas_call", "_flash_kernel"),
}


def test_share_of_the_backward_scope_in_its_kernels():
    run = _run(KERNELS, {"dkv": 6e6, "dq": 3e6, "di": 1e6, "fwd": 8e6})
    assert read_metric("attention_bwd_kernel_share", run) == pytest.approx(90.0)


@pytest.mark.parametrize("run", [
    _run({"bwd": _ins(f"{BWD}/dot_general"), "fwd": KERNELS["fwd"]},
         {"bwd": 9e6, "fwd": 8e6}),
    _run({}, {}),
    Run(arch={}, job={}, chips=1, device_kind="x", peak={}),
], ids=["jnp-backward", "empty-trace", "untraced"])
def test_nothing_without_the_kernels(run):
    assert read_metric("attention_bwd_kernel_share", run) is None


def test_kernel_names_are_the_programs():
    from bench import program
    program._import_path()
    from repro.kernels import flash_attention

    from bench.metrics.attention_bwd_kernel_share import KERNELS
    assert KERNELS == (flash_attention.BWD_DKV_KERNEL_NAME,
                           flash_attention.BWD_DQ_KERNEL_NAME)
