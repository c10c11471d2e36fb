"""Device time by the program's scopes, on the CPU: ``bench/scopes.py``'s
attribution of a stack of names to one scope, its sums over a hand-built
reduction, the readers built on it, and the names it reads against
the constants the program emits them from."""
import json

import pytest

from bench import hlo, scopes, xplane
from bench.harness import Run, read_metric

JIT = "jit(step_body_installed)/shard_map"
RS = "hetccl.reduce_scatter.large.hier"
AG = "hetccl.all_gather.large.hier"


@pytest.mark.parametrize("op_name,scope", [
    (f"{JIT}/while/body/closed_call/jvp()/while/body/attention/bsd,dhk->bshk", "attention"),
    (f"{JIT}/while/body/transpose(jvp())/while/body/checkpoint/attention/attention_bwd/"
     "transpose(jvp())/while/body/checkpoint/rematted_computation/dot_general", "attention_bwd"),
    (f"{JIT}/while/body/transpose(jvp(mlp))/dot_general", "mlp"),
    (f"{JIT}/while/body/transpose(jvp(lm_head))/while/body/checkpoint/reduce_max", "lm_head"),
    (f"{JIT}/transpose(jvp(embed))/scatter-add", "embed"),
    (f"{JIT}/optimizer/sqrt", "optimizer"),
    (f"{JIT}/optimizer/{RS}/cross/_rs_dma_kernel/pallas_call", f"{RS}/cross"),
    (f"{JIT}/optimizer/{RS}/intra/reduce_scatter", f"{RS}/intra"),
    (f"{JIT}/optimizer/{AG}/convert_element_type", AG),
    (f"{JIT}/cross/ppermute", "cross"),
    (f"{JIT}/step_stats/psum", "step_stats"),
    (f"{JIT}/while/body/add", "unscoped"),
    ("", "unscoped"),
    ("state['params']['embed']", "unscoped"),
    (f"{JIT}/jit(flash_attention)/mlp_like/attentions/mul", "unscoped"),
])
def test_scope_of_innermost_wins_and_wrappers_stripped(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def _ins(op_name, kind="other", kernel=None):
    return hlo.Instr(opcode="fusion", kind=kind, kernel=kernel, shapes=(),
                     operands=(), op_name=op_name)


def _run(instrs, ops, devices=2, steps=2):
    run = Run(arch={}, job={}, chips=devices, device_kind="x", peak={})
    run.instrs = {("m", k): v for k, v in instrs.items()}
    run.reduction = xplane.Reduction(
        window_ns=1e9, steps=steps, busy_ns=[5e8] * devices,
        collective_ns=[0.0] * devices, exposed_ns=[0.0] * devices,
        ops={("m", k): {"count": 1, "ns": ns} for k, ns in ops.items()}, gaps=[])
    return run


INSTRS = {
    "bwd": _ins(f"{JIT}/transpose(jvp())/attention/attention_bwd/dot_general"),
    "fwd": _ins(f"{JIT}/attention/_flash_kernel/pallas_call", "kernel", "_flash_kernel"),
    "adam": _ins(f"{JIT}/optimizer/mul"),
    "rs": _ins(f"{JIT}/optimizer/{RS}/cross/_rs_dma_kernel", "collective", "_rs_dma_kernel"),
    "ag": _ins(f"{JIT}/optimizer/{AG}/cross/_ag_dma_kernel", "collective", "_ag_dma_kernel"),
    "ar": _ins(f"{JIT}/optimizer/{RS}/intra/all-reduce", "collective"),
    "add": _ins(f"{JIT}/add"),
}
OPS = {"bwd": 8e6, "fwd": 4e6, "adam": 2e6, "rs": 1.2e6, "ag": 0.8e6, "ar": 0.4e6,
       "add": 0.2e6, "stray": 0.4e6}       # "stray": an op of another program


def test_by_scope_per_device_per_step():
    got = scopes.by_scope(_run(INSTRS, OPS))
    assert got == pytest.approx({"attention_bwd": 2e6, "attention": 1e6, "optimizer": 0.5e6,
                                 f"{RS}/cross": 0.3e6, f"{AG}/cross": 0.2e6,
                                 f"{RS}/intra": 0.1e6, "unscoped": 0.15e6})
    assert sum(got.values()) == pytest.approx(sum(OPS.values()) / 4)


def test_readers(capsys):
    run = _run(INSTRS, OPS)
    assert read_metric("attention_bwd_ms_per_step", run) == pytest.approx(2.0)
    assert read_metric("optimizer_ms_per_step", run) == pytest.approx(0.5)
    assert read_metric("cross_pod_ms_per_step", run) == pytest.approx(0.5)
    assert read_metric("scoped_op_share", run) == pytest.approx(100 * (1 - 0.15 / 4.25))
    head, body = capsys.readouterr().err.strip().split(" ", 1)
    assert head == "scopes"
    assert json.loads(body)["scopes"]["attention_bwd"]["ms"] == pytest.approx(2.0)


def test_readers_find_nothing_in_an_unnamed_program():
    """A program that names no scope (the parent of the scopes) gives no
    reading, and raises nothing; nor does a run without a trace."""
    bare = {k: _ins(v.op_name.rsplit("/", 1)[-1], v.kind, v.kernel) for k, v in INSTRS.items()}
    for name in ("attention_bwd_ms_per_step", "optimizer_ms_per_step", "cross_pod_ms_per_step",
                 "scoped_op_share"):
        assert read_metric(name, _run(bare, OPS)) is None
        assert read_metric(name, Run(arch={}, job={}, chips=1, device_kind="x", peak={})) is None


def test_table_accounts_for_every_op():
    t = scopes.table(_run(INSTRS, OPS))
    assert t["op_ms_per_step"] == pytest.approx(4.25)
    assert sum(r["share"] for r in t["scopes"].values()) == pytest.approx(1.0)
    assert list(t["scopes"])[0] == "attention_bwd"
    assert t["scopes"][f"{RS}/cross"]["ops"] == [("_rs_dma_kernel", pytest.approx(0.3))]
    assert [u[1] for u in t["unscoped"]] == ["stray", "add"]
    assert scopes.table(Run(arch={}, job={}, chips=1, device_kind="x", peak={})) is None


def test_table_keeps_the_longest_ops_and_the_same_sums():
    """``top`` cuts each list to its longest entries and leaves every
    scope's time as ``by_scope`` sums it."""
    run = _run(INSTRS, OPS)
    t = scopes.table(run, top=1)
    assert [u[1] for u in t["unscoped"]] == ["stray"]
    assert all(len(r["ops"]) == 1 for r in t["scopes"].values())
    assert {k: r["ms"] * 1e6 for k, r in t["scopes"].items()} == pytest.approx(scopes.by_scope(run))


def test_names_are_the_programs():
    """The scopes read here are the constants the program emits them from."""
    from bench import program
    program._import_path()
    from repro.core import collectives, hetccl
    from repro.kernels import ops
    from repro.models import transformer
    from repro.train import optim, trainer
    assert scopes.LAYERS == (transformer.ATTENTION_SCOPE, ops.ATTENTION_BWD_SCOPE,
                             transformer.MLP_SCOPE, transformer.LM_HEAD_SCOPE,
                             transformer.EMBED_SCOPE, optim.SCOPE, trainer.STEP_STATS_SCOPE)
    assert scopes.STAGES == (collectives.INTRA_SCOPE, collectives.CROSS_SCOPE)
    name = hetccl.SCOPE.format(op="reduce_scatter", size_class="medium", variant="pipelined")
    assert scopes.HETCCL.fullmatch(name)
