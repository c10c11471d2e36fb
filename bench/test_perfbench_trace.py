"""The benchmark's yardstick on the CPU: interval arithmetic and the trace
reduction on a hand-built event list, HLO classification, work counts and
the peak table."""
import base64
import json
from pathlib import Path

import pytest

from bench import families, hlo, work, xplane
from bench.harness import Run, read_metric

CONFIGS = Path(__file__).with_name("configs")


def test_union_subtract_length():
    a = [(0, 10), (5, 15), (20, 30), (30, 31)]
    assert xplane.union(a) == [(0, 15), (20, 31)]
    assert xplane.length(a) == 26
    assert xplane.subtract(a, [(2, 4), (12, 22), (25, 40)]) == [(0, 2), (4, 12), (22, 25)]
    assert xplane.subtract([(0, 5)], []) == [(0, 5)]
    assert xplane.clip([(0, 10, "x"), (20, 30, "y")], 5, 25) == [(5, 10, "x"), (20, 25, "y")]


def test_leaves_drops_containers():
    evs = [(0, 100, "m", "while"), (10, 20, "m", "a"), (30, 40, "m", "b"), (100, 110, "m", "c")]
    assert [e[3] for e in xplane.leaves(evs)] == ["a", "b", "c"]


def _trace():
    # two devices, one 100 ns step; "ar" is a collective, the rest compute
    host = [(0, 100, "train_step"), (0, 5, "batch"), (5, 8, "dispatch"),
            (8, 100, "block")]
    dev0 = [(10, 40, "jit_step", "mm", "ops"), (30, 60, "jit_step", "ar", "ops"),
            (70, 80, "jit_step", "mm2", "ops"), (90, 95, "other", "copy", "ops"),
            (72, 99, "jit_step", "cp", "async")]
    dev1 = [(10, 50, "jit_step", "mm", "ops"), (45, 52, "jit_step", "ar-start", "async"),
            (52, 55, "jit_step", "ar", "ops")]
    return xplane.Trace(devices={0: dev0, 1: dev1}, host=host)


def _classify(module, op):
    kinds = {"mm": "other", "mm2": "other", "cp": "other", "ar": "collective",
             "ar-start": "collective"}
    if not module.startswith("jit_step"):
        return None
    return hlo.Instr(opcode=op, kind=kinds[op], kernel=None, shapes=(), operands=(), op_name="")


def test_reduce_busy_idle_and_exposed_collective():
    red = xplane.reduce(_trace(), _classify)
    assert red.window_ns == 100 and red.steps == 1
    assert red.busy_ns == [65.0, 43.0]           # dev0: 10-60, 70-80, 90-95; async not busy
    assert red.collective_ns == [30.0, 10.0]
    assert red.exposed_ns == [20.0, 5.0]         # dev0: 40-60; dev1: 50-55 (async 45-52 half hidden)
    assert red.ops[("jit_step", "mm")] == {"count": 2, "ns": 70.0}
    # idle on device 0: 0-10 (batch/dispatch/block), 60-70, 80-90, 95-100
    assert sorted(red.gaps) == sorted([(1e-8, "host: batch"), (1e-8, "host: block"),
                                       (1e-8, "host: block"), (5e-9, "host: block")])
    assert red.gaps[0][0] == 1e-8 and red.gaps[-1][0] == 5e-9
    run = Run(arch={}, job={}, chips=2, device_kind="x", peak={})
    run.reduction = red
    assert read_metric("device_idle_share", run) == pytest.approx(100 * (1 - 54 / 100))
    assert read_metric("collective_ms_per_step", run) == pytest.approx(20e-6)
    assert read_metric("collective_exposed_ms_per_step", run) == pytest.approx(12.5e-6)


def _body(*names):
    raw = b"MLIR\x00" + b"\x00".join(n.encode() for n in names)
    return base64.b64encode(raw).decode()


HLO_TEXT = f"""HloModule jit_step_body, is_scheduled=true

%fused_rs (p: f32[8]) -> f32[4] {{
  %p = f32[8]{{0}} parameter(0)
  ROOT %rs = f32[4]{{0}} reduce-scatter(%p), dimensions={{0}}
}}

ENTRY %main (a: bf16[4,9,2048,64], b: f32[8]) -> f32[4] {{
  %a = bf16[4,9,2048,64]{{3,2,1,0}} parameter(0)
  %b = f32[8]{{0}} parameter(1)
  %ars = f32[8]{{0}} all-reduce-start(%b), replica_groups={{}}
  %ard = f32[8]{{0}} all-reduce-done(%ars)
  %ag = f32[16]{{0}} all-gather(%ard), dimensions={{0}}
  %fus = f32[4]{{0}} fusion(%b), kind=kLoop, calls=%fused_rs
  %flash.3 = bf16[4,9,2048,64]{{3,2,1,0}} custom-call(%a, %a, %a), custom_call_target="tpu_custom_call", operand_layout_constraints={{bf16[4,9,2048,64]{{3,2,1,0}}, bf16[4,3,2048,64]{{3,2,1,0}}, bf16[4,3,2048,64]{{3,2,1,0}}}}, metadata={{op_name="jit(step)/pallas_call"}}, backend_config={{"custom_call_config":{{"body":"{_body("kernels", "_flash_kernel")}","needs_layout_passes":true}}}}
  %shard_map.7 = (f32[64,128]{{1,0}}, f32[1,64,128]{{2,1,0}}) custom-call(%b), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{
"mesh_axes":"[\\"pod\\"]"
}}}}, metadata={{op_name="jit(step)/shard_map/pallas_call"}}, backend_config={{"custom_call_config":{{"body":"{_body("_rs_dma_kernel")}","has_communication":true}}}}
  %dot.1 = f32[8]{{0}} multiply(%b, %b), metadata={{op_name="jit(step)/transpose(jvp(mul))/mul"}}
  ROOT %t = f32[4]{{0}} slice(%ag), slice={{[0:4]}}
}}
"""


def test_hlo_classifies_collectives_and_kernels():
    module, ins = hlo.parse(HLO_TEXT)
    assert module == "jit_step_body"
    kinds = {n: (i.kind, i.kernel) for n, i in ins.items() if i.kind != "other"}
    assert kinds == {
        "ars": ("collective", None), "ard": ("collective", None),
        "ag": ("collective", None), "rs": ("collective", None),
        "fus": ("collective", None),
        "flash.3": ("kernel", "_flash_kernel"),
        "shard_map.7": ("collective", "_rs_dma_kernel"),
    }
    flash = ins["flash.3"]
    assert flash.operands == (("bf16", (4, 9, 2048, 64)), ("bf16", (4, 3, 2048, 64)),
                              ("bf16", (4, 3, 2048, 64)))
    assert flash.shapes == (("bf16", (4, 9, 2048, 64)),)
    assert flash.label == "_flash_kernel"
    assert ins["dot.1"].label == "bwd:mul"
    assert ins["ag"].label == "all-gather"


def _arch(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_model_flops_per_token():
    """The count ``step_mfu`` reads, through each configuration's family."""
    a135, a360 = _arch("smollm-135m"), _arch("smollm-360m")
    assert families.of(a135).matmul_params(a135) == 134_479_872
    assert work.model_flops_per_token(a135, 2048) == pytest.approx(1.0192e9, rel=1e-4)
    assert families.of(a360).matmul_params(a360) == 361_758_720
    assert work.model_flops_per_token(a360, 2048) == pytest.approx(2.548e9, rel=1e-3)


def test_flash_forward_work():
    flops, nbytes = work.flash_fwd_work((4, 9, 2048, 64), (4, 3, 2048, 64), 2, 2, 2)
    assert flops == pytest.approx(1.93e10, rel=2e-3)
    assert nbytes == 2 * (2 * 4 * 9 * 2048 * 64 + 2 * 4 * 3 * 2048 * 64)


def test_flash_roofline_reader():
    ins = hlo.parse(HLO_TEXT)[1]["flash.3"]
    peak = work.peak("TPU v5 lite")
    red = xplane.Reduction(window_ns=1e9, steps=1, busy_ns=[1e9], collective_ns=[0.0],
                           exposed_ns=[0.0], ops={("jit_step_body", "flash.3"): {"count": 2, "ns": 2e8}},
                           gaps=[])
    run = Run(arch={}, job={}, chips=1, device_kind="TPU v5 lite", peak=peak)
    run.reduction, run.instrs = red, {("jit_step_body", "flash.3"): ins}
    need = 2 * 1.9327352832e10 / peak["bf16_flops"]
    assert read_metric("flash_fwd_roofline", run) == pytest.approx(100 * need / 0.2)


def test_peak_table():
    row = work.peak("TPU v5 lite")
    assert row == {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2**30}
    with pytest.raises(KeyError):
        work.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peak("_source")
