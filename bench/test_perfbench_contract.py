"""The benchmark's contract, checked on the CPU: names and units, files
found by name, a run without a TPU refused, and the plain reference
agreeing with the program's model at a tiny size."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n\r]{1,200}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51 and isinstance(SPEC["run_seconds"], int)
    assert SPEC["command"][1] == "bench/run.py" and len(SPEC["command"]) <= 32
    assert SPEC["paths"] == ["bench"]
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_lines():
    entries = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + SPEC["per_layer"]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert LINE.match(e[key]), (e["name"], key)
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for c in SPEC["configs"]:
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


def test_every_cell_finds_its_files_by_name():
    from bench import harness
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["end_to_end"] and cell["per_layer"]
        assert cell["limits"] is not None, f"no limits for {w['name']}"
        assert (BENCH / "references" / f"{cell['arch']['reference']}.py").exists()
        for name in cell["end_to_end"] + cell["per_layer"]:
            assert (BENCH / "metrics" / f"{name}.py").exists(), name
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        arch = json.loads((ROOT / c["file"]).read_text())
        assert arch["name"] == c["name"] and arch["source"] == c["source"]
        assert arch["reduced"] == c["reduced"]


def test_workload_lists_name_existing_cells():
    cells = {w["name"] for w in SPEC["workloads"]}
    moves = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    for m in SPEC["per_layer"]:
        assert m["moves"] in moves


def test_run_without_a_tpu_exits_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = SPEC["workloads"][0]["name"]
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload", cell,
                        "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_every_configuration_names_its_family(conf):
    """Each configuration names a family module (``bench/families/``) that
    holds the whole block: layout, paths, model config, FLOP count and CPU
    size, one path for each canonical leaf."""
    from bench import families
    arch = json.loads((ROOT / conf["file"]).read_text())
    assert (BENCH / "families" / f"{arch['family']}.py").exists()
    fam = families.of(arch)
    for attr in ("LAYOUT", "PATHS", "model_config", "model_flops_per_token", "TINY"):
        assert hasattr(fam, attr), (arch["family"], attr)
    assert set(fam.LAYOUT) == set(fam.PATHS)
    for name, (shape, init, stacked) in fam.LAYOUT.items():
        assert init in ("normal", "ones") and isinstance(stacked, bool), name
        dims = shape({**arch, **fam.TINY})
        assert all(isinstance(d, int) and d > 0 for d in dims), name
        assert len(dims) >= 1 + stacked, name
    assert fam.model_flops_per_token(arch, 2048) > 0


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_reference_agrees_with_the_program_model(conf):
    """At the family's tiny size, in float32, the configuration's reference
    gives the loss and gradients of the program's model (``repro.models``)
    on the same weights."""
    import jax
    import jax.numpy as jnp
    from bench import families, feed, harness, program, weights
    program._import_path()
    from repro.core import compat
    from repro.models import build
    from repro.models.common import make_rules
    from repro.models.transformer import Ctx

    arch = json.loads((ROOT / conf["file"]).read_text())
    fam = families.of(arch)
    arch.update(fam.TINY, torch_dtype="float32")
    ref = harness.reference_module(arch)
    cfg = program.model_config(arch)
    model = build(cfg)
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    ctx = Ctx(rules=make_rules(cfg, mesh, 1), manual=False)
    w = weights.make(arch, weights.seed_key(*weights.seed_words(2**33 + 7)), jnp.float32)
    params = {}
    for name, path in fam.PATHS.items():
        program._set(params, path, w[name])
    batch = feed.synthetic_batch(5, 0, 1, 2, 32, arch["vocab_size"])
    tokens, labels = jnp.asarray(batch["tokens"][0]), jnp.asarray(batch["labels"][0])

    with jax.default_matmul_precision("highest"), compat.set_mesh(mesh):
        def theirs(p):
            loss_sum, _, _ = model.loss(p, {"tokens": tokens, "labels": labels}, ctx)
            return loss_sum
        l_p, g_p = jax.value_and_grad(theirs)(params)
        l_r, g_r = jax.value_and_grad(ref.loss_sum)(w, tokens, labels, arch,
                                                    ref.MATMULS["f32"])
    assert float(l_r) == pytest.approx(float(l_p), rel=1e-5)
    for name, path in fam.PATHS.items():
        a, b = np.asarray(program._get(g_p, path)), np.asarray(g_r[name])
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b), name
