"""A configuration brings its own block (``bench/families/``): the
weights it draws stay those of the layout the family module replaced,
and a configuration of a new family joins by new files alone."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import copy  # noqa: E402
import hashlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import textwrap  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

# The sizes and seeds the checksums below were recorded at, with the
# canonical layout that ``bench/weights.py`` held before the families.
SIZES = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
SEEDS = {"smollm-135m": 2**33 + 17, "smollm-360m": 3 * 2**31 + 5}
ONES = {"bfloat16": {"final_norm": "e72710531b01d91e", "layers/ln1": "1ede9ebfa1ad011b",
                     "layers/ln2": "1ede9ebfa1ad011b"},
        "float32": {"final_norm": "2f20cd03c9cd392a", "layers/ln1": "02722f124d0f1736",
                    "layers/ln2": "02722f124d0f1736"}}
RECORDED = {
    ("smollm-135m", "bfloat16"): {
        "embed": "9bd90baac54f68c4", "layers/w1": "d765297f68693537",
        "layers/w2": "9dcf1d06b364904e", "layers/w3": "949fd0ce0c15bb70",
        "layers/wk": "e4259c40fe5ea1b5", "layers/wo": "53bdc712b5ba3ee8",
        "layers/wq": "ed59f088160ef7f1", "layers/wv": "429316649f171a16",
        "lm_head": "a8231b44e6b45a34"},
    ("smollm-135m", "float32"): {
        "embed": "034ffa1bb706853e", "layers/w1": "49200e79e882fb73",
        "layers/w2": "30dabbc5afa936a1", "layers/w3": "0f5af4c49112a0f5",
        "layers/wk": "f9cc2e8ea0dfe7e4", "layers/wo": "b5f0c13531d88aaa",
        "layers/wq": "306d0b6c69ec62ba", "layers/wv": "1b3be73ff74efb62",
        "lm_head": "b361b7b8843b7312"},
    ("smollm-360m", "bfloat16"): {
        "embed": "ddc4a05c75cb9a37", "layers/w1": "fcf702923f159a9c",
        "layers/w2": "d0b0b254cc39d2f1", "layers/w3": "e2945d34dbf7ed6b",
        "layers/wk": "5be2a4c428e504e3", "layers/wo": "c84a8ec62eee7721",
        "layers/wq": "33e8ad01dfc1b9d2", "layers/wv": "df0ee2bf6d884571",
        "lm_head": "54eed330c8a34797"},
    ("smollm-360m", "float32"): {
        "embed": "d0240b86ba4a80a2", "layers/w1": "f87d729843040828",
        "layers/w2": "e14015f034949c43", "layers/w3": "0911494ca82f0f42",
        "layers/wk": "db2e1b89ce1335de", "layers/wo": "6d833677708bb00b",
        "layers/wq": "85e79e10ebe4cfca", "layers/wv": "32816c282b21d080",
        "lm_head": "84974e9bcbe9a0f3"},
}


@pytest.mark.parametrize("config,dtype", sorted(RECORDED))
def test_weights_are_bit_identical_to_the_recorded_layout(config, dtype):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import weights
    arch = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    arch.update(SIZES)
    w = jax.jit(lambda lo, hi: weights.make(arch, weights.seed_key(lo, hi), jnp.dtype(dtype)))(
        *weights.seed_words(SEEDS[config]))
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16] for k, v in w.items()}
    assert got == {**RECORDED[(config, dtype)], **ONES[dtype]}


# A Llama block under other canonical names, stacked under ``blk.`` and
# not ``layers/``, mapped onto the program's own params.
FAMILY = '''
"""A Llama block under renamed leaves (a test's family)."""
from bench.families import llama_dense

NAMES = {"embed": "tok_embeddings", "final_norm": "output_norm", "lm_head": "output",
         "layers/ln1": "blk.attn_norm", "layers/ln2": "blk.ffn_norm",
         "layers/wq": "blk.attn_q", "layers/wk": "blk.attn_k", "layers/wv": "blk.attn_v",
         "layers/wo": "blk.attn_output", "layers/w1": "blk.ffn_gate",
         "layers/w2": "blk.ffn_down", "layers/w3": "blk.ffn_up"}
LAYOUT = {NAMES[k]: v for k, v in llama_dense.LAYOUT.items()}
PATHS = {
    "tok_embeddings": ("embed",), "output_norm": ("final_norm",), "output": ("lm_head",),
    "blk.attn_norm": ("blocks", "ln1"), "blk.ffn_norm": ("blocks", "ln2"),
    "blk.attn_q": ("blocks", "attn", "wq"), "blk.attn_k": ("blocks", "attn", "wk"),
    "blk.attn_v": ("blocks", "attn", "wv"), "blk.attn_output": ("blocks", "attn", "wo"),
    "blk.ffn_gate": ("blocks", "mlp", "w1"), "blk.ffn_down": ("blocks", "mlp", "w2"),
    "blk.ffn_up": ("blocks", "mlp", "w3"),
}
TINY = llama_dense.TINY
model_config = llama_dense.model_config
model_flops_per_token = llama_dense.model_flops_per_token
'''

REFERENCE = '''
"""The Llama reference under the renamed leaves (a test's reference)."""
from bench.families.{family} import NAMES
from bench.references import llama_dense

BACK = {{v: k for k, v in NAMES.items()}}
MATMULS = llama_dense.MATMULS


def _theirs(w):
    return {{BACK[k]: v for k, v in w.items()}}


def loss_sum(w, tokens, labels, arch, mm):
    return llama_dense.loss_sum(_theirs(w), tokens, labels, arch, mm)


def train_readings(w0, batches, arch, opt, *, precision="f32", rows=1):
    out = llama_dense.train_readings(_theirs(w0), batches, arch, opt,
                                     precision=precision, rows=rows)
    for key in ("grad", "delta", "param"):
        out[key] = {{NAMES[k]: v for k, v in out[key].items()}}
    return out
'''


def test_a_new_family_joins_by_new_files(tmp_path, monkeypatch):
    """A configuration, its family and its reference, written to a
    directory of their own, run through the harness unchanged and read
    correct."""
    import time
    import jax
    from bench import families, harness, references
    name = "renamed_llama"
    for pkg, text in ((families, FAMILY), (references, REFERENCE.format(family=name))):
        where = tmp_path / pkg.__name__.rsplit(".", 1)[1]
        where.mkdir()
        (where / f"{name}.py").write_text(textwrap.dedent(text))
        monkeypatch.setattr(pkg, "__path__", [*pkg.__path__, str(where)])
    importlib.invalidate_caches()
    arch = json.loads((ROOT / "bench" / "configs" / "smollm-135m.json").read_text())
    arch.update(name="smollm-135m-renamed", family=name, reference=name)
    conf = tmp_path / "smollm-135m-renamed.json"
    conf.write_text(json.dumps(arch))
    spec = copy.deepcopy(SPEC)
    spec["configs"].append({"name": arch["name"], "source": arch["source"],
                            "file": str(conf), "reduced": arch["reduced"], "why": "a test"})
    spec["workloads"].append({"name": "smollm-135m-renamed.s2048.1chip", "config": arch["name"],
                              "traffic": "s2048.1chip", "chips": 1, "why": "a test"})

    cell = harness.load_cell("smollm-135m-renamed.s2048.1chip", spec)
    cell["limits"] = harness.load_cell("smollm-135m.s2048.1chip")["limits"]
    cell["arch"].update(families.of(cell["arch"]).TINY)
    cell["job"].update(seq=64)
    res = harness.execute(cell, 2**31 + 23, 0.05, False, jax.devices(),
                          t_start=time.perf_counter(), cache=False)
    assert res["correct"], res["check"]
    assert {w.split("[")[0] for w in res["notes"]["where"].values()} <= {
        *families.of(cell["arch"]).LAYOUT, "step 0", "step 1"}


def test_the_comparison_reads_every_layer_of_a_stacked_leaf():
    """A stacked leaf is compared layer by layer whatever its name: a gap
    in one layer of ``blk.w`` shows, and a whole leaf named ``layers/n``
    is one leaf."""
    from bench import check
    prog = {"blk.w": [2.0, 1.0], "layers/n": [1.0]}
    ref = {"blk.w": [1.0, 1.0], "layers/n": [1.0]}
    assert check.worst_leaf(prog, ref, {"blk.w"}) == (1.0, "blk.w[0]")
    assert check.worst_leaf(ref, prog, {"blk.w"}) == (0.5, "blk.w[0]")
