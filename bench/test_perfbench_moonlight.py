"""The DeepSeek-V3 block (Moonlight) against its plain reference
(``bench/references/deepseek_v3.py``), on the CPU at small sizes: the
program's train step at the family's ``TINY`` size in float32, and the
expert layer's chip share tied to the uncut layer."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import json  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CELL = "moonlight-16b-a3b.s8192.b2.1chip"


def test_program_trains_as_the_reference_at_tiny_size():
    """Loss, per-leaf gradients after one step and the master weights'
    change after two, the program's train step (``make_train_program``,
    through ``bench/program.py``) against the reference, both in float32
    from the same seeded weights on the same batches: apart by rounding
    alone (the cell's limits are for bf16 at full size)."""
    import jax
    import jax.numpy as jnp
    from bench import check, families, feed, harness, program, weights
    cell = harness.load_cell(CELL)
    arch, job = cell["arch"], cell["job"]
    arch.update(families.of(arch).TINY, torch_dtype="float32")
    job.update(seq=64)
    seed = 2**31 + 29
    w = weights.make(arch, weights.seed_key(*weights.seed_words(seed)), jnp.float32)
    batches = [feed.job_batch(seed, k, arch, job) for k in range(2)]
    p = program.build(arch, job, jax.devices())
    stacked = weights.stacked(arch)
    with jax.default_matmul_precision("highest"):
        state = p._state_from_weights(jax.tree.map(jnp.copy, w))   # donated
        ours = {"loss": []}
        for k, batch in enumerate(batches):
            state, metrics = p.step(state, p.put(batch))
            ours["loss"].append(float(metrics["loss"]))
            if k == 0:
                ours["grad"] = p.grad_norms(state)
        ours["delta"] = {
            name: np.asarray(program._row_norms(
                program._get(state["opt"]["master"], path)[:w[name].size]
                - w[name].reshape(-1), w[name].shape, name in stacked))
            for name, path in p.paths.items()}
        theirs = harness.reference_module(arch).train_readings(
            w, batches, arch, job["optimizer"], rows=job["reference_rows"])
    ours["param"] = theirs["param"]           # float32 parameters: the master
    gaps = {k: v[0] for k, v in check.gaps(ours, theirs, stacked).items()}
    # sound: 8.6e-8, 3.7e-7, 3.1e-7; the gates scaled 1 % high read a grad
    # gap of 1.2e-2, k_pe left unrotated 3.7e-3
    assert gaps["loss"] < 1e-6, gaps
    assert gaps["grad"] < 1e-5, gaps
    assert gaps["delta"] < 1e-5, gaps


@pytest.mark.parametrize("n_shares", [8, 2])
def test_chip_shares_sum_to_the_uncut_layer(n_shares):
    """Each of ``n_shares`` chips holds its contiguous range of the 16
    routed experts and routes over all of them; their routed parts summed,
    with the shared experts counted once, are the reference's layer
    computed whole, and each share reads the whole layer's balance loss."""
    import jax
    import jax.numpy as jnp
    from bench import program
    from bench.references import deepseek_v3 as ref
    program._import_path()
    from repro.models import moe

    E, k, D, F, B, S = 16, 3, 64, 32, 2, 32
    arch = {"n_routed_experts_published": E, "num_experts_per_tok": k,
            "expert_offset": 0, "n_routed_experts": E, "norm_topk_prob": True,
            "routed_scaling_factor": 2.446, "seq_aux": True, "seq_aux_alpha": 1e-4}
    rng = np.random.RandomState(4)

    def draw(*shape, scale=0.2):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)
    p = {"router": draw(D, E, scale=0.3), "w1": draw(E, D, F), "w3": draw(E, D, F),
         "w2": draw(E, F, D), "shared_w1": draw(D, 2 * F), "shared_w3": draw(D, 2 * F),
         "shared_w2": draw(2 * F, D)}
    h = draw(B, S, D, scale=1.0)
    with jax.default_matmul_precision("highest"):
        whole, balance = ref._experts(h, p, arch, ref.MATMULS["f32"])
        held = E // n_shares
        total = 0.0
        for i in range(n_shares):
            lo = i * held
            share = {"router": p["router"], **{w: p[w][lo:lo + held] for w in ("w1", "w3", "w2")}}
            if i == 0:          # what every chip computes alike, counted once
                share["shared"] = {w: p[f"shared_{w}"] for w in ("w1", "w3", "w2")}
            out, aux = moe.deepseek_moe(
                h.reshape(B * S, D), share, n_seqs=B, top_k=k,
                expert_offset=lo, n_held=held, routed_scaling=arch["routed_scaling_factor"],
                norm_topk=True, seq_aux_alpha=arch["seq_aux_alpha"])
            total = total + out
            # the balance loss: a mean over the sequences, the reference's sum
            np.testing.assert_allclose(float(aux["moe_balance"]) * B, float(balance), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(total).reshape(B, S, D), np.asarray(whole),
                               atol=1e-5 * float(jnp.max(jnp.abs(whole))))


def test_configuration_states_its_cut():
    """The configuration file keeps every published width, names each
    changed key in ``reduced`` with its published value, and states the
    deployment the cut stands for."""
    arch = json.loads((ROOT / "bench" / "configs" / "moonlight-16b-a3b.json").read_text())
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = {c["name"]: c for c in spec["configs"]}["moonlight-16b-a3b"]
    assert conf["reduced"] == arch["reduced"] == sorted(arch["published"], key=arch["reduced"].index)
    assert arch["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64,
                                 "vocab_size": 163840}
    assert arch["n_routed_experts_published"] == 64
    assert arch["n_routed_experts"] * 8 == 64 and arch["vocab_size"] * 8 == 163840
    widths = {"hidden_size": 2048, "num_attention_heads": 16, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
              "moe_intermediate_size": 1408, "intermediate_size": 11264,
              "num_experts_per_tok": 6, "n_shared_experts": 2}
    assert {key: arch[key] for key in widths} == widths
    assert "8 chips" in arch["deployment"] and arch["assumed"] and arch["departures"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_expert_gmm_roofline_reads_the_compiled_kernels(one_chip):
    """The reader finds the ragged kernels (forward, dX, dW) in the
    program compiled for a v5e, and charges each call the routed rows the
    algorithm needs with x, w and out moved once, whatever the buffer."""
    import jax
    import jax.numpy as jnp
    from bench import harness, hlo, program, work, xplane
    program._import_path()
    from repro.kernels import ops

    K, N, G, R = 256, 384, 2, 1024
    args = [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one_chip)
            for s, d in (((R, K), "bfloat16"), ((G, K, N), "bfloat16"), ((G,), "int32"))]

    def loss(x, w, sizes):
        return jnp.sum(ops.ragged_grouped_matmul(x, w, sizes).astype(jnp.float32))
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(*args).compile().as_text()
    module, instrs = hlo.parse(text)
    calls = {n: i for n, i in instrs.items() if i.kernel in ("_gmm_ragged_kernel",
                                                             "_tgmm_ragged_kernel")}
    assert sorted(i.kernel for i in calls.values()) == [
        "_gmm_ragged_kernel", "_gmm_ragged_kernel", "_tgmm_ragged_kernel"]
    arch = {"num_experts_per_tok": 6, "n_routed_experts": 8, "n_routed_experts_published": 64}
    job = {"micro_batch": 2, "seq": 8192}
    rows = 16384 * 6 * 8 / 64
    peak = work.peak("TPU v5 lite")
    need = 0.0
    for ins in calls.values():
        flops, nbytes = harness._module(
            harness.BENCH / "metrics" / "expert_gmm_roofline.py").gmm_work(ins, rows)
        assert flops == 2 * rows * K * N
        assert nbytes == 2 * (rows * K + rows * N + G * K * N)
        need += max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    red = xplane.Reduction(window_ns=1e9, steps=1, busy_ns=[1e9], collective_ns=[0.0],
                           exposed_ns=[0.0], gaps=[],
                           ops={(module, n): {"count": 1, "ns": 1e6} for n in calls})
    run = harness.Run(arch=arch, job=job, chips=1, device_kind="TPU v5 lite", peak=peak)
    run.reduction, run.instrs = red, {(module, n): i for n, i in calls.items()}
    assert harness.read_metric("expert_gmm_roofline", run) == pytest.approx(
        100 * need / 3e-3)
