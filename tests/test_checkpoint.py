"""Checkpoint/restart + fault tolerance: atomic save, bit-exact resume,
failure injection mid-run, elastic resharding restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.core.balance import PodProfile, uniform_plan
from repro.data.pipeline import DataPipeline, synthetic_batch
from repro.models import build
from repro.train import checkpoint as ck
from repro.train import ft
from repro.train.trainer import make_train_program

CFG = get_config("smollm-135m").reduced()
MODEL = build(CFG)
SEQ = 64


def _prog(mesh3, zero=1):
    rc = RunConfig(zero_stage=zero, collective_mode="hier",
                   learning_rate=1e-3, param_dtype="float32")
    return make_train_program(MODEL, mesh3, rc, uniform_plan(2, 2, 1))


def test_save_restore_roundtrip(tmp_path, mesh3):
    prog = _prog(mesh3)
    state = prog.init_fn(jax.random.PRNGKey(0))
    ck.save(str(tmp_path), 7, state)
    assert ck.latest_step(str(tmp_path)) == 7
    like = jax.tree.map(lambda x: x, state)
    restored = ck.restore(str(tmp_path), 7, like, prog.state_shardings)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_retention_keeps_last_k(tmp_path, mesh3):
    prog = _prog(mesh3)
    state = prog.init_fn(jax.random.PRNGKey(0))
    for s in (1, 2, 3, 4, 5):
        ck.save(str(tmp_path), s, state, keep=2)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 2 and steps[-1].endswith("00000005")


def test_failure_recovery_bit_exact(tmp_path, mesh3):
    """Run 8 steps with a failure injected at step 5; the recovered run must
    produce the same loss trajectory as an uninterrupted run (deterministic
    data pipeline + checkpoint resume)."""
    prog = _prog(mesh3)
    pipe = DataPipeline(seed=0, plan=prog.plan, dp_world=prog.dp_world(),
                        seq_len=SEQ, vocab=CFG.vocab)

    def batches(step):
        return {k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}

    s0 = prog.init_fn(jax.random.PRNGKey(1))
    ck.save(str(tmp_path / "a"), 0, s0)
    _, hist_fail = ft.run_supervised(
        prog.step_fn, s0, batches, ckpt_dir=str(tmp_path / "a"),
        ckpt_every=3, n_steps=8, state_shardings=prog.state_shardings,
        fail_at=5)
    s1 = prog.init_fn(jax.random.PRNGKey(1))
    ck.save(str(tmp_path / "b"), 0, s1)
    _, hist_clean = ft.run_supervised(
        prog.step_fn, s1, batches, ckpt_dir=str(tmp_path / "b"),
        ckpt_every=3, n_steps=8, state_shardings=prog.state_shardings)
    by_step_fail = {h["step"]: h["loss"] for h in hist_fail}
    by_step_clean = {h["step"]: h["loss"] for h in hist_clean}
    for s in range(8):
        assert abs(by_step_fail[s] - by_step_clean[s]) < 1e-5, s


def test_bf16_leaf_resume_through_supervisor(tmp_path):
    """bf16 leaves (the full-size launcher's param dtype) survive save ->
    restore: stored as a same-width integer view, viewed back from the
    manifest dtype.  A failure injected mid-run resumes bit-exact."""
    w0 = jnp.asarray(np.random.RandomState(3).randn(8, 16), jnp.bfloat16)

    @jax.jit
    def step_fn(state, batch):
        w = (state["w"].astype(jnp.float32) * 0.9 + batch["x"]).astype(
            jnp.bfloat16)
        return ({"w": w, "m": state["m"] + 1.0},
                {"loss": jnp.mean(w.astype(jnp.float32))})

    def batches(step):
        return {"x": jnp.full((8, 16), 0.01 * (step + 1), jnp.float32)}

    def run(d, fail_at=None):
        s0 = {"w": w0, "m": jnp.zeros((3,), jnp.float32)}
        ck.save(str(d), 0, s0)
        return ft.run_supervised(step_fn, s0, batches, ckpt_dir=str(d),
                                 ckpt_every=2, n_steps=6, fail_at=fail_at)

    final, hist = run(tmp_path / "fail", fail_at=3)
    clean, hist_clean = run(tmp_path / "clean")
    assert final["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(final["w"]),
                                  np.asarray(clean["w"]))
    assert [h["loss"] for h in hist][-3:] == [h["loss"] for h in hist_clean][-3:]
    _, restored = ck.restore_latest(str(tmp_path / "fail"), final)
    assert restored["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(final["w"]))


def test_elastic_restore_to_different_mesh(tmp_path, mesh3, mesh2):
    """Checkpoint written on the 3-axis mesh restores onto the 2-axis mesh
    (pod loss -> survivors continue), matching values exactly."""
    prog_a = _prog(mesh3)
    state = prog_a.init_fn(jax.random.PRNGKey(2))
    ck.save(str(tmp_path), 3, state)
    rc = RunConfig(zero_stage=1, collective_mode="flat",
                   learning_rate=1e-3, param_dtype="float32")
    prog_b = make_train_program(MODEL, mesh2, rc, uniform_plan(1, 2, 1))
    state_b = prog_b.init_fn(jax.random.PRNGKey(99))
    restored = ck.restore(str(tmp_path), 3,
                          jax.tree.map(lambda x: x, state_b),
                          prog_b.state_shardings)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(state["params"])[0]),
        np.asarray(jax.tree.leaves(restored["params"])[0]))
    # and it can take a step
    b = synthetic_batch(0, 0, *prog_b.batch_shape(SEQ)[:2], SEQ, CFG.vocab)
    _, m = prog_b.step_fn(restored, {k: jnp.asarray(v) for k, v in b.items()})
    assert np.isfinite(float(m["loss"]))


def test_straggler_monitor_and_replan():
    mon = ft.StragglerMonitor(alpha=0.5, tolerance=0.2)
    assert not mon.observe(1.0)
    assert not mon.observe(1.05)
    assert mon.observe(2.0)            # 2x slower -> flagged
    plan = uniform_plan(2, 8, 2)
    new = ft.replan(plan, [PodProfile("a", 3.0), PodProfile("b", 1.0)])
    assert new.micro_per_pod == (6, 2)
    assert new.total_micro == plan.total_micro


def test_corrupt_leaf_detected_and_fallback(tmp_path, mesh3):
    """A leaf that rots on disk fails its manifest crc: restore raises the
    typed error, restore_latest falls back to the previous retained step."""
    prog = _prog(mesh3)
    state = prog.init_fn(jax.random.PRNGKey(3))
    ck.save(str(tmp_path), 2, state)
    ck.save(str(tmp_path), 4, state)
    victim = tmp_path / "step_00000004" / "arr_00000.npy"
    arr = np.load(victim)
    arr.flat[0] += 1.0                       # flip a value, keep shape/dtype
    np.save(victim, arr)
    like = jax.tree.map(lambda x: x, state)
    with pytest.raises(ck.CorruptCheckpointError):
        ck.restore(str(tmp_path), 4, like, prog.state_shardings)
    # unverified restore still reads it (the escape hatch)
    ck.restore(str(tmp_path), 4, like, prog.state_shardings, verify=False)
    step, restored = ck.restore_latest(str(tmp_path), like,
                                       prog.state_shardings)
    assert step == 2                         # fell back past the corruption
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_latest_all_corrupt_raises(tmp_path, mesh3):
    prog = _prog(mesh3)
    state = prog.init_fn(jax.random.PRNGKey(3))
    ck.save(str(tmp_path), 1, state)
    os.remove(tmp_path / "step_00000001" / "arr_00000.npy")
    with pytest.raises(ck.CorruptCheckpointError):
        ck.restore_latest(str(tmp_path), jax.tree.map(lambda x: x, state),
                          prog.state_shardings)
    with pytest.raises(FileNotFoundError):   # no checkpoints at all
        ck.restore_latest(str(tmp_path / "empty"), state)


def test_stale_tmp_swept_and_not_restorable(tmp_path, mesh3):
    """A crash mid-save leaves step_X.tmp: it is never listed as a retained
    step and the next save sweeps it."""
    prog = _prog(mesh3)
    state = prog.init_fn(jax.random.PRNGKey(3))
    stale = tmp_path / "step_00000009.tmp"
    stale.mkdir(parents=True)
    (stale / "garbage").write_text("partial write")
    assert ck.retained_steps(str(tmp_path)) == []
    assert ck.latest_step(str(tmp_path)) is None
    ck.save(str(tmp_path), 1, state)
    assert not stale.exists()                # swept before publishing
    assert ck.retained_steps(str(tmp_path)) == [1]


def test_save_nonblocking_kwarg(tmp_path, mesh3):
    """save(blocking=False) is honored: returns the async future instead of
    silently writing synchronously."""
    prog = _prog(mesh3)
    state = prog.init_fn(jax.random.PRNGKey(3))
    fut = ck.save(str(tmp_path), 5, state, blocking=False)
    assert fut.result().endswith("step_00000005")
    ck.wait_pending()
    assert ck.latest_step(str(tmp_path)) == 5


def test_background_save_failure_surfaces_at_next_save(tmp_path):
    """A failed async save must raise at the next save call, not silently
    vanish into the executor."""
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("file where the ckpt dir should go")
    bad = ck.save_async(str(blocker), 1, {"w": np.ones(4, np.float32)})
    with pytest.raises(Exception):
        bad.result()                        # the failure itself
    with pytest.raises(Exception):
        # next save: _prune_pending re-raises the background failure
        ck.save_async(str(tmp_path / "ok"), 2,
                      {"w": np.ones(4, np.float32)})
    ck.wait_pending()                       # leave the module state clean
