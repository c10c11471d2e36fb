"""Lightweight sharded checkpointing: atomic, resharding-capable, async.

Format: a directory per step —
    step_000123/
      manifest.json        {step, leaf paths, shapes, dtypes, checksum}
      arr_00000.npy ...    one file per pytree leaf (addressable data)

Properties needed for fleet-scale fault tolerance:
  * atomic publish: written to ``.tmp-…`` then renamed, so a crash mid-save
    never corrupts the latest checkpoint; stale ``*.tmp`` dirs left by a
    crash mid-save are swept on the next save;
  * verified restore: the per-leaf ``crc`` the manifest records is checked
    on load — a corrupt leaf raises :class:`CorruptCheckpointError`, and
    :func:`restore_latest` falls back to the previous retained step;
  * resharding restore: arrays are saved as full logical arrays and re-placed
    under the *target* sharding at load, so a job can restart on a different
    mesh (elastic scaling / pod loss).  The re-place step is
    :func:`place_tree`, shared with the checkpointless in-memory recovery
    path (``repro.elastic.recover``, DESIGN.md §13);
  * async: saves run on a background thread (training continues); a failed
    background save surfaces at the *next* save call, never silently;
  * retention: keep-last-k.
"""
from __future__ import annotations

import concurrent.futures as cf
import hashlib
import json
import os
import shutil
import time

import jax
import numpy as np


class CorruptCheckpointError(RuntimeError):
    """A checkpoint leaf failed its manifest checksum (or is unreadable)."""


def _leaf_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(kp), leaf) for kp, leaf in flat]


def _storable(arr: np.ndarray) -> np.ndarray:
    """``np.save`` writes ml_dtypes leaves (bfloat16, float8_*) as raw void
    that ``np.load`` cannot hand back; store their bytes as a same-width
    unsigned-integer view instead — the manifest's ``dtype`` views them
    back on load."""
    if arr.dtype.kind == "V":
        return arr.view(np.dtype(f"u{arr.dtype.itemsize}"))
    return arr


def _crc(arr: np.ndarray) -> str:
    """Leaf checksum: md5 over the first MiB (cheap, catches torn writes)."""
    return hashlib.md5(arr.tobytes()[:1 << 20]).hexdigest()


def sweep_stale(ckpt_dir: str) -> list[str]:
    """Remove ``step_*.tmp`` dirs left by a crash mid-save.

    Safe against the live async writer: the single-worker executor means at
    most one save is in flight, and :func:`save` sweeps only *before* it
    creates its own tmp dir.  Returns the removed paths (for logs/tests).
    """
    removed = []
    if not os.path.isdir(ckpt_dir):
        return removed
    for d in os.listdir(ckpt_dir):
        if d.startswith("step_") and d.endswith(".tmp"):
            path = os.path.join(ckpt_dir, d)
            shutil.rmtree(path, ignore_errors=True)
            removed.append(path)
    return removed


def save(ckpt_dir: str, step: int, state, *, keep: int = 3,
         blocking: bool = True):
    """Write one checkpoint.  ``blocking=False`` delegates to
    :func:`save_async` and returns its future; blocking saves return the
    published directory path."""
    if not blocking:
        return save_async(ckpt_dir, step, state, keep=keep)
    os.makedirs(ckpt_dir, exist_ok=True)
    sweep_stale(ckpt_dir)
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = final + ".tmp"
    os.makedirs(tmp)
    manifest = {"step": int(step), "leaves": [], "time": time.time()}
    for i, (path, leaf) in enumerate(_leaf_paths(state)):
        arr = np.asarray(jax.device_get(leaf))
        fname = f"arr_{i:05d}.npy"
        np.save(os.path.join(tmp, fname), _storable(arr))
        manifest["leaves"].append({
            "path": path, "file": fname, "shape": list(arr.shape),
            "dtype": str(arr.dtype),
            "crc": _crc(arr),
        })
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)                       # atomic publish
    _retain(ckpt_dir, keep)
    return final


_EXECUTOR = cf.ThreadPoolExecutor(max_workers=1)
_PENDING: list[cf.Future] = []


def _prune_pending():
    """Drop completed futures; re-raise the first background failure.

    Called from every :func:`save_async` so (a) ``_PENDING`` never grows
    past the in-flight set and (b) a failed background save surfaces at the
    next save instead of silently deferring to ``wait_pending``.
    """
    first_exc = None
    for f in [f for f in _PENDING if f.done()]:
        _PENDING.remove(f)
        exc = f.exception()
        if exc is not None and first_exc is None:
            first_exc = exc
    if first_exc is not None:
        raise first_exc


def save_async(ckpt_dir: str, step: int, state, *, keep: int = 3) -> cf.Future:
    """Snapshot to host memory synchronously, write to disk asynchronously."""
    _prune_pending()
    host_state = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)
    fut = _EXECUTOR.submit(save, ckpt_dir, step, host_state, keep=keep)
    _PENDING.append(fut)
    return fut


def wait_pending():
    pending, _PENDING[:] = _PENDING[:], []
    for f in pending:
        f.result()


def _retain(ckpt_dir: str, keep: int):
    steps = sorted(d for d in os.listdir(ckpt_dir)
                   if d.startswith("step_") and not d.endswith(".tmp"))
    for d in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, d), ignore_errors=True)


def retained_steps(ckpt_dir: str) -> list[int]:
    """Published steps with a parseable manifest, ascending.  Steps whose
    manifest is missing or unreadable are skipped (a torn publish never
    shadows the previous good step)."""
    steps = []
    if not os.path.isdir(ckpt_dir):
        return steps
    for d in os.listdir(ckpt_dir):
        if d.startswith("step_") and not d.endswith(".tmp"):
            try:
                with open(os.path.join(ckpt_dir, d, "manifest.json")) as f:
                    json.load(f)
                steps.append(int(d.split("_")[1]))
            except (OSError, ValueError):
                continue
    return sorted(steps)


def latest_step(ckpt_dir: str) -> int | None:
    steps = retained_steps(ckpt_dir)
    return steps[-1] if steps else None


def place_tree(host_flat: list, state_like, shardings=None):
    """Re-place full logical host arrays under the target shardings.

    The resharding half of :func:`restore`, shared with the checkpointless
    elastic recovery path (``repro.elastic.recover``, DESIGN.md §13), which
    assembles the same full logical arrays from surviving replicas instead
    of disk.

    Args:
        host_flat: full logical numpy arrays, in ``state_like``'s flat
            leaf order.
        state_like: a tree (arrays or ShapeDtypeStructs) giving structure
            and expected shapes.
        shardings: matching tree of (Named)Shardings, or None to place
            as replicated jnp arrays.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(state_like)
    shard_flat = (jax.tree.leaves(shardings) if shardings is not None
                  else [None] * len(flat))
    out = []
    for (kp, like), arr, sh in zip(flat, host_flat, shard_flat):
        expect = tuple(getattr(like, "shape", arr.shape))
        if tuple(arr.shape) != expect:
            raise ValueError(f"shape mismatch {kp}: {arr.shape} vs {expect}")
        out.append(jax.device_put(arr, sh) if sh is not None
                   else jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out)


def restore(ckpt_dir: str, step: int, state_like, shardings=None, *,
            verify: bool = True):
    """Load into the structure of ``state_like``; re-shard to ``shardings``
    (a matching tree of NamedShardings) if given — the elastic-restart path.

    ``verify=True`` (default) checks every leaf against the per-leaf ``crc``
    the manifest records; a mismatch raises :class:`CorruptCheckpointError`
    (use :func:`restore_latest` to fall back to an earlier retained step).
    """
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    try:
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise CorruptCheckpointError(f"unreadable manifest in {d}: {e}") from e
    by_path = {e["path"]: e for e in manifest["leaves"]}
    flat, _ = jax.tree_util.tree_flatten_with_path(state_like)
    host = []
    for kp, _like in flat:
        entry = by_path[jax.tree_util.keystr(kp)]
        try:
            arr = np.load(os.path.join(d, entry["file"]))
        except (OSError, ValueError) as e:
            raise CorruptCheckpointError(
                f"unreadable leaf {entry['file']} in {d}: {e}") from e
        if verify and entry.get("crc") and _crc(arr) != entry["crc"]:
            raise CorruptCheckpointError(
                f"checksum mismatch for {entry['path']} in {d}")
        if str(arr.dtype) != entry["dtype"]:
            arr = arr.view(jax.numpy.dtype(entry["dtype"]))
        host.append(arr)
    return place_tree(host, state_like, shardings)


def restore_latest(ckpt_dir: str, state_like, shardings=None, *,
                   verify: bool = True):
    """Restore the newest retained step, falling back to earlier steps when
    a checkpoint turns out corrupt (DESIGN.md §13 fallback chain).

    Returns ``(step, state)``; raises :class:`CorruptCheckpointError` when
    no retained step restores cleanly, ``FileNotFoundError`` when none
    exists at all.
    """
    steps = retained_steps(ckpt_dir)
    if not steps:
        raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    last_err: Exception | None = None
    for step in reversed(steps):
        try:
            return step, restore(ckpt_dir, step, state_like, shardings,
                                 verify=verify)
        except CorruptCheckpointError as e:
            last_err = e
            continue
    raise CorruptCheckpointError(
        f"every retained step in {ckpt_dir} is corrupt") from last_err
