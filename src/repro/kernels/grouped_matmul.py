"""Grouped (per-expert) matmul kernels (Pallas TPU).

Batched GEMM over the MoE capacity buffer (``_gmm_kernel``): x (G, M, K) @
w (G, K, N) -> (G, M, N).  Blocked for the MXU:

  grid = (G, M/bm, N/bn, K/bk) — the K axis is innermost/sequential,
  accumulating into an f32 VMEM scratch tile; the output tile is written on
  the last K step.  bm/bn/bk default 128 (MXU-aligned).

Tokens dropped by the capacity dispatch are zero rows — they flow through
harmlessly, so no group-size masking is needed in-kernel (the dispatch layer
owns validity).

Ragged groups (the dropless expert layer): the rows of x (R, K) hold the
groups one after another from row 0, each starting on a multiple of
``ROW_TILE`` (the dispatch pads every group to it), so each row tile
belongs to one group; ``group_sizes`` (G,) are the padded sizes.  Two
kernels, each with its metadata in SMEM by scalar prefetch:

- ``_gmm_ragged_kernel``: out (R, N) = the rows of group g @ w[g], one row
  tile a grid step with w[g] whole in VMEM (it is fetched once per group,
  since consecutive tiles of a group map to the same block).  With
  ``transpose_rhs`` it takes w as (G, N, K): dX = dY w[g]^T.
- ``_tgmm_ragged_kernel``: dW[g] = x_g^T dY_g (G, K, N), the row tiles of a
  group summed in an f32 VMEM accumulator, one grid step per tile; a group
  with no rows gets one step that writes zeros.

Tiles past the last group's rows are neither fetched (their index maps
repeat the last live block, so no DMA is issued) nor computed; the forward
writes zeros there.  MXU operands stay in the inputs' dtype, with f32
accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import compat


def _gmm_kernel(x_ref, w_ref, o_ref, acc_scr):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def grouped_matmul(x, w, *, bm: int = 128, bn: int = 128, bk: int = 128,
                   interpret: bool = False):
    """x: (G, M, K), w: (G, K, N) -> (G, M, N).  Dims padded by ops.py."""
    G, M, K = x.shape
    _, _, N = w.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (x.shape, w.shape)
    grid = (G, M // bm, N // bn, K // bk)

    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        _gmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)


# ---------------------------------------------------------------------------
# ragged groups
# ---------------------------------------------------------------------------

# The kernels' names in the compiled program and the device trace, where the
# benchmark finds them (``bench/metrics/expert_gmm_roofline.py``); kept fixed.
GMM_KERNEL_NAME = "_gmm_ragged_kernel"
TGMM_KERNEL_NAME = "_tgmm_ragged_kernel"
# Rows of a tile, and so the alignment of every group's first row.
ROW_TILE = 128
# Scoped VMEM of the ragged kernels: an expert's whole (K, N) weight block,
# double-buffered, beside the row tiles and, for dW, its f32 accumulator.
VMEM_LIMIT = 64 * 1024 * 1024
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
# _tgmm_ragged_kernel's per-step flags
FIRST, LAST, LIVE = 1, 2, 4


def tile_groups(group_sizes, n_tiles: int, tm: int = ROW_TILE):
    """(group of each row tile (n_tiles,), live tiles (1,)), int32.  A tile
    past the live ones is given the last live tile's group."""
    ends = jnp.cumsum(group_sizes // tm)
    n_live = ends[-1]
    t = jnp.minimum(jnp.arange(n_tiles), jnp.maximum(n_live - 1, 0))
    group = jnp.minimum(jnp.searchsorted(ends, t, side="right"),
                        group_sizes.shape[0] - 1)
    return group.astype(jnp.int32), n_live.reshape(1).astype(jnp.int32)


def tile_items(group_sizes, n_tiles: int, tm: int = ROW_TILE):
    """The dW kernel's steps: every row tile of every group, in group order,
    and one step for each empty group; ``n_tiles + G`` steps, those past the
    last real one repeating it with no flag.  Returns (group, tile, flags),
    each (n_tiles + G,) int32."""
    tiles = group_sizes // tm
    items = jnp.maximum(tiles, 1)
    item_end = jnp.cumsum(items)
    n_real = item_end[-1]
    t = jnp.arange(n_tiles + group_sizes.shape[0])
    tc = jnp.minimum(t, n_real - 1)
    group = jnp.searchsorted(item_end, tc, side="right")
    within = tc - (item_end - items)[group]
    tile = jnp.minimum(jnp.cumsum(tiles)[group] - tiles[group] + within,
                       n_tiles - 1)
    flags = ((within == 0) * FIRST + (within == items[group] - 1) * LAST
             + (tiles[group] > 0) * LIVE) * (t < n_real)
    return group.astype(jnp.int32), tile.astype(jnp.int32), flags.astype(jnp.int32)


def _gmm_ragged_kernel(group_ref, live_ref, x_ref, w_ref, o_ref, *,
                       transpose_rhs: bool):
    live = pl.program_id(0) < live_ref[0]

    @pl.when(live)
    def _compute():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], _NT if transpose_rhs else _NN,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


def gmm_ragged(x, w, group_sizes, *, transpose_rhs: bool = False,
               interpret: bool = False):
    """x (R, K) @ w[g] over ragged, tile-aligned groups -> (R, N), for w
    (G, K, N), or (G, N, K) with ``transpose_rhs``.  R a multiple of
    ``ROW_TILE``; rows past the groups come out zero."""
    R, K = x.shape
    N = w.shape[1] if transpose_rhs else w.shape[2]
    tm = ROW_TILE
    assert R % tm == 0, (x.shape, tm)
    n_tiles = R // tm
    group, n_live = tile_groups(group_sizes, n_tiles, tm)

    def x_map(i, group, n_live):
        return jnp.minimum(i, jnp.maximum(n_live[0] - 1, 0)), 0

    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        functools.partial(_gmm_ragged_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((tm, K), x_map),
                      pl.BlockSpec((1,) + w.shape[1:],
                                   lambda i, group, n_live: (group[i], 0, 0))],
            out_specs=pl.BlockSpec((tm, N), lambda i, group, n_live: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((R, N), x.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=GMM_KERNEL_NAME,
    )(group, n_live, x, w)


def _tgmm_ragged_kernel(group_ref, tile_ref, flag_ref, x_ref, dy_ref, o_ref,
                        acc_scr):
    flags = flag_ref[pl.program_id(0)]

    @pl.when(flags & FIRST != 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(flags & LIVE != 0)
    def _accumulate():
        acc_scr[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], _TN, preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def _finish():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def tgmm_ragged(x, dy, group_sizes, *, interpret: bool = False):
    """dW (G, K, N) with dW[g] = x_g^T dy_g over ragged, tile-aligned groups,
    for x (R, K) and dy (R, N); zero for a group with no rows."""
    R, K = x.shape
    N = dy.shape[1]
    G = group_sizes.shape[0]
    tm = ROW_TILE
    assert R % tm == 0 and dy.shape[0] == R, (x.shape, dy.shape)
    n_tiles = R // tm
    group, tile, flags = tile_items(group_sizes, n_tiles, tm)

    def row_map(t, group, tile, flags):
        return tile[t], 0

    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        _tgmm_ragged_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles + G,),
            in_specs=[pl.BlockSpec((tm, K), row_map),
                      pl.BlockSpec((tm, N), row_map)],
            out_specs=pl.BlockSpec((1, K, N),
                                   lambda t, group, tile, flags: (group[t], 0, 0)),
            scratch_shapes=[pltpu.VMEM((K, N), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((G, K, N), x.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=TGMM_KERNEL_NAME,
    )(group, tile, flags, x, dy)
