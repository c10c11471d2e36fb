"""RDMA-style ring collectives: async remote-copy rings with double-buffered
in-kernel reduction (the ``backend="pallas"`` collective backend, DESIGN.md
§10).

The paper's core mechanism is RDMA point-to-point transfers with reductions
performed entirely on the device (App. E.3).  The ``lax.ppermute`` rings in
``core.collectives`` reproduce the *algorithm* but not the *overlap*: XLA
schedules each ring step's wire transfer and its chunk accumulate serially,
so the per-step critical path is ``wire + reduce``.  Here the ring step is a
Pallas TPU kernel built from ``pltpu.make_async_remote_copy``: the payload is
split across ``NUM_BUFFERS`` streams and while stream k's incoming bytes are
being accumulated (f32 accumulator, optionally narrower wire dtype — the
``collective_reduce`` semantics), stream k+1's DMA is already in flight, so
the step costs ``max(wire, reduce)`` instead of their sum.

Two execution paths, resolved per TACC platform:

  * ``tpu``       -> the fused remote-DMA kernels (``_rs_dma_tpu`` /
    ``_ag_dma_tpu``): HBM-resident payloads streamed through fixed-size
    VMEM working slots, barrier-semaphore neighbour sync, per-(step,
    stream, stripe) buffers and DMA semaphores, neighbours named by mesh
    coordinate.  Any bucket size compiles; each call runs in a region
    where every mesh axis is manual (``compat.manual_region``).
  * anything else -> the *emulated schedule*: identical numerics and wave
    structure, with the wire hop carried by ``lax.ppermute`` and the
    accumulate dispatched through the TACC ``collective_reduce`` entry (the
    Pallas kernel body in interpret mode when pinned, the jnp oracle on raw
    CPU).  This is the interpret-mode contract the equivalence suite tests.

Orthogonal to both paths, ``n_stripes`` adds the transport layer's
multi-NIC stripe dimension (DESIGN.md §11): each wire hop is pad-and-sliced
across k per-link DMA streams — on TPU one ``make_async_remote_copy`` per
stripe with per-(step, stream, stripe) semaphores, in emulation one
ppermute per stripe — bit-equivalent to the unstriped ring by construction.

All functions must run inside a ``jax.shard_map`` whose manual axes include
``axis`` (same contract as ``core.collectives``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import compat, tacc
from repro.kernels import quant
from repro.transport.stripe import MAX_STRIPES

# Double-buffer depth: streams per ring step whose DMAs overlap the other
# stream's accumulate.  The simulator's overlap model (simulator.DMA_STREAMS)
# and the flow scheduler's lane layout (transport.flow.N_STREAMS) must
# agree — tested in tests/test_ring_dma.py and tests/test_transport.py.
NUM_BUFFERS = 2

_LANE = 128          # TPU lane width; payloads are reshaped to (rows, _LANE)


def _ring_perm(n: int, direction: int) -> list[tuple[int, int]]:
    return [(j, (j + direction) % n) for j in range(n)]


def _clamp_stripes(n_stripes: int, rows: int) -> int:
    """Static stripe count for a payload: the transport-layer cap, bounded by
    the payload's own granularity (a stripe must carry at least one row)."""
    return max(1, min(int(n_stripes), MAX_STRIPES, max(rows, 1)))


def _striped_hop(blk: jax.Array, axis: str, perm, n_stripes: int) -> jax.Array:
    """One wire hop as ``n_stripes`` concurrent per-link DMA streams.

    Emulation of the multi-NIC stripe schedule (DESIGN.md §11): the payload
    is pad-and-sliced into k contiguous stripes along dim 0, each carried by
    its own ppermute (its own link's DMA stream); the hops have no data
    dependence, so the scheduler sees them as concurrent — and the
    reassembled result is bit-identical to the single-stream hop.
    """
    k = _clamp_stripes(n_stripes, blk.shape[0])
    if k == 1:
        return lax.ppermute(blk, axis, perm)
    q, r = divmod(blk.shape[0], k)
    sizes = [q + 1] * r + [q] * (k - r)
    parts, lo = [], 0
    for sz in sizes:
        parts.append(lax.ppermute(blk[lo:lo + sz], axis, perm))
        lo += sz
    return jnp.concatenate(parts, axis=0)


def _reduce(acc, incoming):
    """One chunk accumulate: acc(f32) + incoming(wire dtype) -> f32.

    Platform-resolved via TACC: the Pallas ``collective_reduce`` kernel on
    TPU, its interpret-mode body when the default is pinned to "interpret"
    (the equivalence suite does), the jnp oracle otherwise.
    """
    return tacc.dispatch("collective_reduce", acc, incoming)


# ---------------------------------------------------------------------------
# Emulated schedule (CPU / interpret): ppermute wire + kernel reduce.
# ---------------------------------------------------------------------------

def _rs_emulated(chunks: jax.Array, axis: str, direction: int,
                 wire_dtype, n_stripes: int = 1) -> jax.Array:
    """chunks (n, c, ...) -> this rank's reduced chunk (c, ...), f32.

    Mirrors the TPU kernel's wave structure: each step's payload is split
    across NUM_BUFFERS streams; stream 1's wire hop is issued before stream
    0's accumulate and the pair is pinned into one wave with
    ``optimization_barrier``, so the scheduler may overlap them (the
    emulation of "DMA in flight during the reduce") but cannot re-serialize
    the wave.  Each stream's hop is further split into ``n_stripes``
    per-link ppermutes (:func:`_striped_hop`) — the multi-NIC stripe
    schedule of DESIGN.md §11, bit-equivalent to the unstriped hop.
    """
    n = chunks.shape[0]
    idx = lax.axis_index(axis)
    perm = _ring_perm(n, direction)
    acc = chunks.astype(jnp.float32)
    c = chunks.shape[1]
    h = c // NUM_BUFFERS if c >= NUM_BUFFERS else 0

    def body(s, acc):
        send_idx = (idx - direction * (s + 1)) % n
        recv_idx = (idx - direction * (s + 2)) % n
        blk = jnp.take(acc, send_idx, axis=0).astype(wire_dtype)
        cur = jnp.take(acc, recv_idx, axis=0)
        if h:
            r0 = _striped_hop(blk[:h], axis, perm, n_stripes)
            r1 = _striped_hop(blk[h:], axis, perm, n_stripes)   # in flight during r0's reduce
            new0 = _reduce(cur[:h], r0)
            new0, r1 = lax.optimization_barrier((new0, r1))
            new1 = _reduce(cur[h:], r1)
            new = jnp.concatenate([new0, new1], axis=0)
        else:
            new = _reduce(cur, _striped_hop(blk, axis, perm, n_stripes))
        return acc.at[recv_idx].set(new)

    acc = lax.fori_loop(0, n - 1, body, acc)
    return jnp.take(acc, idx, axis=0)


def _quant_hop(blk: jax.Array, axis: str, perm, n_stripes: int,
               codec: str):
    """One quantized wire hop: per-chunk absmax encode, the byte codes ride
    the striped per-link streams exactly like an uncompressed payload, the
    f32 scale sidecar rides one ppermute (DESIGN.md §17)."""
    codes, scales = quant.quantize(blk, codec=codec)
    r_codes = _striped_hop(codes, axis, perm, n_stripes)
    r_scales = lax.ppermute(scales, axis, perm)
    return r_codes, r_scales


def _quant_rs_emulated(chunks: jax.Array, axis: str, direction: int,
                       codec: str, n_stripes: int = 1) -> jax.Array:
    """Quantized ring reduce-scatter: :func:`_rs_emulated`'s wave structure
    with each hop's payload quantized (DESIGN.md §17).

    Every step re-quantizes the *running partial* it forwards — the scale
    sidecar travels alongside the codes — and the receiver dequantizes into
    the f32 accumulator via the ``wire_dequant_accum`` kernel; the
    accumulator itself never narrows.  The double-buffer split and
    ``optimization_barrier`` wave pinning are identical to the
    uncompressed schedule, so stream 1's (quantized) hop may overlap
    stream 0's dequantize-accumulate.
    """
    n = chunks.shape[0]
    idx = lax.axis_index(axis)
    perm = _ring_perm(n, direction)
    acc = chunks.astype(jnp.float32)
    c = chunks.shape[1]
    h = c // NUM_BUFFERS if c >= NUM_BUFFERS else 0

    def body(s, acc):
        send_idx = (idx - direction * (s + 1)) % n
        recv_idx = (idx - direction * (s + 2)) % n
        blk = jnp.take(acc, send_idx, axis=0)
        cur = jnp.take(acc, recv_idx, axis=0)
        if h:
            r0, rs0 = _quant_hop(blk[:h], axis, perm, n_stripes, codec)
            r1, rs1 = _quant_hop(blk[h:], axis, perm, n_stripes, codec)
            new0 = quant.dequantize_accumulate(cur[:h], r0, rs0, codec=codec)
            new0, r1, rs1 = lax.optimization_barrier((new0, r1, rs1))
            new1 = quant.dequantize_accumulate(cur[h:], r1, rs1, codec=codec)
            new = jnp.concatenate([new0, new1], axis=0)
        else:
            rc, rs = _quant_hop(blk, axis, perm, n_stripes, codec)
            new = quant.dequantize_accumulate(cur, rc, rs, codec=codec)
        return acc.at[recv_idx].set(new)

    acc = lax.fori_loop(0, n - 1, body, acc)
    return jnp.take(acc, idx, axis=0)


def _quant_ag_emulated(x: jax.Array, axis: str, direction: int,
                       codec: str, n_stripes: int = 1) -> jax.Array:
    """Quantized ring all-gather: the chunk is encoded **once** and the
    byte codes are forwarded verbatim around the ring (no re-quantization —
    unlike the reduce-scatter there is no growing partial), so every rank
    decodes the identical grid value for every chunk, including its own.
    Result is f32 on the codec grid."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    perm = _ring_perm(n, direction)
    codes, scales = quant.quantize(x, codec=codec)
    own = quant.dequantize(codes, scales, codec=codec)
    out = jnp.zeros((n,) + x.shape, jnp.float32).at[idx].set(own)

    def body(s, state):
        acc, cur_c, cur_s = state
        cur_c = _striped_hop(cur_c, axis, perm, n_stripes)
        cur_s = lax.ppermute(cur_s, axis, perm)
        val = quant.dequantize(cur_c, cur_s, codec=codec)
        acc = acc.at[(idx - direction * (s + 1)) % n].set(val)
        return acc, cur_c, cur_s

    out, _, _ = lax.fori_loop(0, n - 1, body, (out, codes, scales))
    return out


def _ag_emulated(x: jax.Array, axis: str, direction: int,
                 n_stripes: int = 1) -> jax.Array:
    """x (c, ...) per-rank chunk -> (n, c, ...) rank-stacked (no reduction:
    double buffering only pipelines the copy-out against the next hop;
    stripes split each hop over per-link streams, DESIGN.md §11)."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    perm = _ring_perm(n, direction)
    out = jnp.zeros((n,) + x.shape, x.dtype).at[idx].set(x)

    def body(s, state):
        acc, cur = state
        cur = _striped_hop(cur, axis, perm, n_stripes)
        acc = acc.at[(idx - direction * (s + 1)) % n].set(cur)
        return acc, cur

    out, _ = lax.fori_loop(0, n - 1, body, (out, x))
    return out


# ---------------------------------------------------------------------------
# TPU kernels: fused async-remote-copy rings (not reachable on CPU — the
# equivalence suite validates the schedule through the emulated path and the
# collective_reduce kernel body in interpret mode; the AOT compile suite,
# tests/test_chip_compile.py, compiles these for a v5e; DESIGN.md §10).
#
# Payloads stay in HBM (``memory_space=pl.ANY``): the wire hops are HBM ->
# remote HBM DMAs, and the accumulate streams each slice through fixed-size
# VMEM working slots, so VMEM use does not grow with the bucket.  Every ring
# step has its own send/recv buffers and DMA semaphores, so no slot is ever
# reused within a call and no backpressure protocol is needed.  Neighbours
# are named by their coordinate on ``axis`` with ``DeviceIdType.MESH`` (the
# other mesh coordinates are the sender's own), so on a (pod, data, model)
# mesh the hop reaches the next island, not a data neighbour.
# ---------------------------------------------------------------------------

_BLOCK_ROWS = 1024   # VMEM working-slot rows (x _LANE): 512 KiB at f32
_ROW_ALIGN = 32      # row granularity every wire dtype tiles at (8-bit: 32)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _slice_layout(L: int, n_slices: int) -> tuple[int, int]:
    """(rows per slice, block rows) for an L-element payload cut into
    ``n_slices`` equal row slices, each a whole number of aligned blocks of
    at most ``_BLOCK_ROWS`` rows (padding stays under one alignment unit per
    block)."""
    need = max(_cdiv(L, _LANE * n_slices), 1)
    n_blk = _cdiv(need, _BLOCK_ROWS)
    blk = _cdiv(_cdiv(need, n_blk), _ROW_ALIGN) * _ROW_ALIGN
    return n_blk * blk, blk


def _neighbour_barrier(my, n: int, axis: str):
    """Handshake with both ring neighbours: once it returns, both are inside
    this kernel, so their buffers are live for remote writes."""
    barrier = pltpu.get_barrier_semaphore()
    for nb in (lax.rem(my + 1, n), lax.rem(my - 1 + n, n)):
        pltpu.semaphore_signal(barrier, 1, device_id={axis: nb},
                               device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, 2)


def _rs_dma_kernel(my_ref, x_hbm, o_hbm, send_hbm, recv_hbm, a_v, b_v, w_v,
                   f_v, send_sem, recv_sem, *, n, direction, axis, rows_s,
                   blk, n_stripes, wire_dtype):
    """Ring reduce-scatter on one device.  x (n, rows, LANE) f32 -> o (rows,
    LANE) f32, the reduced chunk ``my``.

    Step s forwards this device's partial of chunk (my - d·(s+1)): its own
    x chunk plus the partial received at step s-1, cast to the wire dtype,
    then remote-copied into the downstream neighbour's step-s recv buffer.
    Each step's payload is split into NUM_BUFFERS streams and each stream
    into ``n_stripes`` per-link DMAs (DESIGN.md §11): stream 0's accumulate
    runs while stream 1's previous hop is still on the wire.  The final
    partial received is chunk ``my``: x[my] + it is the output.
    """
    my = my_ref[0]
    dst = lax.rem(my + direction + n, n)
    half = n_stripes * rows_s                       # rows per stream
    _neighbour_barrier(my, n, axis)
    copies = {}

    def stream(s, b, idx, out_ref, out_v):
        """out_ref[b-stream rows] = x[idx] (+ recv[s-1]) through VMEM."""
        def body(i, carry):
            lo = pl.multiple_of(b * half + i * blk, _ROW_ALIGN)
            pltpu.sync_copy(x_hbm.at[idx, pl.ds(lo, blk)], a_v)
            val = a_v[...]
            if s > 0:
                pltpu.sync_copy(recv_hbm.at[s - 1, pl.ds(lo, blk)], b_v)
                val = val + b_v[...].astype(jnp.float32)
            out_v[...] = val.astype(out_v.dtype)
            pltpu.sync_copy(out_v, out_ref.at[pl.ds(lo, blk)])
            return carry
        if s > 0:
            for j in range(n_stripes):
                copies[s - 1, b, j].wait_recv()
        lax.fori_loop(0, half // blk, body, 0)

    for s in range(n - 1):
        send_idx = lax.rem(my - direction * (s + 1) + n * (s + 2), n)
        for b in range(NUM_BUFFERS):
            stream(s, b, send_idx, send_hbm.at[s], w_v)
            for j in range(n_stripes):
                rows = pl.ds(b * half + j * rows_s, rows_s)
                c = pltpu.make_async_remote_copy(
                    src_ref=send_hbm.at[s, rows], dst_ref=recv_hbm.at[s, rows],
                    send_sem=send_sem.at[s, b, j],
                    recv_sem=recv_sem.at[s, b, j],
                    device_id={axis: dst},
                    device_id_type=pltpu.DeviceIdType.MESH)
                c.start()
                copies[s, b, j] = c
    for b in range(NUM_BUFFERS):
        stream(n - 1, b, my, o_hbm, f_v)
    for c in copies.values():
        c.wait_send()


@compat.manual_region
def _rs_dma_tpu(chunks: jax.Array, my: jax.Array, *, axis: str,
                direction: int, wire_dtype, n_stripes: int = 1) -> jax.Array:
    """chunks (n, c, ...) -> (c, ...) reduced, f32.  TPU-only fast path.
    ``my``: this device's (1,) int32 index on ``axis``, taken outside the
    manual region (an outer axis's index does not lower inside it)."""
    n = chunks.shape[0]
    rest = chunks.shape[1:]
    L = int(np.prod(rest)) if rest else 1
    S = _clamp_stripes(n_stripes,
                       _cdiv(L, NUM_BUFFERS * _ROW_ALIGN * _LANE))
    rows_s, blk = _slice_layout(L, NUM_BUFFERS * S)
    rows = NUM_BUFFERS * S * rows_s
    flat = chunks.reshape(n, L).astype(jnp.float32)
    pad = rows * _LANE - L
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    x = flat.reshape(n, rows, _LANE)
    wire = jnp.dtype(wire_dtype)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out, _, _ = pl.pallas_call(
        functools.partial(_rs_dma_kernel, n=n, direction=direction,
                          axis=axis, rows_s=rows_s, blk=blk, n_stripes=S,
                          wire_dtype=wire),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[hbm],
            out_specs=[hbm, hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((blk, _LANE), jnp.float32),   # x block
                pltpu.VMEM((blk, _LANE), wire),          # received block
                pltpu.VMEM((blk, _LANE), wire),          # outgoing block
                pltpu.VMEM((blk, _LANE), jnp.float32),   # final block
                pltpu.SemaphoreType.DMA((n - 1, NUM_BUFFERS, S)),
                pltpu.SemaphoreType.DMA((n - 1, NUM_BUFFERS, S)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((n - 1, rows, _LANE), wire),  # send
                   jax.ShapeDtypeStruct((n - 1, rows, _LANE), wire)],  # recv
        compiler_params=compat.tpu_compiler_params(collective_id=1),
    )(my, x)
    out = out.reshape(-1)
    if pad:
        out = out[:L]
    return out.reshape(rest) if rest else out.reshape(())


def _ag_dma_kernel(my_ref, x_hbm, o_hbm, send_sem, recv_sem, *, n, direction,
                   axis, rows_s, n_stripes):
    """Ring all-gather: HBM -> remote HBM, no staging.  Step s forwards
    chunk (my - d·s) — its own at s=0, else the one that arrived at step
    s-1 — into the same row of the downstream neighbour's output, as
    ``n_stripes`` per-link DMAs (DESIGN.md §11).  Every output row is
    written once per device, so nothing is reused and nothing races."""
    my = my_ref[0]
    dst = lax.rem(my + direction + n, n)
    _neighbour_barrier(my, n, axis)
    pltpu.sync_copy(x_hbm, o_hbm.at[my])
    copies = {}
    for s in range(n - 1):
        k = lax.rem(my - direction * s + n * (s + 1), n)
        if s > 0:
            for j in range(n_stripes):
                copies[s - 1, j].wait_recv()
        for j in range(n_stripes):
            rows = pl.ds(j * rows_s, rows_s)
            c = pltpu.make_async_remote_copy(
                src_ref=o_hbm.at[k, rows], dst_ref=o_hbm.at[k, rows],
                send_sem=send_sem.at[s, j], recv_sem=recv_sem.at[s, j],
                device_id={axis: dst},
                device_id_type=pltpu.DeviceIdType.MESH)
            c.start()
            copies[s, j] = c
    for j in range(n_stripes):
        copies[n - 2, j].wait_recv()
    for c in copies.values():
        c.wait_send()


@compat.manual_region
def _ag_dma_tpu(x: jax.Array, my: jax.Array, *, axis: str, direction: int,
                n_stripes: int = 1) -> jax.Array:
    """x (c, ...) -> (n, c, ...) rank-stacked.  TPU-only fast path (``my``
    as in :func:`_rs_dma_tpu`)."""
    n = lax.axis_size(axis)
    shape = x.shape
    L = int(np.prod(shape))
    S = _clamp_stripes(n_stripes, _cdiv(L, _ROW_ALIGN * _LANE))
    rows_s = _cdiv(_cdiv(L, S * _LANE), _ROW_ALIGN) * _ROW_ALIGN
    rows = S * rows_s
    flat = x.reshape(L)
    pad = rows * _LANE - L
    if pad:
        flat = jnp.pad(flat, (0, pad))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_ag_dma_kernel, n=n, direction=direction,
                          axis=axis, rows_s=rows_s, n_stripes=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[hbm],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.SemaphoreType.DMA((n - 1, S)),
                pltpu.SemaphoreType.DMA((n - 1, S)),
            ]),
        out_shape=jax.ShapeDtypeStruct((n, rows, _LANE), x.dtype),
        compiler_params=compat.tpu_compiler_params(collective_id=2),
    )(my, flat.reshape(rows, _LANE))
    out = out.reshape(n, -1)
    if pad:
        out = out[:, :L]
    return out.reshape((n,) + shape)


def _on_tpu() -> bool:
    return tacc.get_platform() == "tpu"


def _axis_pos(axis: str) -> jax.Array:
    return lax.axis_index(axis).reshape(1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Public ring primitives (the backend="pallas" cross-island stage).
# Signatures match core.collectives' xla rings so the dispatch layer can swap
# them 1:1; extra keyword-only knobs (direction, wire_dtype, n_stripes)
# default to the xla rings' behaviour.
# ---------------------------------------------------------------------------

def ring_reduce_scatter(x: jax.Array, axis: str, *, direction: int = 1,
                        wire_dtype=None, n_stripes: int = 1,
                        wire_quant: str | None = None) -> jax.Array:
    """x (n*c, ...) tiled on dim 0 -> this rank's reduced chunk (c, ...).

    Same result as ``collectives.ring_reduce_scatter`` (within dtype
    tolerance: the accumulator here is f32 regardless of x.dtype, the
    collective_reduce contract).  ``wire_dtype`` narrows only the bytes on
    the wire — the fused decompression of the beyond-paper compression knob.
    ``n_stripes`` splits each wire hop over that many per-link DMA streams
    (the transport layer's stripe schedule, DESIGN.md §11) — bit-equivalent
    to the unstriped ring, clamped to the payload's granularity.

    ``wire_quant`` (``"int8"`` | ``"fp8"``) replaces the dtype cast with
    the per-chunk absmax codec of DESIGN.md §17: each hop quantizes the
    running partial it forwards (scale sidecar alongside the byte codes)
    and dequantize-accumulates into the f32 accumulator.  It takes
    precedence over ``wire_dtype`` and runs the same schedule on every
    platform — the quantize / dequantize-accumulate compute resolves to
    the Pallas kernels per TACC platform, so the tier-1 CPU suite
    exercises the real numerics bit-equivalently.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    assert x.shape[0] % n == 0, (x.shape, n)
    chunks = x.reshape((n, x.shape[0] // n) + x.shape[1:])
    if wire_quant is not None:
        out = _quant_rs_emulated(chunks, axis, direction, wire_quant,
                                 n_stripes)
        return out.astype(x.dtype)
    wire = jnp.dtype(wire_dtype) if wire_dtype is not None else x.dtype
    if _on_tpu():
        out = _rs_dma_tpu(chunks, _axis_pos(axis), axis=axis,
                          direction=direction, wire_dtype=wire,
                          n_stripes=n_stripes)
    else:
        out = _rs_emulated(chunks, axis, direction, wire, n_stripes)
    return out.astype(x.dtype)


def ring_reduce_scatter_bidir(x: jax.Array, axis: str, *,
                              wire_dtype=None, n_stripes: int = 1,
                              wire_quant: str | None = None) -> jax.Array:
    """Bidirectional DMA ring reduce-scatter: the payload's halves travel in
    opposite directions concurrently (independent kernels per direction —
    each link's two lanes carry half the bytes, as in the xla bidir ring)."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    assert x.shape[0] % n == 0, (x.shape, n)
    c = x.shape[0] // n
    if c < 2:
        return ring_reduce_scatter(x, axis, wire_dtype=wire_dtype,
                                   n_stripes=n_stripes,
                                   wire_quant=wire_quant)
    h = c // 2
    chunks = x.reshape((n, c) + x.shape[1:])
    fwd = chunks[:, :h].reshape((n * h,) + x.shape[1:])
    bwd = chunks[:, h:].reshape((n * (c - h),) + x.shape[1:])
    return jnp.concatenate([
        ring_reduce_scatter(fwd, axis, direction=1, wire_dtype=wire_dtype,
                            n_stripes=n_stripes, wire_quant=wire_quant),
        ring_reduce_scatter(bwd, axis, direction=-1, wire_dtype=wire_dtype,
                            n_stripes=n_stripes, wire_quant=wire_quant),
    ], axis=0)


def ring_all_gather(x: jax.Array, axis: str, *, direction: int = 1,
                    n_stripes: int = 1,
                    wire_quant: str | None = None) -> jax.Array:
    """x (c, ...) per-rank chunk -> (n*c, ...) rank-major; matches
    ``collectives.ring_all_gather`` exactly (no reduction, no dtype drift;
    stripes only split the wire hops, DESIGN.md §11).  With ``wire_quant``
    each chunk is encoded once and its byte codes forwarded verbatim, so
    every rank decodes the identical on-grid value (DESIGN.md §17)."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    if wire_quant is not None:
        out = _quant_ag_emulated(x, axis, direction, wire_quant, n_stripes)
        out = out.astype(x.dtype)
    elif _on_tpu():
        out = _ag_dma_tpu(x, _axis_pos(axis), axis=axis, direction=direction,
                          n_stripes=n_stripes)
    else:
        out = _ag_emulated(x, axis, direction, n_stripes)
    return out.reshape((n * x.shape[0],) + x.shape[1:])


def ring_all_gather_bidir(x: jax.Array, axis: str, *,
                          n_stripes: int = 1,
                          wire_quant: str | None = None) -> jax.Array:
    """Bidirectional DMA ring all-gather (halves per-link byte-hops)."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    c = x.shape[0]
    if c < 2:
        return ring_all_gather(x, axis, n_stripes=n_stripes,
                               wire_quant=wire_quant)
    h = c // 2

    def one(xs, direction):
        if wire_quant is not None:
            return _quant_ag_emulated(xs, axis, direction, wire_quant,
                                      n_stripes).astype(x.dtype)
        if _on_tpu():
            return _ag_dma_tpu(xs, _axis_pos(axis), axis=axis,
                               direction=direction, n_stripes=n_stripes)
        return _ag_emulated(xs, axis, direction, n_stripes)

    out = jnp.concatenate([one(x[:h], 1), one(x[h:], -1)], axis=1)
    return out.reshape((n * c,) + x.shape[1:])


def ring_all_reduce(x: jax.Array, axis: str, *, wire_dtype=None,
                    n_stripes: int = 1,
                    wire_quant: str | None = None) -> jax.Array:
    """Bandwidth-optimal DMA ring all-reduce (reduce-scatter + all-gather),
    f32 accumulation, result cast back to x.dtype."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    red = ring_all_gather(
        ring_reduce_scatter(flat, axis, wire_dtype=wire_dtype,
                            n_stripes=n_stripes, wire_quant=wire_quant),
        axis, n_stripes=n_stripes, wire_quant=wire_quant)
    if pad:
        red = red[: flat.shape[0] - pad]
    return red.reshape(shape).astype(dtype)
