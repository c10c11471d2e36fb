"""HetCCL collectives: vendor-local native stages + cross-island P2P rings.

The paper's mechanism (§4.1-§4.2): a collective over a heterogeneous group is
decomposed into

  1. a *vendor-local* stage executed by the vendor's optimized library
     (NCCL / RCCL), and
  2. a *cross-vendor* stage built from RDMA point-to-point transfers,

so near-native local performance is preserved and only the unavoidable
cross-island hop crosses the slow boundary.

TPU mapping (see DESIGN.md §2):

  * vendor-local stage  -> native XLA collectives over intra-pod mesh axes
    (``jax.lax.psum`` / ``all_gather`` / ``psum_scatter``), which XLA lowers to
    ICI-optimized collectives;
  * cross-vendor RDMA   -> explicit ``jax.lax.ppermute`` rings over the
    ``"pod"`` axis (the only pure point-to-point JAX collective).

Everything here must run inside a ``jax.shard_map`` whose manual axes include
the axes being reduced over, created with ``check_vma=False`` (ring ppermutes
produce values the VMA type system cannot prove invariant).

All ops are registered in the TACC function table under variants ``"flat"``
(single-stage native), ``"hier"`` (two-stage HetCCL), and — for the
bandwidth-dominant ops — ``"pipelined"`` (multi-channel two-stage with the
vendor-local stage overlapping the cross-island ring; DESIGN.md §2) so the
whole backend can be swapped at runtime (paper §4.4).

Orthogonally to the mode, the *ring implementation* is selectable via the
``backend`` keyword (``HetCCLConfig.backend``): ``"xla"`` is the ppermute
rings below, ``"pallas"`` swaps in the async remote-copy rings of
``repro.kernels.ring_dma`` (double-buffered in-kernel reduction; DESIGN.md
§10) for the cross-island stage — and for the whole ring in ``flat`` mode.
The vendor-local stage always stays native XLA (it *is* the vendor library).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import tacc

Axis = str | Sequence[str]


def _axes_tuple(axes: Axis) -> tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def axis_world(axes: Axis) -> int:
    n = 1
    for a in _axes_tuple(axes):
        n *= lax.axis_size(a)
    return n


def _native_reduce(reduce, x, *args, **kwargs):
    """``reduce(x, ...)``: a native XLA reduction (``lax.psum`` or
    ``lax.psum_scatter``).  XLA:CPU aborts on a sub-f32 float reduction
    inside a partially-manual shard_map (DESIGN.md §8), so on the ``cpu``
    platform those reduce in f32 and cast back — the promotion XLA:CPU would
    apply itself.  Every other platform reduces in ``x``'s own dtype."""
    if (tacc.get_platform() == "cpu" and x.dtype.itemsize < 4
            and jnp.issubdtype(x.dtype, jnp.floating)):
        return reduce(x.astype(jnp.float32), *args, **kwargs).astype(x.dtype)
    return reduce(x, *args, **kwargs)


RING_BACKENDS = ("xla", "pallas")


def resolve_ring_backend(backend: str, *, bidir: bool = False,
                         n_stripes: int = 1, wire_quant: str | None = None):
    """(reduce_scatter, all_gather) ring primitives for ``backend``.

    ``"xla"``: the ``lax.ppermute`` rings in this module.  ``"pallas"``: the
    DMA-style rings of :mod:`repro.kernels.ring_dma` — async remote copies
    with double-buffered in-kernel f32 reduction on TPU, the same schedule
    emulated with ppermute + the ``collective_reduce`` kernel elsewhere
    (DESIGN.md §10).  Imported lazily so the default path never touches
    Pallas.

    ``n_stripes`` > 1 binds the transport layer's multi-NIC stripe count
    into the pallas rings (one DMA stream per link, DESIGN.md §11); the xla
    rings are single-stream by construction (one ppermute is one logical
    transfer), so the knob is ignored there — mirroring
    ``HetCCLConfig.resolved_stripes``.

    ``wire_quant`` binds the wire-quantization codec (None | "int8" |
    "fp8", DESIGN.md §17) into the pallas rings: payloads cross each hop as
    per-chunk absmax codes with the f32 scale sidecar and accumulate in
    f32.  The xla ppermute rings carry no codec — the knob is ignored
    there, mirroring the communicator's creation-time collapse.
    """
    if backend == "pallas":
        from repro.kernels import ring_dma
        rs = (ring_dma.ring_reduce_scatter_bidir if bidir
              else ring_dma.ring_reduce_scatter)
        ag = (ring_dma.ring_all_gather_bidir if bidir
              else ring_dma.ring_all_gather)
        kw = {}
        if n_stripes and int(n_stripes) > 1:
            kw["n_stripes"] = int(n_stripes)
        if wire_quant is not None:
            kw["wire_quant"] = wire_quant
        if kw:
            rs = functools.partial(rs, **kw)
            ag = functools.partial(ag, **kw)
        return rs, ag
    if backend != "xla":
        raise ValueError(f"unknown collective backend {backend!r}; "
                         f"expected one of {RING_BACKENDS}")
    return ((ring_reduce_scatter_bidir if bidir else ring_reduce_scatter),
            (ring_all_gather_bidir if bidir else ring_all_gather))


# ---------------------------------------------------------------------------
# Ring primitives over a single axis (the "RDMA" stage).
# Wire traffic per rank: reduce_scatter / all_gather move (n-1)/n * bytes,
# all_reduce 2(n-1)/n * bytes — bandwidth-optimal, like NCCL's ring.
# Each takes a ``direction`` (+1 clockwise / -1 counterclockwise); the
# ``*_bidir`` variants run both directions concurrently on half payloads,
# halving the per-link byte-hops on full-duplex fabrics (H2 §4 / Holmes §5
# style multi-channel rings).
# ---------------------------------------------------------------------------

def _fwd_perm(n: int) -> list[tuple[int, int]]:
    return [(j, (j + 1) % n) for j in range(n)]


def _ring_perm(n: int, direction: int) -> list[tuple[int, int]]:
    return [(j, (j + direction) % n) for j in range(n)]


def _ring_rs_chunks(chunks: jax.Array, axis: str, direction: int = 1) -> jax.Array:
    """chunks: (n, c, ...) -> this rank's reduced chunk (c, ...)."""
    n = chunks.shape[0]
    idx = lax.axis_index(axis)
    perm = _ring_perm(n, direction)

    def body(s, acc):
        send_idx = (idx - direction * (s + 1)) % n
        blk = jnp.take(acc, send_idx, axis=0)
        rblk = lax.ppermute(blk, axis, perm)
        return acc.at[(idx - direction * (s + 2)) % n].add(rblk)

    acc = lax.fori_loop(0, n - 1, body, chunks)
    return jnp.take(acc, idx, axis=0)


def ring_reduce_scatter(x: jax.Array, axis: str) -> jax.Array:
    """x: (n*c, ...) tiled on dim 0 -> this rank's reduced chunk (c, ...).

    Matches ``lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)``.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    assert x.shape[0] % n == 0, (x.shape, n)
    chunks = x.reshape((n, x.shape[0] // n) + x.shape[1:])
    return _ring_rs_chunks(chunks, axis, 1)


def ring_reduce_scatter_bidir(x: jax.Array, axis: str) -> jax.Array:
    """Bidirectional ring reduce-scatter: the payload's two halves travel
    clockwise and counterclockwise simultaneously.

    Same result as :func:`ring_reduce_scatter`; each direction's ring carries
    half the bytes over its own full-duplex lane, so per-link wire time is
    halved (step/latency count unchanged).  Both directions' ppermutes sit in
    one loop body with no data dependence — the roofline analyzer and the
    device scheduler both see the opposite-direction transfers as concurrent.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    assert x.shape[0] % n == 0, (x.shape, n)
    chunks = x.reshape((n, x.shape[0] // n) + x.shape[1:])
    c = chunks.shape[1]
    if c < 2:
        return _ring_rs_chunks(chunks, axis, 1)
    h = c // 2
    idx = lax.axis_index(axis)
    perm_f, perm_b = _ring_perm(n, 1), _ring_perm(n, -1)

    def body(s, carry):
        af, ab = carry
        rf = lax.ppermute(jnp.take(af, (idx - s - 1) % n, axis=0), axis, perm_f)
        rb = lax.ppermute(jnp.take(ab, (idx + s + 1) % n, axis=0), axis, perm_b)
        return (af.at[(idx - s - 2) % n].add(rf),
                ab.at[(idx + s + 2) % n].add(rb))

    fwd, bwd = lax.fori_loop(0, n - 1, body, (chunks[:, :h], chunks[:, h:]))
    return jnp.concatenate([jnp.take(fwd, idx, axis=0),
                            jnp.take(bwd, idx, axis=0)], axis=0)


def ring_reduce_scatter_mixed(x: jax.Array, axis: str,
                              wire_dtype=None) -> jax.Array:
    """Ring reduce-scatter with narrow wire + f32 accumulation.

    Payloads cross the wire in ``wire_dtype`` (default: x.dtype) while the
    local accumulator stays f32 — the semantics of the paper's GPU-side
    collective reduction (App. E.3) and of the `collective_reduce` Pallas
    kernel.  Halves ZeRO-3 gradient wire bytes vs an f32 reduce-scatter.
    Returns the f32-reduced chunk owned by this rank (tiled on dim 0).
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x.astype(jnp.float32)
    wire_dtype = wire_dtype or x.dtype
    assert x.shape[0] % n == 0, (x.shape, n)
    chunks = x.reshape((n, x.shape[0] // n) + x.shape[1:]).astype(jnp.float32)
    idx = lax.axis_index(axis)
    perm = _fwd_perm(n)

    def body(s, acc):
        send_idx = (idx - s - 1) % n
        blk = jnp.take(acc, send_idx, axis=0).astype(wire_dtype)
        rblk = lax.ppermute(blk, axis, perm)
        return acc.at[(idx - s - 2) % n].add(rblk.astype(jnp.float32))

    acc = lax.fori_loop(0, n - 1, body, chunks)
    return jnp.take(acc, idx, axis=0)


def _ring_ag_stack(x: jax.Array, axis: str, direction: int = 1) -> jax.Array:
    """x: (c, ...) per-rank chunk -> (n, c, ...) rank-stacked."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    perm = _ring_perm(n, direction)
    out = jnp.zeros((n,) + x.shape, x.dtype).at[idx].set(x)

    def body(s, state):
        acc, cur = state
        cur = lax.ppermute(cur, axis, perm)   # chunk of rank (idx - d*(s+1))
        acc = acc.at[(idx - direction * (s + 1)) % n].set(cur)
        return acc, cur

    out, _ = lax.fori_loop(0, n - 1, body, (out, x))
    return out


def ring_all_gather(x: jax.Array, axis: str) -> jax.Array:
    """x: (c, ...) per-rank chunk -> (n*c, ...) rank-major, all ranks equal.

    Matches ``lax.all_gather(x, axis, axis=0, tiled=True)``.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    out = _ring_ag_stack(x, axis, 1)
    return out.reshape((n * x.shape[0],) + x.shape[1:])


def ring_all_gather_bidir(x: jax.Array, axis: str) -> jax.Array:
    """Bidirectional ring all-gather (halves per-link byte-hops).

    Same result as :func:`ring_all_gather`: each half of every rank's chunk
    circulates in its own direction, so a link carries (n-1)/n of *half* the
    buffer per direction, concurrently (one fused loop body, like
    :func:`ring_reduce_scatter_bidir`).
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    c = x.shape[0]
    if c < 2:
        return ring_all_gather(x, axis)
    h = c // 2
    idx = lax.axis_index(axis)
    perm_f, perm_b = _ring_perm(n, 1), _ring_perm(n, -1)
    xf, xb = x[:h], x[h:]
    accf = jnp.zeros((n,) + xf.shape, x.dtype).at[idx].set(xf)
    accb = jnp.zeros((n,) + xb.shape, x.dtype).at[idx].set(xb)

    def body(s, carry):
        accf, curf, accb, curb = carry
        curf = lax.ppermute(curf, axis, perm_f)   # chunk of rank (idx - s - 1)
        curb = lax.ppermute(curb, axis, perm_b)   # chunk of rank (idx + s + 1)
        accf = accf.at[(idx - s - 1) % n].set(curf)
        accb = accb.at[(idx + s + 1) % n].set(curb)
        return accf, curf, accb, curb

    accf, _, accb, _ = lax.fori_loop(0, n - 1, body, (accf, xf, accb, xb))
    out = jnp.concatenate([accf, accb], axis=1)       # (n, c, ...)
    return out.reshape((n * c,) + x.shape[1:])


def ring_all_reduce(x: jax.Array, axis: str) -> jax.Array:
    """Bandwidth-optimal ring all-reduce (reduce-scatter + all-gather)."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    red = ring_all_gather(ring_reduce_scatter(flat, axis), axis)
    if pad:
        red = red[: flat.shape[0] - pad]
    return red.reshape(shape).astype(dtype)


def ring_all_to_all(x: jax.Array, axis: str) -> jax.Array:
    """x: (n, ...) block i destined for rank i -> (n, ...) block j from rank j.

    Matches ``lax.all_to_all(x, axis, split_axis=0, concat_axis=0)`` for a
    leading block dim of size n.  Uses n-1 ppermutes of stride s.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    idx = lax.axis_index(axis)
    out = jnp.zeros_like(x)
    out = out.at[idx].set(jnp.take(x, idx, axis=0))
    for s in range(1, n):  # static unroll: perms differ per step
        perm = [(j, (j + s) % n) for j in range(n)]
        blk = jnp.take(x, (idx + s) % n, axis=0)     # my block destined (idx+s)
        rblk = lax.ppermute(blk, axis, perm)          # from rank (idx - s)
        out = out.at[(idx - s) % n].set(rblk)
    return out


def ring_broadcast(x: jax.Array, axis: str, root: int = 0) -> jax.Array:
    """Chain-forward the root's value around the ring (n-1 hops)."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    perm = _fwd_perm(n)
    # Chain-forward: after k hops rank (root+k) receives root's value (every
    # rank forwards what it currently holds); each rank keeps the value that
    # arrives on its turn.
    idx = lax.axis_index(axis)
    cur = x
    kept = x
    for s in range(n - 1):
        cur = lax.ppermute(cur, axis, perm)
        kept = jnp.where((idx - root) % n == s + 1, cur, kept)
    return kept


# ---------------------------------------------------------------------------
# Flat (single-stage, native XLA) collectives — the homogeneous baseline.
#
# Each registration declares exactly the CommPolicy fields it consumes
# (``policy_fields=``, DESIGN.md §12); tacc.dispatch maps only those, so no
# signature needs a ``**_`` catch-all to swallow irrelevant knobs.
# ---------------------------------------------------------------------------

def _flat_rank_index(all_axes: tuple[str, ...]) -> jax.Array:
    """Pod-major flat rank of this device over ``all_axes`` (rank =
    pod·D + data, DESIGN.md §3) — the root-matching index of the
    rooted collectives (broadcast / reduce)."""
    flat_idx = jnp.zeros((), jnp.int32)
    stride = 1
    for a in reversed(all_axes):
        flat_idx = flat_idx + lax.axis_index(a) * stride
        stride *= lax.axis_size(a)
    return flat_idx


@tacc.register("all_reduce", "flat", default=True,
               policy_fields=("backend", "n_stripes", "wire_quant"))
def flat_all_reduce(x, axes: Axis, pod_axis: str | None = None, *,
                    backend: str = "xla", n_stripes: int = 1,
                    wire_quant: str | None = None):
    all_axes = _axes_tuple(axes) + ((pod_axis,) if pod_axis else ())
    if backend == "pallas":
        # the naive single-stage ring, but with the DMA kernels: one explicit
        # ring per axis (sum is associative, so per-axis rings == one psum)
        from repro.kernels import ring_dma
        out = x
        for a in all_axes:
            out = ring_dma.ring_all_reduce(out, a, n_stripes=n_stripes,
                                           wire_quant=wire_quant)
        return out
    return _native_reduce(lax.psum, x, all_axes)


@tacc.register("all_gather", "flat", default=True,
               policy_fields=("backend", "n_stripes", "wire_quant"))
def flat_all_gather(x, axes: Axis, pod_axis: str | None = None, *, dim: int = 0,
                    tiled: bool = True, backend: str = "xla",
                    n_stripes: int = 1, wire_quant: str | None = None):
    gather_axes = _axes_tuple(axes) + ((pod_axis,) if pod_axis else ())
    if backend == "pallas" and tiled:
        from repro.kernels import ring_dma
        out = jnp.moveaxis(x, dim, 0) if dim != 0 else x
        for a in gather_axes:
            out = ring_dma.ring_all_gather(out, a, n_stripes=n_stripes,
                                           wire_quant=wire_quant)
        return jnp.moveaxis(out, 0, dim) if dim != 0 else out
    out = x
    for a in gather_axes:
        out = lax.all_gather(out, a, axis=dim, tiled=tiled)
    return out


@tacc.register("reduce_scatter", "flat", default=True,
               policy_fields=("backend", "n_stripes", "wire_quant"))
def flat_reduce_scatter(x, axes: Axis, pod_axis: str | None = None, *,
                        dim: int = 0, backend: str = "xla",
                        n_stripes: int = 1, wire_quant: str | None = None):
    all_axes = ((pod_axis,) if pod_axis else ()) + _axes_tuple(axes)
    if backend == "pallas":
        from repro.kernels import ring_dma
        out = jnp.moveaxis(x, dim, 0) if dim != 0 else x
        for a in all_axes:
            out = ring_dma.ring_reduce_scatter(out, a, n_stripes=n_stripes,
                                               wire_quant=wire_quant)
        return jnp.moveaxis(out, 0, dim) if dim != 0 else out
    out = x
    for a in all_axes:
        out = _native_reduce(lax.psum_scatter, out, a,
                             scatter_dimension=dim, tiled=True)
    return out


@tacc.register("all_to_all", "flat", default=True)
def flat_all_to_all(x, axes: Axis, pod_axis: str | None = None, *,
                    split_axis: int = 0, concat_axis: int = 0):
    all_axes = ((pod_axis,) if pod_axis else ()) + _axes_tuple(axes)
    return lax.all_to_all(x, all_axes, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


@tacc.register("broadcast", "flat", default=True)
def flat_broadcast(x, axes: Axis, pod_axis: str | None = None, *, root: int = 0):
    all_axes = _axes_tuple(axes) + ((pod_axis,) if pod_axis else ())
    # emulate: zero non-root contributions, then sum.
    flat_idx = _flat_rank_index(all_axes)
    return _native_reduce(
        lax.psum, jnp.where(flat_idx == root, x, jnp.zeros_like(x)), all_axes)


@tacc.register("reduce", "flat", default=True)
def flat_reduce(x, axes: Axis, pod_axis: str | None = None, *, root: int = 0):
    all_axes = _axes_tuple(axes) + ((pod_axis,) if pod_axis else ())
    s = _native_reduce(lax.psum, x, all_axes)
    flat_idx = _flat_rank_index(all_axes)
    return jnp.where(flat_idx == root, s, jnp.zeros_like(s))


@tacc.register("p2p", "flat", default=True)
def p2p(x, axis: str, perm: Sequence[tuple[int, int]]):
    """Point-to-point send/recv (the RDMA verbs analogue)."""
    return lax.ppermute(x, axis, list(perm))


# ---------------------------------------------------------------------------
# Hierarchical (HetCCL) collectives: local native stage + cross-pod ring.
# ---------------------------------------------------------------------------

def _flatten_pad(x: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % multiple
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


@tacc.register("all_reduce", "hier",
               policy_fields=("backend", "n_stripes", "cross_dtype",
                              "wire_quant"))
def hier_all_reduce(x, axes: Axis, pod_axis: str | None = "pod", *,
                    cross_dtype=None, backend: str = "xla",
                    n_stripes: int = 1, wire_quant: str | None = None):
    """AllReduce = local ReduceScatter -> cross-pod ring AllReduce -> local AllGather.

    ``cross_dtype`` optionally compresses the cross-island stage (the slow
    links), a beyond-paper knob: gradients cast to e.g. bf16 only while they
    transit the pod boundary.  ``backend="pallas"`` swaps the cross-pod rings
    for the DMA rings (which additionally keep an f32 accumulator under the
    narrow wire — the fused decompression of DESIGN.md §10); ``n_stripes``
    is their multi-NIC stripe count (DESIGN.md §11) and ``wire_quant`` their
    per-chunk absmax codec (DESIGN.md §17) — when set it supersedes the
    ``cross_dtype`` cast (the codec already narrows the wire harder and the
    DMA rings keep the f32 accumulator underneath).
    """
    local = _axes_tuple(axes)
    if not pod_axis:
        return _native_reduce(lax.psum, x, local)
    cross_rs, cross_ag = resolve_ring_backend(backend, n_stripes=n_stripes,
                                              wire_quant=wire_quant)
    if wire_quant is not None and backend == "pallas":
        cross_dtype = None       # the codec owns the wire format
    D = 1
    for a in local:
        D *= lax.axis_size(a)
    P = lax.axis_size(pod_axis)
    shape, dtype = x.shape, x.dtype
    flat, pad = _flatten_pad(x, D * P)
    n = flat.shape[0]
    if D > 1:
        shard = _native_reduce(lax.psum_scatter, flat.reshape(D, n // D),
                               local, scatter_dimension=0, tiled=False)
    else:
        shard = flat
    if cross_dtype is not None and cross_dtype != dtype:
        shard = shard.astype(cross_dtype)
    shard = cross_ag(cross_rs(shard, pod_axis), pod_axis)
    if cross_dtype is not None and cross_dtype != dtype:
        shard = shard.astype(dtype)
    if D > 1:
        flat = lax.all_gather(shard, local, axis=0, tiled=False).reshape(n)
    else:
        flat = shard
    if pad:
        flat = flat[:n - pad]
    return flat.reshape(shape)


@tacc.register("all_gather", "hier",
               policy_fields=("backend", "n_stripes", "wire_quant"))
def hier_all_gather(x, axes: Axis, pod_axis: str | None = "pod", *, dim: int = 0,
                    tiled: bool = True, backend: str = "xla",
                    n_stripes: int = 1, wire_quant: str | None = None):
    """Local native gather, then cross-pod ring gather (pod-major order)."""
    out = flat_all_gather(x, axes, None, dim=dim, tiled=tiled)
    if pod_axis:
        _, cross_ag = resolve_ring_backend(backend, n_stripes=n_stripes,
                                           wire_quant=wire_quant)
        if dim != 0:
            out = jnp.moveaxis(out, dim, 0)
        out = cross_ag(out, pod_axis)
        if dim != 0:
            out = jnp.moveaxis(out, 0, dim)
    return out


@tacc.register("reduce_scatter", "hier",
               policy_fields=("backend", "n_stripes", "wire_quant"))
def hier_reduce_scatter(x, axes: Axis, pod_axis: str | None = "pod", *,
                        dim: int = 0, backend: str = "xla",
                        n_stripes: int = 1, wire_quant: str | None = None):
    """Cross-pod ring reduce-scatter first (P2P), then local native stage."""
    out = x
    if pod_axis:
        cross_rs, _ = resolve_ring_backend(backend, n_stripes=n_stripes,
                                           wire_quant=wire_quant)
        if dim != 0:
            out = jnp.moveaxis(out, dim, 0)
        out = cross_rs(out, pod_axis)
        if dim != 0:
            out = jnp.moveaxis(out, 0, dim)
    return flat_reduce_scatter(out, axes, None, dim=dim)


@tacc.register("all_to_all", "hier")
def hier_all_to_all(x, axes: Axis, pod_axis: str | None = "pod", *,
                    split_axis: int = 0, concat_axis: int = 0):
    """Two-stage A2A: cross-pod superblocks via P2P ring, then local native A2A.

    Matches flat all_to_all over (pod, *axes) with pod-major rank order for
    split_axis == concat_axis == 0.
    """
    if not pod_axis:
        return flat_all_to_all(x, axes, None, split_axis=split_axis,
                               concat_axis=concat_axis)
    assert split_axis == 0 and concat_axis == 0, "hier a2a supports dim 0"
    P = lax.axis_size(pod_axis)
    D = 1
    for a in _axes_tuple(axes):
        D *= lax.axis_size(a)
    n = x.shape[0]
    assert n % (P * D) == 0, (n, P, D)
    blk = x.reshape((P, D, n // (P * D)) + x.shape[1:])
    blk = ring_all_to_all(blk, pod_axis)             # exchange pod superblocks
    blk = blk.reshape((P * D, n // (P * D)) + x.shape[1:])
    blk = blk.reshape((P, n // P) + x.shape[1:])
    out = lax.all_to_all(blk, _axes_tuple(axes), split_axis=1, concat_axis=1,
                         tiled=True)
    return out.reshape((n,) + x.shape[1:])


@tacc.register("broadcast", "hier")
def hier_broadcast(x, axes: Axis, pod_axis: str | None = "pod", *, root: int = 0):
    out = flat_broadcast(x, axes, None, root=root)   # local stage from local root
    if pod_axis:
        out = ring_broadcast(out, pod_axis, root=0)
    return out


@tacc.register("reduce", "hier",
               policy_fields=("backend", "n_stripes", "wire_quant"))
def hier_reduce(x, axes: Axis, pod_axis: str | None = "pod", *, root: int = 0,
                backend: str = "xla", n_stripes: int = 1,
                wire_quant: str | None = None):
    s = hier_all_reduce(x, axes, pod_axis, backend=backend,
                        n_stripes=n_stripes, wire_quant=wire_quant)
    all_axes = _axes_tuple(axes) + ((pod_axis,) if pod_axis else ())
    flat_idx = _flat_rank_index(all_axes)
    return jnp.where(flat_idx == root, s, jnp.zeros_like(s))


# ---------------------------------------------------------------------------
# Pipelined (multi-channel) hierarchical collectives.
#
# The hier_* ops above run their two stages serially over one monolithic
# payload: the cross-pod link idles during the vendor-local stage and vice
# versa.  The pipelined variants split the payload into ``n_channels`` chunks
# and software-pipeline the schedule so chunk k's cross-pod ring overlaps
# chunk k+1's local native stage (H2 / Holmes style).  The cross stage also
# uses the bidirectional rings, halving per-link byte-hops.
# ---------------------------------------------------------------------------

def software_pipeline(chunks: list, stages: Sequence) -> list:
    """Run every chunk through ``stages`` on a skewed wavefront schedule.

    Wave t computes stage (t - k) of chunk k for every live chunk, and pins
    each wave together with an ``optimization_barrier`` so XLA's scheduler
    can overlap the wave's stage executions (chunk k's cross-pod ring runs
    while chunk k+1 is in its local stage) but cannot re-serialize them
    across waves.  Semantically the identity schedule.
    """
    C, S = len(chunks), len(stages)
    vals = list(chunks)
    for t in range(C + S - 1):
        live = [k for k in range(C) if 0 <= t - k < S]
        outs = [stages[t - k](vals[k]) for k in live]
        if len(outs) > 1:
            outs = list(lax.optimization_barrier(tuple(outs)))
        for k, o in zip(live, outs):
            vals[k] = o
    return vals


MAX_CHANNELS = 16    # schedule-unroll guard: each channel emits its own stages


def resolve_channels(nbytes: int, n_channels: int,
                     chunk_bytes: int | None, limit: int,
                     n_stripes: int = 1) -> int:
    """Channel count for a payload: explicit chunk size wins, else
    ``n_channels``; clamped to [1, min(limit, MAX_CHANNELS)] where ``limit``
    is the payload granularity (can't split finer than one element/row) and
    MAX_CHANNELS bounds the unrolled wavefront the schedule emits.

    ``n_stripes`` is the transport layer's per-channel stripe count: the two
    knobs fragment multiplicatively (each channel's ring chunk is further
    pad-and-sliced over k links), so channels are additionally clamped so a
    ``channels × stripes`` fragment never drops below one MXU tile
    (``transport.MXU_TILE_BYTES``) — a tiny gradient bucket runs one wide
    channel instead of 16 tile-starved ones (DESIGN.md §11).
    """
    from repro.transport.stripe import MXU_TILE_BYTES
    c = -(-nbytes // chunk_bytes) if chunk_bytes else n_channels
    tile_limit = max(nbytes // (MXU_TILE_BYTES * max(int(n_stripes), 1)), 1)
    return max(1, min(c, limit, MAX_CHANNELS, tile_limit))


@tacc.register("all_reduce", "pipelined",
               policy_fields=("backend", "n_stripes", "cross_dtype",
                              "n_channels", "wire_quant"))
def pipelined_all_reduce(x, axes: Axis, pod_axis: str | None = "pod", *,
                         cross_dtype=None, n_channels: int = 4,
                         pipeline_chunk_bytes: int | None = None,
                         bidir: bool = True, backend: str = "xla",
                         n_stripes: int = 1, wire_quant: str | None = None):
    """AllReduce as a C-channel pipeline of (local RS -> cross ring -> local AG).

    Equals :func:`hier_all_reduce` numerically; chunk k's cross-pod stage is
    scheduled alongside chunk k+1's local reduce-scatter and chunk k-1's
    local all-gather, so the slow cross link streams continuously.
    """
    local = _axes_tuple(axes)
    if not pod_axis:
        return _native_reduce(lax.psum, x, local) if local else x
    D = 1
    for a in local:
        D *= lax.axis_size(a)
    P = lax.axis_size(pod_axis)
    shape, dtype = x.shape, x.dtype
    C = resolve_channels(x.size * x.dtype.itemsize, n_channels,
                         pipeline_chunk_bytes, max(x.size // (D * P), 1),
                         n_stripes)
    flat, pad = _flatten_pad(x, C * D * P)
    n = flat.shape[0]
    chunks = list(jnp.split(flat, C)) if C > 1 else [flat]
    cross_ring_rs, cross_ring_ag = resolve_ring_backend(
        backend, bidir=bidir, n_stripes=n_stripes, wire_quant=wire_quant)
    if wire_quant is not None and backend == "pallas":
        cross_dtype = None       # the codec owns the wire format (§17)

    def local_rs(c):
        if D == 1:
            return c
        return _native_reduce(lax.psum_scatter,
                              c.reshape(D, c.shape[0] // D), local,
                              scatter_dimension=0, tiled=False)

    def cross(c):
        if cross_dtype is not None and cross_dtype != dtype:
            c = c.astype(cross_dtype)
        c = cross_ring_ag(cross_ring_rs(c, pod_axis), pod_axis)
        if cross_dtype is not None and cross_dtype != dtype:
            c = c.astype(dtype)
        return c

    def local_ag(c):
        if D == 1:
            return c
        return lax.all_gather(c, local, axis=0, tiled=False).reshape(-1)

    outs = software_pipeline(chunks, (local_rs, cross, local_ag))
    flat = jnp.concatenate(outs) if C > 1 else outs[0]
    if pad:
        flat = flat[:n - pad]
    return flat.reshape(shape)


@tacc.register("all_gather", "pipelined",
               policy_fields=("backend", "n_stripes", "n_channels",
                              "wire_quant"))
def pipelined_all_gather(x, axes: Axis, pod_axis: str | None = "pod", *,
                         dim: int = 0, tiled: bool = True,
                         n_channels: int = 4,
                         pipeline_chunk_bytes: int | None = None,
                         bidir: bool = True, backend: str = "xla",
                         n_stripes: int = 1, wire_quant: str | None = None):
    """Two-stage gather, pipelined: chunk k's cross-pod ring gather overlaps
    chunk k+1's local native gather.  Pod-major result order (same as hier)."""
    if not pod_axis:
        return flat_all_gather(x, axes, None, dim=dim, tiled=tiled)
    if not tiled:
        # stacked (new-axis) layout: chunk re-interleaving doesn't apply —
        # keep the serial hier schedule so the output matches flat/hier.
        return hier_all_gather(x, axes, pod_axis, dim=dim, tiled=False)
    xm = jnp.moveaxis(x, dim, 0) if dim != 0 else x
    c0 = xm.shape[0]
    C = resolve_channels(x.size * x.dtype.itemsize, n_channels,
                         pipeline_chunk_bytes, c0, n_stripes)
    chunks = list(jnp.array_split(xm, C)) if C > 1 else [xm]
    _, cross_ring_ag = resolve_ring_backend(backend, bidir=bidir,
                                            n_stripes=n_stripes,
                                            wire_quant=wire_quant)

    def local_ag(c):
        return flat_all_gather(c, axes, None, dim=0, tiled=True)

    def cross(c):
        return cross_ring_ag(c, pod_axis)

    outs = software_pipeline(chunks, (local_ag, cross))
    if C > 1:
        # chunk j holds [rank0 chunk-j, rank1 chunk-j, ...]; re-interleave to
        # rank-major: (W, cj, ...) stacked along the chunk dim.
        W = axis_world(_axes_tuple(axes)) * lax.axis_size(pod_axis)
        parts = [o.reshape((W, o.shape[0] // W) + o.shape[1:]) for o in outs]
        out = jnp.concatenate(parts, axis=1)
        out = out.reshape((W * c0,) + xm.shape[1:])
    else:
        out = outs[0]
    return jnp.moveaxis(out, 0, dim) if dim != 0 else out


@tacc.register("reduce_scatter", "pipelined",
               policy_fields=("backend", "n_stripes", "n_channels",
                              "wire_quant"))
def pipelined_reduce_scatter(x, axes: Axis, pod_axis: str | None = "pod", *,
                             dim: int = 0, n_channels: int = 4,
                             pipeline_chunk_bytes: int | None = None,
                             bidir: bool = True, backend: str = "xla",
                             n_stripes: int = 1,
                             wire_quant: str | None = None):
    """Two-stage reduce-scatter, pipelined: chunk k's local native stage
    overlaps chunk k+1's cross-pod ring."""
    if not pod_axis:
        return flat_reduce_scatter(x, axes, None, dim=dim)
    xm = jnp.moveaxis(x, dim, 0) if dim != 0 else x
    W = axis_world(_axes_tuple(axes)) * lax.axis_size(pod_axis)
    n = xm.shape[0]
    assert n % W == 0, (n, W)
    s = n // W                                        # rows this rank keeps
    C = resolve_channels(x.size * x.dtype.itemsize, n_channels,
                         pipeline_chunk_bytes, s, n_stripes)
    # chunk j must carry rows [r*s + j*s/C, ...) for every rank r, so split
    # the per-rank dim, not the raw leading dim.
    grouped = xm.reshape((W, s) + xm.shape[1:])
    chunks = [c.reshape((W * c.shape[1],) + xm.shape[1:])
              for c in jnp.array_split(grouped, C, axis=1)] if C > 1 else [xm]
    cross_ring_rs, _ = resolve_ring_backend(backend, bidir=bidir,
                                            n_stripes=n_stripes,
                                            wire_quant=wire_quant)

    def cross(c):
        return cross_ring_rs(c, pod_axis)

    def local_rs(c):
        return flat_reduce_scatter(c, axes, None, dim=0)

    outs = software_pipeline(chunks, (cross, local_rs))
    out = jnp.concatenate(outs) if C > 1 else outs[0]
    return jnp.moveaxis(out, 0, dim) if dim != 0 else out


# ---------------------------------------------------------------------------
# Differentiable wrappers (used inside fwd/bwd of the model, e.g. ZeRO-3).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fsdp_all_gather(x: jax.Array, axis: str, dim: int = 0) -> jax.Array:
    """AllGather whose adjoint is ReduceScatter — ZeRO-3's parameter gather.

    The gathered value is pinned behind an optimization barrier so XLA cannot
    hoist a later bf16->f32 convert BEFORE the gather (which would double the
    wire bytes; observed on the CPU backend, which upcasts bf16 dots).
    """
    out = lax.all_gather(x, axis, axis=dim, tiled=True)
    return lax.optimization_barrier(out)


def _fsdp_ag_fwd(x, axis, dim):
    return fsdp_all_gather(x, axis, dim), None


def _fsdp_ag_bwd(axis, dim, _, g):
    # Gradient reduce-scatter with the narrow wire (g.dtype) and f32
    # accumulation — the collective_reduce kernel semantics.  Also dodges an
    # XLA:CPU miscompile of bf16 psum_scatter inside partially-manual
    # shard_map (see DESIGN.md §8).  Routed through the active communicator's
    # reduce_scatter policy for this payload (DESIGN.md §12): under
    # backend="pallas" the DMA ring keeps the same narrow-wire / f32
    # contract inside the kernel (DESIGN.md §10).
    from repro.core import hetccl   # lazy: hetccl imports this module
    gm = jnp.moveaxis(g, dim, 0) if dim else g
    pol = hetccl.current().policy("reduce_scatter",
                                  g.size * jnp.dtype(g.dtype).itemsize)
    if pol.backend == "pallas":
        from repro.kernels import ring_dma
        out = ring_dma.ring_reduce_scatter(gm, axis, wire_dtype=g.dtype,
                                           n_stripes=pol.n_stripes,
                                           wire_quant=pol.wire_quant)
    else:
        out = ring_reduce_scatter_mixed(gm, axis)
    out = jnp.moveaxis(out, 0, dim) if dim else out
    return (out.astype(g.dtype),)


fsdp_all_gather.defvjp(_fsdp_ag_fwd, _fsdp_ag_bwd)
