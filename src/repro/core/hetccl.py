"""HetCCL public API — the drop-in collective layer (paper §4, Fig 2b).

Applications (our trainer, serving engine, examples) call these functions;
dispatch is **communicator-scoped** (DESIGN.md §12): the active
:class:`repro.comm.Communicator` resolves each call's payload to a
:class:`~repro.comm.policy.CommPolicy` from its per-op, size-classed
``PolicyTable``, and the TACC registry routes to the *flat* (single-stage
native), *hier* (vendor-local + cross-pod P2P), or *pipelined*
(multi-channel hier with the local stage overlapping the cross-island ring)
implementation at **runtime**.  Swapping the backend under an unmodified
application — the paper's LD_PRELOAD trick — is :func:`install`;
:func:`uninstall` / :func:`use` restore it.  :class:`HetCCLConfig` remains
as the legacy single-policy facade: it compiles into a one-row table
(:meth:`HetCCLConfig.to_table`) and is accepted everywhere a communicator
is.

Also provides :func:`tree_all_reduce`, a bucketed gradient all-reduce
(flatten leaves -> fixed-size fusion buckets -> pipelined reduce-scatter ->
all-gather schedule across buckets), the classic DDP optimization NCCL users
get from bucketing; plus optional ``cross_dtype`` compression of the
cross-island stage only.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core import tacc
from repro.core import collectives as _coll  # noqa: F401  (registers impls)
from repro.comm.communicator import Communicator, from_config, variant_for
from repro.comm.policy import CommPolicy, PolicyTable, size_class

_SWAPPABLE_OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
                  "broadcast", "reduce")


@dataclasses.dataclass(frozen=True)
class HetCCLConfig:
    """Runtime configuration of the collective layer.

    mode:        "flat" | "hier" | "pipelined" | "auto".  "auto" picks "hier"
                 iff a pod axis is present (i.e. the job spans islands) —
                 mirroring HetCCL's transparent activation on heterogeneous
                 clusters.  "pipelined" is the multi-channel hier schedule
                 (opt-in; see DESIGN.md §2).
    local_axes:  intra-island mesh axes carrying data parallelism.
    pod_axis:    the island boundary axis (None on single-island meshes).
    bucket_bytes: gradient fusion bucket size.
    cross_dtype: optional dtype for the cross-island stage (gradient
                 compression on the slow links; beyond-paper).
    n_channels:  pipeline channel count of the "pipelined" mode (chunks per
                 payload; the local stage of chunk k+1 overlaps the
                 cross-island ring of chunk k).
    pipeline_chunk_bytes: alternative channel sizing — split payloads into
                 ~this many bytes per chunk instead of a fixed channel count.
    Either sizing is clamped per payload to ``collectives.MAX_CHANNELS`` (16)
    and to the payload's own granularity.
    backend:     "xla" | "pallas" ring implementation (orthogonal to mode).
                 "pallas" swaps the cross-island rings for the async
                 remote-copy kernels of ``repro.kernels.ring_dma`` with
                 double-buffered in-kernel reduction (DESIGN.md §10); on
                 non-TPU platforms they fall back to an interpret-mode-
                 equivalent ppermute schedule with the same numerics.
    n_stripes:   multi-NIC stripe count of the DMA rings (DESIGN.md §11):
                 each cross-island wire hop is pad-and-sliced over this many
                 per-link DMA streams.  Only meaningful under
                 ``backend="pallas"`` — the xla ppermute ring is a single
                 logical transfer, so :meth:`resolved_stripes` collapses the
                 knob to 1 there.  The plan autotuner searches it jointly
                 (``SearchSpace.stripe_counts``).
    wire_quant:  optional wire-quantization codec of the DMA rings
                 (None | "int8" | "fp8", DESIGN.md §17): ring payloads cross
                 each hop as per-chunk absmax codes with an f32 scale
                 sidecar, accumulated in f32.  Pallas-backend only — the
                 communicator's creation-time resolve collapses it to None
                 for xla rows and non-ring ops.
    """

    mode: str = "auto"
    local_axes: tuple[str, ...] = ("data",)
    pod_axis: str | None = "pod"
    bucket_bytes: int = 64 * 1024 * 1024
    cross_dtype: Any = None
    n_channels: int = 4
    pipeline_chunk_bytes: int | None = None
    backend: str = "xla"
    n_stripes: int = 1
    wire_quant: str | None = None

    def resolved_mode(self) -> str:
        if self.mode == "auto":
            return "hier" if self.pod_axis else "flat"
        if self.mode not in ("flat", "hier", "pipelined"):
            raise ValueError(
                f"unknown collective mode {self.mode!r}; "
                "expected flat | hier | pipelined | auto")
        return self.mode

    def resolved_backend(self) -> str:
        if self.backend not in _coll.RING_BACKENDS:
            raise ValueError(
                f"unknown collective backend {self.backend!r}; "
                f"expected one of {_coll.RING_BACKENDS}")
        return self.backend

    def resolved_stripes(self) -> int:
        """Effective per-link DMA stream count (DESIGN.md §11): validated,
        clamped to the transport layer's cap, and collapsed to 1 for the xla
        backend (one ppermute is one logical transfer — there is nothing to
        stripe)."""
        from repro.transport.stripe import MAX_STRIPES
        if int(self.n_stripes) < 1:
            raise ValueError(f"n_stripes must be >= 1, got {self.n_stripes}")
        if self.resolved_backend() != "pallas":
            return 1
        return min(int(self.n_stripes), MAX_STRIPES)

    def dp_axes(self) -> tuple[str, ...]:
        """Pod-major: matches the gather order of both flat and hier
        all_gather (pod blocks of local blocks) and P(('pod','data'))."""
        return ((self.pod_axis,) if self.pod_axis else ()) + self.local_axes

    def to_policy(self) -> CommPolicy:
        """Compile this config's knobs into one resolved
        :class:`~repro.comm.policy.CommPolicy` (validates eagerly)."""
        return CommPolicy(mode=self.resolved_mode(),
                          backend=self.resolved_backend(),
                          n_channels=max(int(self.n_channels), 1),
                          n_stripes=self.resolved_stripes(),
                          cross_dtype=self.cross_dtype,
                          wire_quant=(self.wire_quant
                                      if self.resolved_backend() == "pallas"
                                      else None))

    def to_table(self) -> PolicyTable:
        """The facade contract (DESIGN.md §12): a legacy single-policy
        config IS a one-row policy table — every (op, size class) resolves
        to :meth:`to_policy`, bit-for-bit."""
        return PolicyTable.single(self.to_policy())

    def communicator(self) -> Communicator:
        """Compile into a :class:`repro.comm.Communicator` (what
        :func:`install`/:func:`use` do with a config internally)."""
        return from_config(self)


_CURRENT = from_config(HetCCLConfig(pod_axis=None))
# (previous communicator, TACC defaults captured before each install) — LIFO
# so nested installs unwind correctly.
_INSTALL_STACK: list[tuple[Communicator, dict[str, str]]] = []


def _as_communicator(cfg) -> Communicator:
    """Normalize a ``cfg`` argument: None -> the active communicator,
    HetCCLConfig -> its one-row-table facade compile, Communicator -> as-is."""
    if cfg is None:
        return _CURRENT
    if isinstance(cfg, Communicator):
        return cfg
    return from_config(cfg)


def _variant_for(op: str, mode: str) -> str:
    """Back-compat alias of :func:`repro.comm.communicator.variant_for`."""
    return variant_for(op, mode)


def install(config: "HetCCLConfig | Communicator") -> Communicator:
    """Swap the active collective backend (the LD_PRELOAD analogue).

    Existing training code keeps calling the same functions; only the active
    communicator (and the registry defaults derived from its policy table)
    changes.  Installing exactly the communicator the most recent install
    displaced is recognized as that undo — the legacy
    ``prev = install(cfg); ...; install(prev)`` restore pattern unwinds the
    stack instead of growing it.

    Args:
        config: the :class:`repro.comm.Communicator` to activate, or a
            legacy :class:`HetCCLConfig` (compiled into its one-row-table
            facade).  A planner-produced config
            (``repro.plan.TrainPlan.hetccl_config()``, DESIGN.md §9) plugs
            in here unchanged.
    Returns:
        The previously active communicator; :func:`uninstall` (or the
        :func:`use` context manager) pops the install and restores the TACC
        registry defaults it displaced.
    Example::

        prev = hetccl.install(HetCCLConfig(mode="pipelined", n_channels=4))
        ...   # unmodified application code now runs pipelined collectives
        hetccl.uninstall()
    """
    return _install(config, allow_undo=True)


def _install(config, *, allow_undo: bool) -> Communicator:
    global _CURRENT
    c = _as_communicator(config)      # validates before mutating any state
    prev = _CURRENT
    if allow_undo and _INSTALL_STACK and c == _INSTALL_STACK[-1][0]:
        uninstall()
        return prev
    prev_defaults = {op: tacc.get_default(op) for op in _SWAPPABLE_OPS}
    _INSTALL_STACK.append((prev, prev_defaults))
    _CURRENT = c
    for op in _SWAPPABLE_OPS:
        tacc.set_default(op, c.default_variant(op))
    return prev


def uninstall() -> Communicator:
    """Undo the most recent :func:`install`: restore both the previous
    communicator and the TACC registry defaults that install() mutated.

    Returns:
        The communicator that was active before the uninstalled one.
        Calling with no install outstanding is a no-op that returns the
        current one.
    """
    global _CURRENT
    if not _INSTALL_STACK:
        return _CURRENT
    prev, prev_defaults = _INSTALL_STACK.pop()
    _CURRENT = prev
    for op, variant in prev_defaults.items():
        tacc.set_default(op, variant)
    return prev


@contextlib.contextmanager
def use(config: "HetCCLConfig | Communicator"):
    """Scoped backend swap: ``with hetccl.use(cfg): ...`` installs ``cfg``
    (a communicator, or a legacy config compiled into one) and restores the
    previous backend (communicator + registry defaults) on exit.

    Always pushes a stack entry (no install()-style undo detection), so its
    enter/exit pair stays balanced even when ``cfg`` equals a config an
    enclosing scope displaced.

    Args:
        config: the :class:`HetCCLConfig` or :class:`Communicator` to
            activate inside the scope.
    Yields:
        The installed config.
    Example::

        with hetccl.use(HetCCLConfig(mode="hier")):
            loss = train_step(state, batch)   # hier collectives
        # previous backend restored here, even on exception
    """
    _install(config, allow_undo=False)
    try:
        yield config
    finally:
        uninstall()


def current() -> Communicator:
    """Return the active :class:`repro.comm.Communicator` (the install-stack
    top, or the module default — flat, no pod axis — when nothing is
    installed)."""
    return _CURRENT


def _payload_bytes(op: str, x, c: Communicator) -> int:
    """The logical payload a collective moves — what the policy table (and
    the simulator's pricing, DESIGN.md §12) keys on.  For all_gather that is
    the *gathered* buffer (the wire carries (n-1)/n of it), not the input
    shard, so runtime resolution matches the size the planner tuned the row
    at.  The world factor is only computed for genuinely size-classed
    tables (a one-row facade resolves identically at any size, and may be
    dispatched outside a mesh context where axis sizes don't exist)."""
    nbytes = int(x.size) * jnp.dtype(x.dtype).itemsize
    if op == "all_gather" and c.table.rows:
        nbytes *= _coll.axis_world(c.dp_axes())
    return nbytes


# Armed collective watchdog (DESIGN.md §15), or None.  Module-global like
# the _CURRENT communicator: the dispatch path must not thread a watchdog
# argument through every collective call site.
_WATCHDOG = None


def arm_watchdog(wd) -> None:
    """Install a :class:`repro.elastic.watchdog.CollectiveWatchdog` on the
    dispatch path: every *eagerly executed* collective is timed against its
    model-derived deadline and a breach raises ``CollectiveHangError``.
    Traced dispatches (inside jit — the train step compiles once and the
    per-call wall time belongs to XLA, not to one collective) pass through
    unwatched; step-level stalls there are the elastic loop's
    ``watchdog.stall`` territory."""
    global _WATCHDOG
    _WATCHDOG = wd


def disarm_watchdog() -> None:
    global _WATCHDOG
    _WATCHDOG = None


# Telemetry hook (DESIGN.md §16): an installed repro.obs.Tracer records every
# eager dispatch as a policy-tagged span (a traced one is named instead, see
# SCOPE).  Same eager-only span contract as the watchdog above; same
# stack-safe install/uninstall shape as the communicator.
_TRACER = None
_TRACER_STACK: list = []


def install_tracer(tracer) -> None:
    """Make ``tracer`` the process dispatch-span recorder.  Stack-safe:
    :func:`uninstall_tracer` restores whatever was installed before."""
    global _TRACER
    _TRACER_STACK.append(_TRACER)
    _TRACER = tracer


def uninstall_tracer() -> None:
    global _TRACER
    _TRACER = _TRACER_STACK.pop() if _TRACER_STACK else None


def current_tracer():
    """The tracer observing dispatches, if any (communicator-pinned tracers
    take precedence inside :func:`_call` itself)."""
    return _TRACER


# Name of a traced dispatch in the compiled program (DESIGN.md §16): every op
# it emits carries ``hetccl.<op>.<size_class>.<variant>`` in its HLO op_name,
# with the stage scopes of ``collectives`` (intra / cross) nested below.
SCOPE = "hetccl.{op}.{size_class}.{variant}"


def _call(op: str, x, cfg, **kw):
    """Communicator-scoped dispatch (DESIGN.md §12): resolve this payload's
    policy from the active communicator's (op, size class) table, then let
    tacc.dispatch map exactly the policy fields the resolved variant
    declared.  Eager dispatches are observed by an armed watchdog (deadline
    enforcement, DESIGN.md §15) and an installed/pinned tracer (telemetry
    spans, DESIGN.md §16); traced dispatches inside jit skip both and are
    *named* instead: their ops run under the :data:`SCOPE` of this call, so
    a device trace attributes them to ``(op, size_class, variant)``."""
    c = _as_communicator(cfg)
    nbytes = _payload_bytes(op, x, c)
    pol = c.policy(op, nbytes)
    variant = c.variant_for(op, pol)
    if variant == "pipelined" and c.pipeline_chunk_bytes:
        kw.setdefault("pipeline_chunk_bytes", c.pipeline_chunk_bytes)
    if isinstance(x, jax.core.Tracer):
        name = SCOPE.format(op=op, variant=variant,
                            size_class=size_class(nbytes, c.table.bounds))
        with jax.named_scope(name):
            return tacc.dispatch(op, x, c.local_axes, c.pod_axis,
                                 variant=variant, policy=pol, **kw)
    tr = c.tracer if c.tracer is not None else _TRACER
    if tr is None and _WATCHDOG is None:
        return tacc.dispatch(op, x, c.local_axes, c.pod_axis,
                             variant=variant, policy=pol, **kw)
    with contextlib.ExitStack() as stack:
        if tr is not None and tr.enabled:
            stack.enter_context(tr.collective(op, nbytes, pol))
        if _WATCHDOG is not None:
            stack.enter_context(_WATCHDOG.watch(op, nbytes))
        return tacc.dispatch(op, x, c.local_axes, c.pod_axis,
                             variant=variant, policy=pol, **kw)


def all_reduce(x, cfg=None, **kw):
    """Sum ``x`` across the DP world (pod-major flat group, DESIGN.md §3).

    Must run inside the train step's shard_map whose manual axes include the
    config's DP axes — like every op below.

    Args:
        x: array shard to reduce.
        cfg: optional :class:`Communicator` or legacy :class:`HetCCLConfig`
            override; defaults to the installed communicator.
        **kw: implementation extras (e.g. ``cross_dtype`` to compress the
            cross-island stage — normally carried by the resolved policy).
    Returns:
        The summed array, identical on every DP rank.
    Example::

        grads = hetccl.all_reduce(grads)      # policy picked by install()
    """
    return _call("all_reduce", x, cfg, **kw)


def all_gather(x, cfg=None, **kw):
    """Concatenate every DP rank's ``x`` along ``dim`` (kw, default 0),
    pod-major.  Returns an array ``world_size()`` times larger on that dim."""
    return _call("all_gather", x, cfg, **kw)


def reduce_scatter(x, cfg=None, **kw):
    """Sum across the DP world, then keep this rank's 1/world shard of dim
    ``dim`` (kw, default 0).  The bandwidth-optimal half of an all-reduce;
    ZeRO-3's gradient op.  Returns the reduced shard."""
    return _call("reduce_scatter", x, cfg, **kw)


def all_to_all(x, cfg=None, **kw):
    """Transpose shard ownership: split ``split_axis`` world-ways, every rank
    keeps chunk j of rank i concatenated on ``concat_axis`` (kwargs).  MoE's
    dispatch/return op.  No pipelined variant — degrades to hier."""
    return _call("all_to_all", x, cfg, **kw)


def broadcast(x, cfg=None, **kw):
    """Every rank receives root's ``x`` (kw ``root``, default 0).  Returns
    the root value everywhere.  No pipelined variant — degrades to hier."""
    return _call("broadcast", x, cfg, **kw)


def reduce(x, cfg=None, **kw):
    """Sum across the DP world; only ``root`` (kw, default 0) keeps the
    result, other ranks get zeros.  No pipelined variant — degrades to hier."""
    return _call("reduce", x, cfg, **kw)


def p2p(x, axis: str, perm: Sequence[tuple[int, int]]):
    """Raw point-to-point permute over ``axis`` (the paper's RDMA verbs):
    ``perm`` lists (src, dst) rank pairs; ranks not named receive zeros."""
    return tacc.dispatch("p2p", x, axis, perm)


def world_size(cfg=None) -> int:
    """Total DP ranks of ``cfg``'s axes (pod × local) inside the current
    shard_map; 1 outside any mesh context.  ``cfg``: communicator or legacy
    config, default the installed communicator."""
    return _coll.axis_world(_as_communicator(cfg).dp_axes())


# ---------------------------------------------------------------------------
# Bucketed gradient reduction (DDP-style fusion).
# ---------------------------------------------------------------------------

def _make_buckets(leaves, bucket_bytes: int) -> list[list[int]]:
    """Group leaf indices into ~bucket_bytes fusion buckets of equal dtype."""
    order = sorted(range(len(leaves)),
                   key=lambda i: jnp.dtype(leaves[i].dtype).name)
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    cur_dtype = None
    for i in order:
        lf = leaves[i]
        nbytes = lf.size * lf.dtype.itemsize
        if cur and (lf.dtype != cur_dtype or cur_bytes + nbytes > bucket_bytes):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_dtype = lf.dtype
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def tree_all_reduce(tree, cfg=None, *, mean_by=None):
    """All-reduce every leaf of ``tree``, fused into ~bucket_bytes buckets.

    Leaves are flattened, grouped by dtype into buckets, and reduced with a
    *pipelined reduce-scatter -> all-gather schedule*: each bucket's
    all-reduce is decomposed into its bandwidth-optimal halves and the
    buckets are run on a skewed wavefront, so bucket i's all-gather overlaps
    bucket i+1's reduce-scatter (on top of whatever intra-op pipelining the
    resolved per-bucket policy adds).  Numerically equal to one blocking
    all-reduce per bucket.

    ``cfg``: communicator or legacy config (default: the installed
    communicator) — its ``bucket_bytes`` sizes the fusion buckets and its
    policy table routes each bucket's RS/AG by payload size.

    ``mean_by``: optional scalar (e.g. summed token count) every *floating*
    leaf is divided by after reduction (integer leaves stay summed).
    """
    c = _as_communicator(cfg)
    leaves, treedef = jax.tree.flatten(tree)
    buckets = _make_buckets(leaves, c.bucket_bytes)
    world = world_size(c)

    flats, pads = [], []
    for bucket in buckets:
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in bucket]) \
            if len(bucket) > 1 else leaves[bucket[0]].reshape(-1)
        pad = (-flat.shape[0]) % max(world, 1)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        flats.append(flat)
        pads.append(pad)

    big = max((int(f.size) * jnp.dtype(f.dtype).itemsize for f in flats),
              default=0)
    if world > 1 and c.policy("all_reduce", big).cross_dtype is None:
        reduced = _coll.software_pipeline(
            flats,
            (lambda f: reduce_scatter(f, c, dim=0),
             lambda s: all_gather(s, c, dim=0)))
    elif world > 1:
        # cross-stage compression only exists on the fused all_reduce path
        reduced = _coll.software_pipeline(
            flats, (lambda f: all_reduce(f, c),))
    else:
        reduced = flats

    out = list(leaves)
    for bucket, red, pad in zip(buckets, reduced, pads):
        if pad:
            red = red[:red.shape[0] - pad]
        off = 0
        for i in bucket:
            sz = leaves[i].size
            out[i] = red[off:off + sz].reshape(leaves[i].shape)
            off += sz
    if mean_by is not None:
        out = [o / mean_by.astype(o.dtype) if jnp.issubdtype(o.dtype, jnp.floating)
               else o for o in out]
    return jax.tree.unflatten(treedef, out)
