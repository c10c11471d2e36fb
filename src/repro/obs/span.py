"""Nestable spans + the collective span source (DESIGN.md §16).

A :class:`Span` is one timed region — a collective dispatch, a train step, a
probe, any ``with tracer.span(...)`` block — carried as plain data so every
consumer (the flight recorder's ring, the metrics registry, the Chrome-trace
exporter) can subscribe to the same stream.  The :class:`Tracer` owns the
open-span stack (nesting), the wall clock (injectable, so tests are
deterministic), and the simulator pricing cache that stamps each *collective*
span with the α-β model's time for exactly the policy that dispatched — so a
span carries its own modeled-vs-measured residual, the per-dispatch analogue
of the PR-7 calibration rows (DESIGN.md §14).

The dispatch hook lives in ``repro.core.hetccl._call`` (mirroring the
watchdog hook, DESIGN.md §15): every **eager** dispatch is recorded here.
Traced dispatches (inside jit) are not spans — the per-call wall time there
belongs to XLA's whole step — but they are *named*: each runs under a
``hetccl.<op>.<size_class>.<variant>`` scope of the compiled program, and a
profiler's device trace times it (the elastic loop's telemetry probes still
keep eager per-cell evidence flowing in real runs, ``repro.obs.probe``).

jax-free and stdlib-pure: the simulator import is lazy and numpy-only.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Iterable, Mapping

from repro.comm.policy import size_class

SPAN_SCHEMA_VERSION = 1

CAT_COLLECTIVE = "collective"
CAT_STEP = "step"
CAT_PHASE = "phase"


@dataclasses.dataclass
class Span:
    """One timed region.  ``dur_s`` is None while the span is open; a
    finished collective span with a modeled price exposes ``residual``
    (measured/modeled — the same ratio convention as
    :class:`repro.plan.measured.CalibrationRow`)."""

    id: int
    name: str
    cat: str
    track: str
    t0_s: float
    dur_s: float | None = None
    depth: int = 0
    parent: int | None = None
    step: int | None = None
    pod: str | None = None
    tags: dict = dataclasses.field(default_factory=dict)
    modeled_s: float | None = None

    @property
    def residual(self) -> float | None:
        """measured / modeled wall time (None until both exist)."""
        if self.dur_s is None or not self.modeled_s:
            return None
        return self.dur_s / self.modeled_s

    def summary(self) -> dict:
        """JSON-friendly digest — the flight-recorder / export wire form."""
        return {"span_schema": SPAN_SCHEMA_VERSION, "id": self.id,
                "name": self.name, "cat": self.cat, "track": self.track,
                "t0_s": self.t0_s, "dur_s": self.dur_s, "depth": self.depth,
                "parent": self.parent, "step": self.step, "pod": self.pod,
                "tags": dict(self.tags), "modeled_s": self.modeled_s,
                "residual": self.residual}


class Tracer:
    """Nestable span recording with sink fan-out.

    ``sinks`` are objects with an ``on_span(span)`` method (the flight
    recorder and the fleet metrics registry); each *finished* span is handed
    to every sink.  ``enabled=False`` (or :meth:`disable`) turns
    :meth:`collective` into a no-op context — the dispatch hook additionally
    short-circuits before even calling in, so the disabled overhead on the
    hot path is one attribute read (guarded by ``tests/test_obs.py``).

    ``cluster`` (a :class:`repro.core.topology.ClusterSpec`) is the pricing
    side: with it set, every collective span gets the simulator's modeled
    time for its exact ``(op, nbytes, policy)`` — memoized, since a training
    run dispatches the same few cells thousands of times.

    ``comm_epoch`` is stamped into every collective span's tags; the elastic
    loop bumps it on each membership/communicator rebuild so post-rebuild
    dispatches are distinguishable in the trace (DESIGN.md §13).
    """

    def __init__(self, *, cluster=None, clock: Callable[[], float] =
                 time.perf_counter, sinks: Iterable = (), enabled: bool = True,
                 comm_epoch: int = 0):
        self.cluster = cluster
        self.enabled = enabled
        self.comm_epoch = comm_epoch
        self.sinks = list(sinks)
        self.spans: list[Span] = []
        self._clock = clock
        self._stack: list[Span] = []
        self._next_id = 0
        self._step: int | None = None
        self._extra: dict = {}
        self._price_cache: dict[tuple, float | None] = {}

    # -- lifecycle ----------------------------------------------------------

    def set_step(self, step: int | None) -> None:
        """Current training step, stamped into subsequently opened spans."""
        self._step = step

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    @property
    def open_depth(self) -> int:
        return len(self._stack)

    # -- span plumbing ------------------------------------------------------

    def begin(self, name: str, cat: str = CAT_PHASE, *,
              track: str | None = None, pod: str | None = None,
              step: int | None = None, tags: Mapping | None = None,
              modeled_s: float | None = None) -> Span:
        """Open a span nested under the current stack top."""
        sp = Span(id=self._next_id, name=name, cat=cat,
                  track=track if track is not None else cat,
                  t0_s=self._clock(), depth=len(self._stack),
                  parent=self._stack[-1].id if self._stack else None,
                  step=self._step if step is None else step, pod=pod,
                  tags={**self._extra, **dict(tags or {})},
                  modeled_s=modeled_s)
        self._next_id += 1
        self._stack.append(sp)
        return sp

    def end(self, sp: Span) -> Span:
        """Close ``sp`` (and, stack-safely, any span leaked open inside it)."""
        now = self._clock()
        while self._stack:
            top = self._stack.pop()
            if top.dur_s is None:
                top.dur_s = now - top.t0_s
            self._finish(top)
            if top is sp:
                break
        return sp

    def _finish(self, sp: Span) -> None:
        self.spans.append(sp)
        for sink in self.sinks:
            sink.on_span(sp)

    def record(self, name: str, cat: str, dur_s: float, *,
               track: str | None = None, pod: str | None = None,
               step: int | None = None, tags: Mapping | None = None,
               modeled_s: float | None = None) -> Span:
        """Record an already-measured region as a closed span (e.g. the
        train loop's own step timing): ``t0`` is back-dated by ``dur_s`` so
        the trace timeline stays consistent."""
        sp = Span(id=self._next_id, name=name, cat=cat,
                  track=track if track is not None else cat,
                  t0_s=self._clock() - dur_s, dur_s=dur_s,
                  depth=len(self._stack),
                  parent=self._stack[-1].id if self._stack else None,
                  step=self._step if step is None else step, pod=pod,
                  tags={**self._extra, **dict(tags or {})},
                  modeled_s=modeled_s)
        self._next_id += 1
        self._finish(sp)
        return sp

    @contextlib.contextmanager
    def span(self, name: str, cat: str = CAT_PHASE, **tags):
        """``with tracer.span("recover", phase="restore"): ...`` — the
        general nestable region."""
        sp = self.begin(name, cat, tags=tags)
        try:
            yield sp
        finally:
            self.end(sp)

    @contextlib.contextmanager
    def extra(self, **tags):
        """Merge ``tags`` into every span opened inside the context (how the
        probe runner marks its dispatches ``probe=True`` without threading
        arguments through the dispatch path)."""
        prev = self._extra
        self._extra = {**prev, **tags}
        try:
            yield
        finally:
            self._extra = prev

    # -- the collective span source (the hetccl._call hook) -----------------

    def price(self, op: str, nbytes: float, policy) -> float | None:
        """Simulator price of ``(op, nbytes, policy)`` on the bound cluster
        (None without one).  Memoized: dispatch repeats the same cells."""
        if self.cluster is None:
            return None
        key = (op, int(nbytes), policy)
        if key not in self._price_cache:
            from repro.core import simulator as sim
            mode = policy.mode
            if mode == "auto":      # unresolved facade row: price the default
                n_pods = len(getattr(self.cluster, "pods", ()) or ())
                mode = "hier" if n_pods > 1 else "flat"
            try:
                self._price_cache[key] = float(sim.collective_time(
                    op, float(nbytes), self.cluster, mode,
                    n_channels=max(int(policy.n_channels), 1),
                    backend=policy.backend,
                    n_stripes=max(int(policy.n_stripes), 1)
                    if policy.backend == "pallas" else 1,
                    wire_quant=getattr(policy, "wire_quant", None)))
            except Exception:
                self._price_cache[key] = None   # unpriceable op: span stays
        return self._price_cache[key]

    @contextlib.contextmanager
    def collective(self, op: str, nbytes: float, policy, *,
                   pod: str | None = None):
        """Record one eager dispatch as a span tagged with the full policy
        identity — the instrumented hook of ``hetccl._call``.  The span is
        finalized even when the dispatch raises (a watchdog breach is
        exactly when the evidence matters most); the error type lands in
        the tags."""
        if not self.enabled:
            yield None
            return
        cls = size_class(nbytes)
        sp = self.begin(op, CAT_COLLECTIVE, track=f"comm:{op}", pod=pod,
                        tags={"op": op, "size_class": cls,
                              "backend": policy.backend, "mode": policy.mode,
                              "n_channels": int(policy.n_channels),
                              "n_stripes": int(policy.n_stripes),
                              "wire_quant": getattr(policy, "wire_quant",
                                                    None),
                              "nbytes": int(nbytes),
                              "comm_epoch": self.comm_epoch},
                        modeled_s=self.price(op, nbytes, policy))
        try:
            yield sp
        except BaseException as e:
            sp.tags["error"] = type(e).__name__
            raise
        finally:
            self.end(sp)

    # -- views --------------------------------------------------------------

    def collective_spans(self) -> list[Span]:
        return [s for s in self.spans if s.cat == CAT_COLLECTIVE]

    def dispatched_cells(self) -> set[tuple[str, str, str]]:
        """Every ``(op, size_class, backend)`` cell an eager dispatch hit —
        the coverage set ``plan.measured.rows_from_flight`` must reproduce
        from a flight dump (the ISSUE-9 acceptance contract)."""
        return {(s.tags["op"], s.tags["size_class"], s.tags["backend"])
                for s in self.collective_spans() if "op" in s.tags}

    def dispatched_quant_cells(self) -> set[tuple[str, str, str, str | None]]:
        """``(op, size_class, backend, wire_quant)`` dispatch coverage —
        the finer cell the watchdog deadline table keys on once rows carry a
        codec (DESIGN.md §17); :meth:`dispatched_cells` keeps the legacy
        3-tuple shape for the flight-dump calibration consumers."""
        return {(s.tags["op"], s.tags["size_class"], s.tags["backend"],
                 s.tags.get("wire_quant"))
                for s in self.collective_spans() if "op" in s.tags}
