"""Sequential vs concurrent launch executors — §2.2 on a real JAX runtime.

* :class:`SequentialExecutor` is the paper's *sequential configuration*
  timeline: prepare the step's configuration on the host, launch, then
  ``block_until_ready`` before preparing the next one. Host and device take
  turns; configuration time adds to the critical path.

* :class:`ConcurrentExecutor` is *concurrent configuration*: JAX's async
  dispatch queue plays the role of OpenGeMM's staging registers. Up to
  ``depth`` launches stay in flight while the host prepares the next
  configuration, hiding host time behind device time (§5.5 overlap).

* :class:`ScheduledExecutor` is the scheduler-backed path: concurrent
  staging *plus* a :class:`~repro.sched.state_cache.ConfigStateCache` in
  front of the launch descriptors, which counts the fields whose values
  changed since the previous launch apart from those that did not. The
  count models runtime deduplication; the launch itself still passes, and
  copies, every field.

All report a timeline breakdown so benchmarks can place the measurement on
the configuration roofline (host prep time ⇒ T_calc of Eq. 4).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass

import jax
import numpy as np
from jax.profiler import TraceAnnotation


@dataclass
class ExecReport:
    wall_s: float
    host_prep_s: float
    steps: int
    bytes_per_step: float
    bytes_elided_per_step: float = 0.0  # descriptor bytes the cache kept off the wire

    @property
    def steps_per_s(self) -> float:
        return self.steps / self.wall_s if self.wall_s else 0.0

    @property
    def elision_ratio(self) -> float:
        from repro.sched.state_cache import elision_ratio

        return elision_ratio(self.bytes_per_step, self.bytes_elided_per_step)


class SequentialExecutor:
    def __init__(self, device_fn, host_prep):
        self.device_fn = device_fn
        self.host_prep = host_prep

    def run(self, state, n_steps: int) -> tuple[object, ExecReport]:
        t0 = time.perf_counter()
        prep_s = 0.0
        nbytes = 0
        for step in range(n_steps):
            tp = time.perf_counter()
            args = self.host_prep(step)
            prep_s += time.perf_counter() - tp
            nbytes += sum(getattr(a, "nbytes", 0) for a in jax.tree.leaves(args))
            state = self.device_fn(state, args)
            jax.block_until_ready(state)  # sequential: host stalls per launch
        wall = time.perf_counter() - t0
        return state, ExecReport(wall, prep_s, n_steps, nbytes / max(n_steps, 1))


class ConcurrentExecutor:
    def __init__(self, device_fn, host_prep, depth: int = 2):
        self.device_fn = device_fn
        self.host_prep = host_prep
        self.depth = depth

    def run(self, state, n_steps: int) -> tuple[object, ExecReport]:
        t0 = time.perf_counter()
        prep_s = 0.0
        nbytes = 0
        inflight: deque = deque()
        for step in range(n_steps):
            tp = time.perf_counter()
            args = self.host_prep(step)  # overlaps the in-flight device work
            prep_s += time.perf_counter() - tp
            nbytes += sum(getattr(a, "nbytes", 0) for a in jax.tree.leaves(args))
            state = self.device_fn(state, args)  # async dispatch: returns early
            inflight.append(state)
            if len(inflight) > self.depth:  # bounded staging queue (§2.2)
                jax.block_until_ready(inflight.popleft())
        jax.block_until_ready(state)
        wall = time.perf_counter() - t0
        return state, ExecReport(wall, prep_s, n_steps, nbytes / max(n_steps, 1))


class _UnreadyLeaf:
    """Placeholder for a descriptor leaf still being computed on-device:
    carries its wire size but never compares equal, so accounting stays
    conservative (counted as sent) without ever forcing a sync."""

    __slots__ = ("nbytes",)

    def __init__(self, nbytes: int):
        self.nbytes = nbytes


def _host_view(v):
    """Host-side bit-stable view of a descriptor leaf. A device array that
    is not yet ready is left opaque — the cache comparison must never block
    the pipeline it is measuring."""
    if isinstance(v, np.ndarray) or np.isscalar(v):
        return v
    is_ready = getattr(v, "is_ready", None)
    if is_ready is not None and not is_ready():
        return _UnreadyLeaf(int(getattr(v, "nbytes", 0)))
    return np.asarray(v)


def _leaf_bytes(name, v) -> int:
    from repro.sched.state_cache import nbytes_of

    return v.nbytes if isinstance(v, _UnreadyLeaf) else nbytes_of(v)


class ScheduledExecutor:
    """Concurrent staging + an account of descriptor deduplication.

    Each launch descriptor (a pytree) flows through a
    :class:`~repro.sched.state_cache.ConfigStateCache`: fields bit-identical
    to the previous launch are counted as elided, as an unwritten
    configuration register would be (§3.2/§5.4 at the runtime layer). Only
    the count changes: ``device_fn`` still gets the full argument tree, and
    whatever it copies to the device crosses the boundary on every launch.
    What the report splits out is how many descriptor bytes would need to
    cross it if unchanged fields stayed resident.

    ``launch`` opens two ``jax.profiler`` spans: ``dispatch.config_cache``
    around the cache pass and ``dispatch.ring_wait`` around a wait for the
    oldest staged launch (``drain`` opens the latter too).

    Two entry points: the batch :meth:`run` loop (``host_prep`` builds each
    step's descriptor), and the incremental :meth:`launch` API that stateful
    callers — ``serving.ServingEngine``'s decode loop — drive one launch at
    a time while the executor keeps the staging ring and the traffic
    accounting. ``host_prep`` may be ``None`` for incremental use.
    """

    def __init__(self, device_fn, host_prep=None, depth: int = 2,
                 tenant: str = "exec", sync_fn=None):
        from repro.sched.state_cache import ConfigStateCache

        self.device_fn = device_fn
        self.host_prep = host_prep
        self.depth = depth
        self.tenant = tenant
        # what the staging ring blocks on: a sub-tree of device_fn's return
        # that is never donated to a later launch (callers whose device_fn
        # donates buffers — the serving engine's KV cache — pick the
        # per-launch output, e.g. the logits)
        self.sync_fn = sync_fn or (lambda out: out)
        self.cache = ConfigStateCache(max_contexts=1, bytes_of=_leaf_bytes)
        self._inflight: deque = deque()
        self._steps = 0
        self._prep_s = 0.0
        self._sent = 0
        self._elided = 0

    @property
    def launches(self) -> int:
        return self._steps

    def launch(self, state, args):
        """One staged launch: route ``args`` through the descriptor cache,
        dispatch asynchronously, and block only when the staging ring
        exceeds ``depth`` — returns whatever ``device_fn`` returned, still
        in flight.

        No-aliasing contract: numpy leaves of ``args`` are cached by
        reference, so callers must not mutate a leaf in place between
        launches (pass a fresh array or a copy, as the serving engine's
        descriptors do) — otherwise the changed field compares equal to
        itself and is misreported as elided."""
        tp = time.perf_counter()
        # the cache comparison is host descriptor work: count it as prep
        # (T_calc), and compare host-side views so accounting never forces
        # a device sync mid-pipeline
        with TraceAnnotation("dispatch.config_cache"):
            leaves, _ = jax.tree_util.tree_flatten_with_path(args)
            plan = self.cache.dispatch(
                self.tenant,
                {jax.tree_util.keystr(k): _host_view(v) for k, v in leaves},
            )
        self._prep_s += time.perf_counter() - tp
        self._sent += plan.bytes_sent
        self._elided += plan.bytes_elided
        state = self.device_fn(state, args)  # async dispatch: returns early
        self._inflight.append(self.sync_fn(state))
        if len(self._inflight) > self.depth:
            with TraceAnnotation("dispatch.ring_wait"):
                jax.block_until_ready(self._inflight.popleft())
        self._steps += 1
        return state

    def drain(self) -> None:
        """Retire every staged launch (end-of-run / engine idle barrier)."""
        if not self._inflight:
            return
        with TraceAnnotation("dispatch.ring_wait"):
            while self._inflight:
                jax.block_until_ready(self._inflight.popleft())

    def report(self, wall_s: float) -> ExecReport:
        """Cumulative traffic split over every launch so far."""
        n = max(self._steps, 1)
        return ExecReport(wall_s, self._prep_s, self._steps,
                          self._sent / n, self._elided / n)

    def run(self, state, n_steps: int) -> tuple[object, ExecReport]:
        t0 = time.perf_counter()
        steps0, sent0, elided0, prep0 = (self._steps, self._sent,
                                         self._elided, self._prep_s)
        for step in range(n_steps):
            tp = time.perf_counter()
            args = self.host_prep(step)
            self._prep_s += time.perf_counter() - tp
            state = self.launch(state, args)
        jax.block_until_ready(state)
        wall = time.perf_counter() - t0
        n = max(n_steps, 1)
        return state, ExecReport(
            wall, self._prep_s - prep0, self._steps - steps0,
            (self._sent - sent0) / n, (self._elided - elided0) / n,
        )
