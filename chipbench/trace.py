"""Profiler trace of a slice of the window, reduced to events.

The JAX profiler writes an ``.xplane.pb``; ``from_xplane`` keeps what the
metric readers need, as plain events on one nanosecond clock:

* ``modules``: the device's compiled programs (the TPU plane's
  ``XLA Modules`` line), named by the jitted function;
* ``ops``: the device's operations (its ``XLA Ops`` line), named by their
  HLO instruction (``%greedy_sample.1``);
* ``spans``: the host spans ``chipbench`` placed (``chipbench.*``).

``Trace`` then answers the questions the readers ask: busy time as the
union of operation intervals, idle gaps, and what the host was doing in
each gap. A ``Trace`` saves to and loads from JSON, which is how the
recorded sample under ``testdata/`` is kept.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
from collections import defaultdict
from dataclasses import dataclass

SPAN_PREFIX = "chipbench."
SLICE_SPAN = "chipbench.slice"
SAMPLING_OP = "%greedy_sample"  # the Pallas greedy-sampling kernel's op
PREFILL_PROGRAM = "prefill_chunk"  # jitted Model.prefill_chunk


@dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    end: int  # ns

    @property
    def dur(self) -> int:
        return self.end - self.start


def _short(name: str) -> str:
    """An XLA operation's instruction name: the device line names each op
    by its whole HLO text, ``%name = type op(operands)``."""
    return name.split(" = ", 1)[0]


def _events(line, short: bool = False) -> list[Event]:
    return [Event(_short(e.name) if short else e.name, int(e.start_ns),
                  int(e.start_ns + e.duration_ns))
            for e in line.events]


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Trace:
    def __init__(self, modules: list[Event], ops: list[Event],
                 spans: list[Event], start: int, end: int):
        self.start, self.end = start, end

        def clip(evs):
            return sorted((Event(e.name, max(e.start, start), min(e.end, end))
                           for e in evs if e.end > start and e.start < end),
                          key=lambda e: (e.start, e.end))

        self.modules, self.ops, self.spans = clip(modules), clip(ops), clip(spans)
        self._busy = union((e.start, e.end) for e in self.ops)
        # prefix sums of busy time, for busy_within
        self._busy_starts = [s for s, _ in self._busy]
        self._busy_cum = [0]
        for s, e in self._busy:
            self._busy_cum.append(self._busy_cum[-1] + e - s)

    # ------------------------------------------------------------ reading

    @classmethod
    def from_xplane(cls, path: str, device_plane: str = "/device:TPU:0") -> "Trace":
        """Read one ``.xplane.pb``. The slice is the ``chipbench.slice``
        span; the device plane is the first chip's."""
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        modules, ops, spans = [], [], []
        found = False
        for plane in data.planes:
            if plane.name == device_plane:
                found = True
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        modules = _events(line)
                    elif line.name == "XLA Ops":
                        ops = _events(line, short=True)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [e for e in _events(line)
                              if e.name.startswith(SPAN_PREFIX)]
        if not found:
            raise ValueError(f"no plane {device_plane!r} in {path}")
        bounds = [e for e in spans if e.name == SLICE_SPAN]
        if len(bounds) != 1:
            raise ValueError(f"{len(bounds)} {SLICE_SPAN} spans in {path}")
        return cls(modules, ops, spans, bounds[0].start, bounds[0].end)

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_xplane(paths[-1])

    def to_json(self, path: str) -> None:
        def enc(evs):
            return [[e.name, e.start, e.end] for e in evs]

        doc = {"start": self.start, "end": self.end, "modules": enc(self.modules),
               "ops": enc(self.ops), "spans": enc(self.spans)}
        with gzip.open(path, "wt") as f:
            json.dump(doc, f)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            doc = json.load(f)

        def dec(rows):
            return [Event(n, int(s), int(e)) for n, s, e in rows]

        return cls(dec(doc["modules"]), dec(doc["ops"]), dec(doc["spans"]),
                   int(doc["start"]), int(doc["end"]))

    # ---------------------------------------------------------- questions

    @property
    def window_ns(self) -> int:
        return self.end - self.start

    def busy(self) -> list[tuple[int, int]]:
        """Intervals in which some operation ran on the device."""
        return self._busy

    def busy_ns(self) -> int:
        return self._busy_cum[-1]

    def idle_gaps(self) -> list[tuple[int, int]]:
        """Intervals of the slice in which no operation ran."""
        gaps, t = [], self.start
        for s, e in self.busy():
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.end:
            gaps.append((t, self.end))
        return gaps

    def programs(self, name_part: str) -> list[Event]:
        """Device program runs whose name contains ``name_part``."""
        return [e for e in self.modules if name_part in e.name]

    def programs_running(self, op_prefix: str) -> list[Event]:
        """Device program runs inside which an operation whose name starts
        with ``op_prefix`` ran: a program found by what it computes, where
        its own name says nothing (``jit__unknown`` for a jitted
        ``functools.partial``)."""
        starts = [o.start for o in self.ops if o.name.startswith(op_prefix)]
        out = []
        for m in self.modules:
            i = bisect.bisect_left(starts, m.start)
            if i < len(starts) and starts[i] < m.end:
                out.append(m)
        return out

    def decode_programs(self) -> list[Event]:
        """The engine's decode-and-sample runs: the programs that run the
        sampling kernel."""
        return self.programs_running(SAMPLING_OP)

    def prefill_programs(self) -> list[Event]:
        return self.programs(PREFILL_PROGRAM)

    def sampling_ops(self) -> list[Event]:
        return [o for o in self.ops if o.name.startswith(SAMPLING_OP)]

    def busy_within(self, start: int, end: int) -> int:
        """Device-busy nanoseconds inside ``[start, end)``."""
        if end <= start:
            return 0

        def upto(t):  # busy time before t
            i = bisect.bisect_right(self._busy_starts, t)
            if i == 0:
                return 0
            s, e = self._busy[i - 1]
            return self._busy_cum[i - 1] + min(e, t) - s

        return upto(end) - upto(start)

    def span_time(self, name: str) -> int:
        return sum(e.dur for e in self.spans if e.name == name)

    def host_at(self, t: int) -> str:
        """The innermost ``chipbench`` span open at ``t`` (the slice span
        aside), or ``"host"`` where none is."""
        inner = None
        for e in self.spans:
            if e.name != SLICE_SPAN and e.start <= t < e.end:
                if inner is None or e.dur < inner.dur:
                    inner = e
        return inner.name[len(SPAN_PREFIX):] if inner else "host"

    def until_next_program(self, runs: list[Event]) -> int:
        """Sum over ``runs`` of the time from each run's start to the start
        of the next program on the device (or the slice's end): the run
        with the idle gap it leaves behind."""
        starts = sorted(e.start for e in self.modules)
        total = 0
        for r in runs:
            i = bisect.bisect_right(starts, r.start)
            total += (starts[i] if i < len(starts) else self.end) - r.start
        return total

    def leaf_ops(self) -> list[Event]:
        """Operations that hold no other: a loop's body ops run inside the
        loop op's interval on the same line, and only they are counted."""
        return [o for o, nxt in zip(self.ops, self.ops[1:] + [None])
                if nxt is None or nxt.start >= o.end]

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, named
        ``program:op``, and the longest idle gaps by the host span open in
        each, in seconds."""
        starts = [m.start for m in self.modules]
        by_op: dict[str, int] = defaultdict(int)
        for e in self.leaf_ops():
            i = bisect.bisect_right(starts, e.start) - 1
            inside = i >= 0 and e.start < self.modules[i].end
            program = self.modules[i].name.split("(")[0] if inside else "?"
            by_op[f"{program}:{e.name}"] += e.dur
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[n, ns * 1e-9] for n, ns in ops],
            "idle_gaps": [[self.host_at((s + e) // 2), (e - s) * 1e-9]
                          for s, e in gaps],
        }
