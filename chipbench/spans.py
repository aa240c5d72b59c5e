"""The program's own spans in a traced slice, and what they say.

``ServingEngine`` and its executor open ``jax.profiler`` spans named
``serving.*`` and ``dispatch.*`` (``src/repro/serving/engine.py``), with
their arguments as span metadata. ``chipbench/trace.py`` keeps only the
benchmark's ``chipbench.*`` spans; this module reads the program's from the
same ``.xplane.pb``, on the same clock, and reduces them:

* ``decode_host_ms``: over the ``serving.step`` spans that hold a
  ``serving.decode_launch``, the mean of the step's duration less the time
  its ``serving.sync``, ``dispatch.ring_wait`` and ``serving.admit`` spans
  cover: the host's own time per decode launch;
* ``admit_host_ms_per_token``: the ``serving.admit`` spans' time less the
  ``dispatch.ring_wait`` inside them, over the prompt tokens they admitted;
* ``decode_h2d_bytes``: the mean ``h2d_bytes`` of ``serving.decode_launch``;
* ``decode_split_ms``: a decode step's time by span, each span's own time
  with its children's taken out, and the waits whole;
* ``idle_gaps``: the device's longest idle gaps, those inside a
  ``chipbench.step`` named by the innermost program span open at their
  midpoint.

Only whole spans count: a span clipped at either edge of the slice is
skipped. Each reading is ``None`` where the slice holds nothing to read, as
it does for a program that opens no such span.

    python chipbench/spans.py --workload <name> --seed <n> --seconds <s> [--out <file.json.gz>]

runs one cell as ``chipbench/run.py --trace 1`` does, and prints its result
line with these readings added under ``program_spans``. ``--out`` saves the
slice, the device's events and the program spans, in the format of
``Trace.to_json`` with a ``program_spans`` list beside them.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from the process's start

import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

PREFIXES = ("serving.", "dispatch.")
STEP, ADMIT, SYNC, RING_WAIT = ("serving.step", "serving.admit", "serving.sync",
                                "dispatch.ring_wait")
DECODE_LAUNCH = "serving.decode_launch"
WAITS = (SYNC, RING_WAIT, ADMIT)  # a decode step's time that is not the host's own


@dataclass(frozen=True)
class Span:
    name: str
    start: int  # ns
    end: int  # ns
    args: dict = field(default_factory=dict, compare=False)

    @property
    def dur(self) -> int:
        return self.end - self.start


def _covered(spans) -> int:
    """Nanoseconds that the union of ``spans`` covers."""
    total, reach = 0, None
    for s in sorted(spans, key=lambda s: s.start):
        if reach is None or s.start >= reach:
            total += s.dur
            reach = s.end
        elif s.end > reach:
            total += s.end - reach
            reach = s.end
    return total


class ProgramSpans:
    """The program spans of one traced slice ``[start, end)``."""

    def __init__(self, spans: list[Span], start: int, end: int):
        self.start, self.end = start, end
        self.spans = sorted((s for s in spans if s.end > start and s.start < end),
                            key=lambda s: (s.start, -s.end))

    # ------------------------------------------------------------ reading

    @classmethod
    def from_xplane(cls, path: str, start: int, end: int) -> "ProgramSpans":
        from jax.profiler import ProfileData

        spans = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                spans += [Span(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns),
                               {k: v for k, v in e.stats if k != "_r"})
                          for e in line.events if e.name.startswith(PREFIXES)]
        return cls(spans, start, end)

    @classmethod
    def from_dir(cls, log_dir: str, start: int, end: int) -> "ProgramSpans":
        paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_xplane(paths[-1], start, end)

    def save(self, trace, path: str) -> None:
        """Write ``trace`` (a ``chipbench.trace.Trace`` of the same slice)
        with these spans beside it; ``Trace.from_json`` reads it as it
        reads its own files."""
        trace.to_json(path)
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        doc["program_spans"] = [[s.name, s.start, s.end, s.args] for s in self.spans]
        with gzip.open(path, "wt") as f:
            json.dump(doc, f)

    @classmethod
    def load(cls, path: str) -> "ProgramSpans":
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        return cls([Span(n, int(s), int(e), a)
                    for n, s, e, a in doc.get("program_spans", [])],
                   int(doc["start"]), int(doc["end"]))

    # ---------------------------------------------------------- questions

    def whole(self, name: str) -> list[Span]:
        """Spans of ``name`` that lie wholly inside the slice."""
        return [s for s in self.spans
                if s.name == name and s.start >= self.start and s.end <= self.end]

    def inside(self, outer: Span) -> list[Span]:
        """The spans that ``outer`` holds, in order of their start."""
        return [s for s in self.spans if s is not outer
                and outer.start <= s.start and s.end <= outer.end]

    def decode_steps(self) -> list[Span]:
        return [s for s in self.whole(STEP)
                if any(c.name == DECODE_LAUNCH for c in self.inside(s))]

    def split(self, outer: Span) -> dict[str, int]:
        """Nanoseconds of ``outer`` by span name: each span's own time (its
        duration less its children's), except that a wait (``WAITS``)
        counts whole, with what it holds, under its own name."""
        out: dict[str, int] = {}
        stack: list[list] = [[outer, 0]]  # [span, children's time]

        def close(upto: int):
            while len(stack) > 1 and stack[-1][0].end <= upto:
                span, kids = stack.pop()
                out[span.name] = out.get(span.name, 0) + span.dur - kids

        skip_to = None
        for s in self.inside(outer):
            if skip_to is not None and s.start < skip_to:
                continue  # held by a wait that already counts whole
            close(s.start)
            stack[-1][1] += s.dur
            if s.name in WAITS:
                out[s.name] = out.get(s.name, 0) + s.dur
                skip_to = s.end
            else:
                stack.append([s, 0])
        close(outer.end)
        out[outer.name] = out.get(outer.name, 0) + outer.dur - stack[0][1]
        return out

    def decode_host_ms(self) -> float | None:
        steps = self.decode_steps()
        if not steps:
            return None
        host = [s.dur - _covered(c for c in self.inside(s) if c.name in WAITS)
                for s in steps]
        return sum(host) / len(host) * 1e-6

    def decode_split_ms(self) -> dict[str, float] | None:
        steps = self.decode_steps()
        if not steps:
            return None
        total: dict[str, int] = {}
        for s in steps:
            for name, ns in self.split(s).items():
                total[name] = total.get(name, 0) + ns
        return {n: ns / len(steps) * 1e-6
                for n, ns in sorted(total.items(), key=lambda kv: -kv[1])}

    def admit_host_ms_per_token(self) -> float | None:
        admits = self.whole(ADMIT)
        tokens = sum(a.args.get("prompt_tokens", 0) for a in admits)
        if not tokens:
            return None
        ns = sum(a.dur - _covered(c for c in self.inside(a) if c.name == RING_WAIT)
                 for a in admits)
        return ns * 1e-6 / tokens

    def decode_h2d_bytes(self) -> float | None:
        launches = [s for s in self.whole(DECODE_LAUNCH) if "h2d_bytes" in s.args]
        if not launches:
            return None
        return sum(s.args["h2d_bytes"] for s in launches) / len(launches)

    def host_at(self, t: int) -> str | None:
        """The innermost program span open at ``t``, or ``None``."""
        inner = None
        for s in self.spans:
            if s.start > t:
                break
            if t < s.end and (inner is None or s.dur < inner.dur):
                inner = s
        return inner.name if inner else None

    def idle_gaps(self, trace, top: int = 10) -> list[list]:
        """``trace.breakdown()``'s idle gaps, with a gap inside a
        ``chipbench.step`` named by the program span open at its
        midpoint where there is one."""
        steps = [(e.start, e.end) for e in trace.spans if e.name == "chipbench.step"]
        out = []
        for s, e in sorted(trace.idle_gaps(), key=lambda g: g[0] - g[1])[:top]:
            mid = (s + e) // 2
            name = trace.host_at(mid)
            if any(a <= mid < b for a, b in steps):
                name = self.host_at(mid) or name
            out.append([name, (e - s) * 1e-9])
        return out

    def summary(self, trace) -> dict:
        return {"decode_host_ms": self.decode_host_ms(),
                "admit_host_ms_per_token": self.admit_host_ms_per_token(),
                "decode_h2d_bytes": self.decode_h2d_bytes(),
                "decode_steps": len(self.decode_steps()),
                "decode_split_ms": self.decode_split_ms(),
                "idle_gaps": self.idle_gaps(trace)}


def main(argv=None) -> int:
    import argparse

    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import harness
    from chipbench.trace import Trace

    cell = harness.load_cell(args.workload)
    harness.set_compile_cache()
    kept = {}
    trace_from_dir = Trace.from_dir

    def from_dir(log_dir):
        # the harness deletes the profile once it has reduced it: read the
        # program spans from it first
        trace = kept["trace"] = trace_from_dir(log_dir)
        kept["spans"] = ProgramSpans.from_dir(log_dir, trace.start, trace.end)
        return trace

    Trace.from_dir = from_dir
    try:
        result = harness.execute(cell, args.seed, args.seconds, True, T_START)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    finally:
        Trace.from_dir = trace_from_dir
    result["program_spans"] = kept["spans"].summary(kept["trace"])
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        kept["spans"].save(kept["trace"], args.out)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
