"""The reduction of the program's own spans (``chipbench/spans.py``), on
hand-made spans and on the spans ``ServingEngine`` opens on the CPU; and
the ``decode_h2d_bytes`` reader of the engine's count of copies."""

import dataclasses
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402
from chipbench.spans import ProgramSpans, Span  # noqa: E402
from chipbench.trace import Event, Trace  # noqa: E402
from repro.configs import get  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402
from repro.serving.engine import H2DCount  # noqa: E402

PEAKS = harness.load_peaks("TPU v5 lite")


def _step(t0, prompt_tokens=5):
    """One decode step at ``t0``, 100 ns long: an admission whose prefill
    launch waits 15 ns on the ring, a decode launch, the sync, the
    retirement."""
    return [
        Span("serving.step", t0, t0 + 100, {"step_num": t0}),
        Span("serving.admit", t0 + 5, t0 + 30, {"prompt_tokens": prompt_tokens}),
        Span("serving.prefill_launch", t0 + 6, t0 + 28, {"h2d_bytes": 76}),
        Span("dispatch.ring_wait", t0 + 10, t0 + 25),
        Span("serving.decode_launch", t0 + 30, t0 + 50, {"h2d_bytes": 80, "live": 8}),
        Span("dispatch.config_cache", t0 + 31, t0 + 33),
        Span("serving.h2d", t0 + 33, t0 + 35),
        Span("serving.dispatch", t0 + 35, t0 + 45),
        Span("dispatch.ring_wait", t0 + 45, t0 + 47),
        Span("serving.sync", t0 + 50, t0 + 90, {"d2h_bytes": 32}),
        Span("serving.retire", t0 + 90, t0 + 98),
    ]


def test_self_time_on_hand_made_spans():
    ps = ProgramSpans(_step(0), 0, 1000)
    # 100 less the admission (25), the decode's ring wait (2), the sync (40)
    assert ps.decode_host_ms() == pytest.approx(33e-6)
    split = ps.split(ps.whole("serving.step")[0])
    assert split == {"serving.step": 7, "serving.admit": 25,
                     "serving.decode_launch": 4, "dispatch.config_cache": 2,
                     "serving.h2d": 2, "serving.dispatch": 10,
                     "dispatch.ring_wait": 2, "serving.sync": 40,
                     "serving.retire": 8}
    assert sum(split.values()) == 100
    assert sum(ps.decode_split_ms().values()) == pytest.approx(100e-6)
    # the admission's 25 ns less its 15 ns on the ring, over 5 tokens
    assert ps.admit_host_ms_per_token() == pytest.approx(2e-6)
    assert ps.decode_h2d_bytes() == 80
    assert ps.host_at(40) == "serving.dispatch" and ps.host_at(99) == "serving.step"
    assert ps.host_at(200) is None


def test_clipped_spans_are_skipped():
    # the slice cuts the second step: only the first counts, whole
    spans = _step(0) + [dataclasses.replace(s, args={**s.args, "h2d_bytes": 0})
                        if s.name == "serving.decode_launch" else s
                        for s in _step(200, prompt_tokens=50)]
    ps = ProgramSpans(spans, 0, 240)  # the second decode launch is cut
    assert len(ps.decode_steps()) == 1
    assert ps.decode_host_ms() == pytest.approx(33e-6)
    assert ps.decode_h2d_bytes() == 80
    # both admissions are whole: 10 ns of the host's own each, 55 tokens
    assert ps.admit_host_ms_per_token() == pytest.approx(20e-6 / 55)
    # the second admission is cut
    assert ProgramSpans(spans, 0, 220).admit_host_ms_per_token() == pytest.approx(2e-6)
    # clipped at the start too
    assert ProgramSpans(_step(0), 10, 1000).decode_host_ms() is None


def test_nothing_to_read_is_none():
    for ps in (ProgramSpans([], 0, 10),
               ProgramSpans([Span("serving.step", 0, 5)], 0, 10)):
        assert ps.decode_host_ms() is None and ps.decode_split_ms() is None
        assert ps.admit_host_ms_per_token() is None
        assert ps.decode_h2d_bytes() is None


def test_idle_gaps_inside_a_step_are_named_by_the_program():
    ops = [Event("%a", 0, 50), Event("%b", 92, 150)]
    trace = Trace([], ops, [Event("chipbench.step", 0, 100),
                            Event("chipbench.wait", 150, 300)], 0, 300)
    ps = ProgramSpans(_step(0), 0, 300)
    gaps = dict(ps.idle_gaps(trace))
    assert gaps["wait"] == pytest.approx(150e-9)  # outside any step
    assert gaps["serving.sync"] == pytest.approx(42e-9)  # 50..92, midpoint 71
    # a program that opens no span leaves the benchmark's names
    assert dict(ProgramSpans([], 0, 300).idle_gaps(trace)) == {
        "wait": pytest.approx(150e-9), "step": pytest.approx(42e-9)}


def test_round_trip_beside_the_trace(tmp_path):
    trace = Trace([Event("jit_decode_and_sample(1)", 30, 60)], [Event("%a", 30, 60)],
                  [Event("chipbench.step", 0, 100)], 0, 1000)
    ps = ProgramSpans(_step(0), 0, 1000)
    path = str(tmp_path / "slice.json.gz")
    ps.save(trace, path)
    again = ProgramSpans.load(path)
    assert again.spans == ps.spans
    assert [s.args for s in again.spans] == [s.args for s in ps.spans]
    assert Trace.from_json(path).modules == trace.modules


def test_decode_h2d_bytes_reads_the_engines_count():
    read = harness.load_reader("decode_h2d_bytes")
    engine = SimpleNamespace(h2d={"decode": H2DCount(launches=3, copies=12, bytes=240)})
    assert read(SimpleNamespace(engine=engine), PEAKS) == 80
    # an engine that keeps no count, or none at all: nothing to read
    assert read(SimpleNamespace(engine=SimpleNamespace()), PEAKS) is None
    assert read(SimpleNamespace(engine=None), PEAKS) is None
    assert read(SimpleNamespace(), PEAKS) is None


def test_the_engines_spans_on_the_cpu(tmp_path):
    """The names the reduction looks for are the names the engine opens."""
    cfg = dataclasses.replace(get("qwen2-0.5b").reduced(), remat="none")
    model = Model(cfg)
    engine = ServingEngine(model, model.init(jax.random.key(0)), max_slots=4,
                           max_len=32, prefill_chunk=4)
    for uid in range(5):
        engine.submit(Request(uid=uid, prompt=[3 + uid] * (6 + uid), max_new_tokens=4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run_until_done()
    finally:
        jax.profiler.stop_trace()
    ps = ProgramSpans.from_dir(str(tmp_path), 0, 2**63)
    assert len(ps.decode_steps()) == engine.steps
    assert ps.decode_h2d_bytes() == 4 * 10  # 4 slots: 2 int32 and 2 bool leaves
    assert engine.h2d["decode"].bytes == 40 * engine.h2d["decode"].launches
    assert ps.decode_host_ms() > 0 and ps.admit_host_ms_per_token() > 0
    assert sum(ps.decode_split_ms().values()) == pytest.approx(
        sum(s.dur for s in ps.decode_steps()) * 1e-6 / engine.steps)


RECORDED = ROOT / "chipbench" / "testdata" / "phi4-mini-3.8b.longgen.spans.trace.json.gz"


@pytest.fixture(scope="module")
def recorded():
    """A slice recorded from one chip run of phi4-mini-3.8b.longgen on one
    TPU v5e: three decode steps, then the start of an admission."""
    return Trace.from_json(str(RECORDED)), ProgramSpans.load(str(RECORDED))


def test_readings_on_the_recorded_slice(recorded):
    trace, ps = recorded
    assert len(ps.decode_steps()) == 3
    assert ps.decode_host_ms() == pytest.approx(2.444887, rel=1e-6)
    assert ps.decode_h2d_bytes() == 80
    # the admission runs past the slice's end: it is not read
    (admit,) = [s for s in ps.spans if s.name == "serving.admit"]
    assert admit.args["prompt_tokens"] == 134 and admit.end > ps.end
    assert ps.admit_host_ms_per_token() is None
    assert [s.args["h2d_bytes"] for s in ps.spans
            if s.name == "serving.prefill_launch"] == [76, 76, 76]
    split = ps.decode_split_ms()
    assert max(split, key=split.get) == "serving.sync"
    host = sum(v for k, v in split.items() if k not in ("serving.sync", "dispatch.ring_wait"))
    assert host == pytest.approx(ps.decode_host_ms())
    assert {m.name.split("(")[0] for m in trace.decode_programs()} == {"jit_decode_and_sample"}


def test_the_recorded_clocks_agree_up_to_one_offset(recorded):
    """The two planes of this recording disagree by a constant: the first
    prefill program reads 11.1 ms before its launch's ``serving.dispatch``
    begins, which no program can do. Shifted by that offset, each decode
    program starts within 0.3 ms of its launch's ``serving.dispatch`` and
    ends before the ``serving.sync`` that waits for it ends."""
    trace, ps = recorded

    def dispatches(kind):
        launches = [s for s in ps.spans if s.name == kind]
        return [d for d in ps.spans if d.name == "serving.dispatch"
                and any(a.start <= d.start and d.end <= a.end for a in launches)]

    skew = trace.prefill_programs()[0].start - dispatches("serving.prefill_launch")[0].start
    assert skew == pytest.approx(-11.096e6, abs=1e3)
    syncs = [s for s in ps.spans if s.name == "serving.sync"]
    checked = 0
    for prog in trace.decode_programs():
        if prog.start == trace.start:  # launched before the slice
            continue
        start = prog.start - skew
        d = min(dispatches("serving.decode_launch"), key=lambda d: abs(d.start - start))
        assert abs(start - d.start) < 0.3e6
        sync = min((s for s in syncs if s.start >= d.end), key=lambda s: s.start)
        assert prog.end - skew < sync.end
        checked += 1
    assert checked == 2
