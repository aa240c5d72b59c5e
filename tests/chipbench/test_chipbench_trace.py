"""The reduction from a profiler trace to per-layer metrics, on hand-made
events and on a slice recorded from one chip run (phi4-mini-3.8b.longgen on
one TPU v5e: three decode steps, then the prefill chunks of an admission)."""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import flops, harness  # noqa: E402
from chipbench.harness import Counters  # noqa: E402
from chipbench.trace import Event, Trace, union  # noqa: E402

RECORDED = ROOT / "chipbench" / "testdata" / "phi4-mini-3.8b.longgen.trace.json.gz"
PEAKS = harness.load_peaks("TPU v5 lite")


def _brute_busy(trace, step=1000):
    """Busy nanoseconds by marking a 1 µs grid: an independent count."""
    n = (trace.end - trace.start) // step + 1
    grid = np.zeros(n, bool)
    for o in trace.ops:
        grid[(o.start - trace.start) // step:(o.end - trace.start + step - 1) // step] = True
    return grid.sum() * step


def test_union_and_gaps_on_hand_made_events():
    ops = [Event("%a", 10, 20), Event("%b", 15, 30), Event("%c", 40, 50)]
    t = Trace([Event("jit_p(1)", 10, 30), Event("jit_q(2)", 40, 50)], ops,
              [Event("chipbench.wait", 30, 40)], 0, 60)
    assert union([(10, 20), (15, 30), (40, 50)]) == [(10, 30), (40, 50)]
    assert t.busy_ns() == 30
    assert t.idle_gaps() == [(0, 10), (30, 40), (50, 60)]
    assert t.busy_within(0, 60) == 30 and t.busy_within(25, 45) == 10
    assert t.busy_within(31, 39) == 0
    assert t.until_next_program(t.programs("jit_p")) == 30  # 10 -> 40
    assert t.until_next_program(t.programs("jit_q")) == 20  # to the end
    assert t.host_at(35) == "wait" and t.host_at(5) == "host"
    # %a holds %b's start, so only %b and %c are leaves
    assert [o.name for o in t.leaf_ops()] == ["%b", "%c"]
    name, seconds = t.breakdown()["device_ops"][0]
    assert name == "jit_p:%b" and seconds == pytest.approx(15e-9)


def test_events_are_clipped_to_the_slice():
    t = Trace([Event("jit_p(1)", -5, 5)], [Event("%a", -5, 5)], [], 0, 10)
    assert t.modules[0].start == 0 and t.busy_ns() == 5


def test_programs_found_by_the_kernel_they_run():
    ops = [Event("%fusion", 10, 12), Event("%greedy_sample.1", 13, 14),
           Event("%copy.3", 14, 15), Event("%fusion", 20, 25)]
    t = Trace([Event("jit__unknown(7)", 10, 15), Event("jit_prefill_chunk(8)", 20, 25)],
              ops, [], 0, 30)
    assert [m.name for m in t.decode_programs()] == ["jit__unknown(7)"]
    assert [m.name for m in t.prefill_programs()] == ["jit_prefill_chunk(8)"]
    assert [o.name for o in t.sampling_ops()] == ["%greedy_sample.1"]


@pytest.fixture(scope="module")
def recorded():
    return Trace.from_json(str(RECORDED))


def test_recorded_trace_holds_what_the_readers_need(recorded):
    t = recorded
    decodes, prefills = t.decode_programs(), t.prefill_programs()
    assert len(decodes) == len(t.sampling_ops()) == 3
    assert len([s for s in t.spans if s.name == "chipbench.decode_launch"]) == 3
    assert len(prefills) == 3  # two whole chunks and the start of a third
    # one decode step of phi4-mini: ~24.5 ms on the chip
    assert all(24e6 < d.dur < 25e6 for d in decodes)
    assert t.busy_ns() == pytest.approx(_brute_busy(t), rel=1e-3)
    assert 0 < t.busy_ns() <= t.window_ns


def test_recorded_trace_round_trips(recorded, tmp_path):
    path = tmp_path / "t.json.gz"
    recorded.to_json(str(path))
    again = Trace.from_json(str(path))
    assert again.busy_ns() == recorded.busy_ns()
    assert again.modules == recorded.modules and again.spans == recorded.spans


def _run(trace):
    cfg = harness.load_json(harness.config_file("phi4-mini-3.8b"))
    mix = harness.load_json(harness.mix_file("longgen"))
    decodes = trace.decode_programs()
    # 8 live slots at contexts 300..307 in each of the three decode steps
    c = Counters(decode_launches=3,
                 decode_flops=3 * flops.decode_flops(cfg, range(300, 308)),
                 admitted_prompt_tokens=17, prefill_tokens=16,
                 prefill_flops=flops.prefill_flops(cfg, 17))
    cell = SimpleNamespace(config=cfg, mix=mix)
    return SimpleNamespace(trace_data=trace, counters=c, cell=cell), decodes


def test_readers_on_the_recorded_trace(recorded):
    run, decodes = _run(recorded)
    read = {m: harness.load_reader(m)(run, PEAKS) for m in (
        "decode_step_ms", "decode_gap_ms", "decode_mfu", "idle_share",
        "greedy_sample_roofline", "prefill_ms_per_token", "prefill_mfu",
        "admit_ms_per_token")}
    assert read["decode_step_ms"] == pytest.approx(
        np.mean([d.dur for d in decodes]) * 1e-6)
    gaps = [(b.start - a.end) for a, b in zip(decodes, decodes[1:])]
    # the small jit_dynamic_slice programs between steps are busy, not idle
    assert read["decode_gap_ms"] < np.mean(gaps) * 1e-6
    assert read["decode_gap_ms"] > 0.5 * np.mean(gaps) * 1e-6
    assert read["idle_share"] == pytest.approx(
        100 * (1 - _brute_busy(recorded) / recorded.window_ns), abs=0.1)
    # 8 x 200064 float32 logits read once at 819 GB/s, against the kernel
    calls = recorded.sampling_ops()
    least = (8 * 200064 * 4 + 8 * 4) / 819e9
    assert read["greedy_sample_roofline"] == pytest.approx(
        100 * least * 3 / (sum(o.dur for o in calls) * 1e-9))
    assert 0 < read["greedy_sample_roofline"] < 100
    assert 0 < read["decode_mfu"] < 100 and 0 < read["prefill_mfu"] < 100
    prefill_ns = sum(p.dur for p in recorded.prefill_programs())
    assert read["prefill_ms_per_token"] == pytest.approx(prefill_ns * 1e-6 / 16)
    admit_ns = sum(s.dur for s in recorded.spans if s.name == "chipbench.admit")
    assert read["admit_ms_per_token"] == pytest.approx(admit_ns * 1e-6 / 17)


def test_readers_return_nothing_where_nothing_was_traced():
    empty = Trace([], [], [], 0, 10)
    run = SimpleNamespace(trace_data=empty, counters=Counters(),
                          cell=SimpleNamespace(config={}, mix={"engine": {"slots": 8}}))
    for m in ("decode_step_ms", "decode_gap_ms", "decode_mfu", "idle_share",
              "greedy_sample_roofline", "prefill_ms_per_token", "prefill_mfu",
              "admit_ms_per_token"):
        assert harness.load_reader(m)(run, PEAKS) is None, m


def test_breakdown_lists_at_most_ten_of_each(recorded):
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(s, float) and s > 0 for _, s in b["device_ops"])
    names = [n for n, _ in b["device_ops"]]
    assert all(":" in n for n in names)
