"""The check that decides ``correct``, driven through a whole run on the
CPU at ``.reduced()`` widths with the look for a chip skipped: a sound run
passes, and each fault a serving cell can have (``chipbench.faults``),
planted in the timed path after warm-up, comes out not correct. So does the control: the float32
reference computed in fp8, read at the served positions."""

import gc
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from _chipbench_small import small_cell  # noqa: E402
from chipbench import check, harness  # noqa: E402
from chipbench.faults import FAULTS  # noqa: E402
from chipbench.traffic import Traffic  # noqa: E402
from repro.serving import Request  # noqa: E402

SECONDS = 3.0
WIDE_SECONDS = 8.0  # long enough for most of 8 slots to finish a request
SEED = 2**31 + 99
# The control's limit at these widths (2 layers of 64), set like the
# cells' limits from readings on 8 seeds of 12 requests (408 served tokens):
# the program read 0 to 0.016, the fp8 control 0.072 to 0.243.
SMALL_LIMIT = 0.04


def _execute(hook=None, mix="longgen"):
    cell, mc = small_cell("qwen2-0.5b", mix)
    return harness.execute(cell, SEED, SECONDS, False, time.perf_counter(),
                           require_chip=False, model_cfg=mc, engine_hook=hook)


def _limit():
    return harness.load_json(harness.config_file("qwen2-0.5b"))["correct"]["max_logit_gap"]


def test_sound_run_is_correct_and_prints_its_numbers():
    res = _execute()
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"  # the numbers compared come last
    assert res["checks"]["max_logit_gap"]["limit"] == _limit()
    assert res["checks"]["window_compiles"]["value"] == 0
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) >= {"output_tok_s", "ttft_p50_ms", "setup_s"}
    # a quantity split by cell reads the same under each of its names
    m = res["metrics"]
    assert m["ttft_p50_ms.open_loop"]["value"] == m["ttft_p50_ms"]["value"]


def test_the_window_runs_with_the_set_up_kept_out_of_the_collector(monkeypatch):
    """Set-up's objects are frozen out of the collector for the window
    alone: a full collection there scans only what the window made."""
    frozen = []
    window = harness.Run.window

    def watched(run):
        frozen.append(gc.get_freeze_count())
        window(run)

    monkeypatch.setattr(harness.Run, "window", watched)
    assert _execute()["correct"] is True
    assert frozen[0] > 10_000
    assert gc.get_freeze_count() < 1_000  # unfrozen once the window closed


def test_prefill_chunks_first_tokens_are_kept_for_the_check():
    """Every finished request carries the first token of the last position
    of each of its prefill chunks, as the chunk's program returned it."""
    cell, mc = small_cell("qwen2-0.5b", "longgen")
    run = harness.Run(cell, SEED, SECONDS, False, model_cfg=mc)
    run.setup()
    run.window()
    done = run.finished()
    assert done
    chunk = cell.config["engine"]["prefill_chunk"]
    for req in done:
        ends = [min(start + chunk, len(req.prompt) - 1) - 1
                for start in range(0, len(req.prompt) - 1, chunk)]
        assert [pos for pos, _ in req.prefilled] == ends
        assert all(0 <= tok < mc.vocab_size for _, tok in req.prefilled)
    seq, rows, ids = check.positions(done[0])
    assert len(rows) == len(ids) == len(done[0].prefilled) + len(done[0].served)
    assert rows.max() < len(seq)


def _fails_by_its_tokens(res):
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > _limit()
    # the fault compiles what it adds before the window: it fails by its
    # tokens, not by a compile
    assert res["checks"]["window_compiles"]["value"] == 0


def test_token_altered_where_it_is_produced():
    _fails_by_its_tokens(_execute(FAULTS["altered_token"]))


def test_half_the_slots_left_out():
    """The decode step's tokens are kept for the lower half of the slots;
    the upper half gets its input token back instead of a new one."""
    _fails_by_its_tokens(_execute(FAULTS["half_slots"]))


def test_prefill_that_leaves_the_cache_unchanged():
    _fails_by_its_tokens(_execute(FAULTS["stale_prefill"]))


def test_half_the_slots_left_out_when_one_request_meets_the_token_floor(monkeypatch):
    """At the longgen cell's own slot count, with a token floor that the
    longest request meets alone (as phi4-mini's answers of ~400 tokens
    meet 384 on the chip): the sample still reads a request from every
    slot that finished one, so the broken upper half is read."""
    monkeypatch.setattr(check, "SAMPLE_TOKENS", 1)
    cell, mc = small_cell("qwen2-0.5b", "longgen", slots=8)
    sound = harness.Run(cell, SEED, WIDE_SECONDS, False, model_cfg=mc)
    sound.setup()
    sound.window()
    done = sound.finished()
    picked = check.sample(done, SEED)
    assert {r.slot for r in picked} == {r.slot for r in done}
    assert len({r.slot for r in done}) > 4  # more than half the batch
    _fails_by_its_tokens(harness.execute(
        cell, SEED, WIDE_SECONDS, False, time.perf_counter(), require_chip=False,
        model_cfg=mc, engine_hook=FAULTS["half_slots"]))


def _served(slot, n, prompt=4):
    return check.Served(list(range(prompt)), list(range(n)), slot=slot)


def test_sample_reads_the_longest_and_one_request_of_every_slot():
    done = [_served(s % 8, 10 + s) for s in range(24)] + [_served(3, 500)]
    for seed in (1, SEED):
        picked = check.sample(done, seed)
        assert picked[0].served == list(range(500))
        assert {r.slot for r in picked} == set(range(8))
        assert len(picked) == 8  # the longest alone meets the token floor
        assert check.sample(done, seed) == picked  # the seed fixes the draw


def test_sample_tops_up_to_the_token_floor():
    done = [_served(s % 2, 40) for s in range(30)]
    picked = check.sample(done, SEED, want=200)
    assert sum(len(r.served) for r in picked) >= 200
    assert len(picked) == 5 and {r.slot for r in picked} == {0, 1}
    assert len(check.sample(done, SEED, want=10_000)) == len(done)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_is_not_correct(seed):
    """The control in the program's place: the sequences the engine served
    for a fixed set of requests, read by the fp8 reference."""
    cell, mc = small_cell("qwen2-0.5b", "longgen")
    run = harness.Run(cell, seed, SECONDS, False, model_cfg=mc)
    run.setup()
    traffic = Traffic(cell.mix, seed, SECONDS, mc.vocab_size)
    reqs = [Request(uid=d.index, prompt=d.prompt, max_new_tokens=d.max_new_tokens)
            for d in (traffic.next_draw() for _ in range(12))]
    for r in reqs:
        run.engine.submit(r)
    run.engine.run_until_done()
    run.release_program()
    served = [check.Served(r.prompt, r.generated) for r in reqs]
    reference = harness.load_reference("dense")
    found = check.widest_gap(reference(cell.config, run.params), served,
                             reference(cell.config, run.params, precision="fp8"))
    assert found["served_tokens"] == sum(r.max_new_tokens for r in reqs)
    assert found["max_logit_gap"] <= SMALL_LIMIT < found["control_max_logit_gap"]
    # through the decision a benchmark run makes, as control.py takes it
    n = found["served_tokens"]
    assert harness.decide(found["max_logit_gap"], SMALL_LIMIT, 0, n)[0] is True
    assert harness.decide(found["control_max_logit_gap"], SMALL_LIMIT, 0, n)[0] is False
