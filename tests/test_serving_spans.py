"""The engine's profiler spans: ``ServingEngine`` run under
``jax.profiler`` at reduced width, its host plane read back with
``ProfileData``."""

import dataclasses
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import get
from repro.models.model import Model
from repro.serving import Request, ServingEngine

PROMPTS = {11: [5, 9, 2, 7, 1], 12: [3, 3, 4, 4, 6, 6, 8], 13: [8, 1], 14: [2, 4, 6]}
NEW_TOKENS = {11: 5, 12: 3, 13: 4, 14: 2}
SLOTS, CHUNK = 2, 4

# span -> the spans it may open inside (its children)
TREE = {
    "serving.step": {"serving.admit", "serving.decode_launch", "serving.sync",
                     "serving.retire"},
    "serving.admit": {"serving.prefill_launch"},
    "serving.prefill_launch": {"dispatch.config_cache", "serving.h2d",
                               "serving.dispatch", "dispatch.ring_wait"},
    "serving.decode_launch": {"dispatch.config_cache", "serving.h2d",
                              "serving.dispatch", "dispatch.ring_wait"},
}


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int
    args: dict
    parent: "Span | None" = None


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(get("qwen2-0.5b").reduced(), remat="none")
    model = Model(cfg)
    return model, model.init(jax.random.key(0))


def _engine(small_model):
    model, params = small_model
    return ServingEngine(model, params, max_slots=SLOTS, max_len=32,
                         prefill_chunk=CHUNK)


def _submit_all(engine):
    for uid, prompt in PROMPTS.items():
        engine.submit(Request(uid=uid, prompt=prompt,
                              max_new_tokens=NEW_TOKENS[uid]))


def _program_spans(log_dir) -> list[Span]:
    """The ``serving.*`` and ``dispatch.*`` events of the host plane, each
    with its innermost enclosing span as parent."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [Span(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                        dict(e.stats)) for e in line.events
                   if e.name.startswith(("serving.", "dispatch."))]
            evs.sort(key=lambda s: (s.start, -s.end))
            open_ = []
            for s in evs:
                while open_ and open_[-1].end <= s.start:
                    open_.pop()
                s.parent = open_[-1] if open_ else None
                open_.append(s)
            spans += evs
    return spans


@pytest.fixture(scope="module")
def traced(small_model, tmp_path_factory):
    """A run of four requests through two slots, traced; what each jitted
    step was passed, recorded beside it."""
    engine = _engine(small_model)
    passed = []  # (kind, bytes of the arrays copied for the launch)
    prefill, decode = engine._prefill, engine._decode

    def prefill_w(params, cache, *args):
        passed.append(("prefill", sum(a.nbytes for a in args)))
        return prefill(params, cache, *args)

    def decode_w(params, cache, dev_tokens, *args):
        passed.append(("decode", sum(a.nbytes for a in args)))
        return decode(params, cache, dev_tokens, *args)

    engine._prefill, engine._decode = prefill_w, decode_w
    _submit_all(engine)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        done = engine.run_until_done()
    finally:
        jax.profiler.stop_trace()
    return engine, done, passed, _program_spans(log_dir)


def test_span_names_and_nesting(traced):
    engine, _, _, spans = traced
    names = {s.name for s in spans}
    assert names == set(TREE) | set().union(*TREE.values())
    for s in spans:
        if s.parent is None:
            # the engine's own drain at the end of run_until_done
            assert s.name in ("serving.step", "dispatch.ring_wait"), s.name
        else:
            assert s.name in TREE[s.parent.name], (s.parent.name, s.name)
            assert s.parent.start <= s.start and s.end <= s.parent.end
    steps = [s for s in spans if s.name == "serving.step"]
    assert [s.args["step_num"] for s in steps] == list(range(engine.steps))
    # every step that decodes holds one launch, one sync and one retire
    for st in steps:
        kids = [s.name for s in spans if s.parent is st]
        if "serving.decode_launch" in kids:
            assert sorted(k for k in kids if k != "serving.admit") == [
                "serving.decode_launch", "serving.retire", "serving.sync"]
            sync = next(s for s in spans if s.parent is st and s.name == "serving.sync")
            assert sync.args["d2h_bytes"] == SLOTS * 4


def test_one_admit_per_request(traced):
    _, _, _, spans = traced
    admits = [s for s in spans if s.name == "serving.admit"]
    assert sorted(s.args["uid"] for s in admits) == sorted(PROMPTS)
    assert sum(s.args["prompt_tokens"] for s in admits) == sum(
        len(p) for p in PROMPTS.values())
    for s in admits:
        assert 0 <= s.args["slot"] < SLOTS and s.args["queued_us"] >= 0
        chunks = [c for c in spans if c.parent is s]
        assert len(chunks) == -(-(len(PROMPTS[s.args["uid"]]) - 1) // CHUNK)


def test_prefill_launch_says_its_path_and_valid_tokens(traced):
    engine, _, _, spans = traced
    for admit in (s for s in spans if s.name == "serving.admit"):
        chunks = [c for c in spans if c.parent is admit]
        assert {c.args["path"] for c in chunks} == {engine.model.prefill_path} == {"chunk"}
        valid = [c.args["valid_tokens"] for c in chunks]
        assert sum(valid) == admit.args["prompt_tokens"] - 1
        assert all(0 < n <= CHUNK for n in valid) and all(n == CHUNK for n in valid[:-1])


def test_h2d_bytes_are_the_leaves_passed(traced):
    engine, _, passed, spans = traced
    launches = sorted((s for s in spans if s.name.endswith("_launch")),
                      key=lambda s: s.start)
    assert [(s.name.split(".")[1].split("_")[0], s.args["h2d_bytes"])
            for s in launches] == passed
    assert all(s.args["h2d_copies"] == 4 for s in launches)
    # fused decode copies overrides and positions (int32), two masks (bool)
    assert {b for k, b in passed if k == "decode"} == {SLOTS * (4 + 1 + 4 + 1)}
    for kind in ("prefill", "decode"):
        c = engine.h2d[kind]
        assert c.launches == sum(k == kind for k, _ in passed)
        assert c.bytes == sum(b for k, b in passed if k == kind)
        assert c.copies == 4 * c.launches


def test_decode_args_and_produced(traced):
    _, done, _, spans = traced
    steps = [s for s in spans if s.name == "serving.step"]
    assert sum(s.args["produced"] for s in steps) == sum(
        len(r.generated) for r in done) == sum(NEW_TOKENS.values())
    assert sum(s.args["finished"] for s in steps) == len(PROMPTS)
    for d in (s for s in spans if s.name == "serving.decode_launch"):
        assert d.parent.args["produced"] == d.args["live"]
        assert d.args["live"] <= d.args["context_tokens"]


def test_no_argument_is_computed_with_the_profiler_off(small_model, monkeypatch,
                                                     tmp_path):
    def never(*_, **__):
        raise AssertionError("a span argument was computed")

    monkeypatch.setattr(ServingEngine, "_admit_args", never)
    monkeypatch.setattr(ServingEngine, "_launch_args", never)
    engine = _engine(small_model)
    _submit_all(engine)
    assert len(engine.run_until_done()) == len(PROMPTS)  # no profiler running

    monkeypatch.setattr(TraceAnnotation, "is_enabled", staticmethod(lambda: False))
    engine = _engine(small_model)
    _submit_all(engine)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert len(engine.run_until_done()) == len(PROMPTS)
    finally:
        jax.profiler.stop_trace()

