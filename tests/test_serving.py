"""Continuous-batching engine + int8 KV cache tests."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models.model import Model
from repro.serving import Request, ServingEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(get("qwen2-0.5b").reduced(), remat="none")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _greedy_reference(model, params, prompt, n_new, max_len=64):
    """Single-sequence greedy decode, lock-step reference."""
    cache = model.init_cache(1, max_len)
    step = jax.jit(model.decode_step)
    pos = 0
    tok = None
    for t in prompt:
        logits, cache = step(
            params, cache, jnp.asarray([[t]], jnp.int32), jnp.int32(pos)
        )
        pos += 1
    out = []
    tok = int(jnp.argmax(logits[0, 0]))
    for _ in range(n_new):
        out.append(tok)
        logits, cache = step(
            params, cache, jnp.asarray([[tok]], jnp.int32), jnp.int32(pos)
        )
        pos += 1
        tok = int(jnp.argmax(logits[0, 0]))
    return out


def test_engine_matches_single_sequence_reference(small_model):
    cfg, model, params = small_model
    prompts = [[5, 9, 2], [7, 1], [3, 3, 3, 3]]
    n_new = 5

    refs = [
        _greedy_reference(model, params, p, n_new - 1) for p in prompts
    ]

    engine = ServingEngine(model, params, max_slots=2, max_len=64)
    for i, p in enumerate(prompts):
        engine.submit(Request(uid=i, prompt=list(p), max_new_tokens=n_new))
    finished = engine.run_until_done()
    assert len(finished) == 3
    by_uid = {r.uid: r for r in finished}
    for i, ref in enumerate(refs):
        got = by_uid[i].generated
        assert len(got) == n_new
        # engine's first generated token comes from the same prompt prefill;
        # subsequent tokens follow greedy decode — compare the shared stretch
        assert got[1 : 1 + len(ref)] == ref[: n_new - 1] or got[:n_new - 1] == ref[: n_new - 1]


def test_engine_overlapping_lifetimes(small_model):
    cfg, model, params = small_model
    engine = ServingEngine(model, params, max_slots=2, max_len=32)
    engine.submit(Request(uid=0, prompt=[1], max_new_tokens=8))
    engine.submit(Request(uid=1, prompt=[2], max_new_tokens=2))
    engine.submit(Request(uid=2, prompt=[3], max_new_tokens=2))  # queued
    # one step: both live slots advance together
    assert engine.step() == 2
    finished = engine.run_until_done()
    assert sorted(r.uid for r in finished) == [0, 1, 2]
    assert all(len(r.generated) == r.max_new_tokens for r in finished)


def test_int8_cache_decode_top1_agreement(small_model):
    cfg, model, params = small_model
    cfg_q = dataclasses.replace(cfg, cache_quant="int8")
    model_q = Model(cfg_q)

    B, S = 2, 10
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)

    def run(m):
        cache = m.init_cache(B, S)
        step = jax.jit(m.decode_step)
        outs = []
        for i in range(S):
            lg, cache = step(params, cache, toks[:, i : i + 1], jnp.int32(i))
            outs.append(np.asarray(lg[:, 0], np.float32))
        return np.stack(outs, 1)

    a, b = run(model), run(model_q)
    # inclusive: int8 quantization legitimately flips a knife-edge argmax on
    # ~1/20 positions of this tiny model; at the boundary that's still fine
    assert (a.argmax(-1) == b.argmax(-1)).mean() >= 0.95
    np.testing.assert_allclose(a, b, rtol=0.2, atol=0.5)


def test_submit_rejects_empty_prompt(small_model):
    cfg, model, params = small_model
    engine = ServingEngine(model, params, max_slots=2, max_len=16)
    with pytest.raises(ValueError, match="empty prompt"):
        engine.submit(Request(uid=0, prompt=[], max_new_tokens=4))


def test_submit_rejects_prompt_at_or_over_max_len(small_model):
    cfg, model, params = small_model
    engine = ServingEngine(model, params, max_slots=2, max_len=8)
    with pytest.raises(ValueError, match="overrun"):
        engine.submit(Request(uid=0, prompt=list(range(8)), max_new_tokens=1))
    with pytest.raises(ValueError, match="overrun"):
        engine.submit(Request(uid=1, prompt=list(range(12)), max_new_tokens=1))
    # the boundary case is admissible and yields exactly one token: the
    # prompt fills positions 0..6, leaving room for a single decode step
    engine.submit(Request(uid=2, prompt=[1, 2, 3, 4, 5, 6, 7],
                          max_new_tokens=100))
    (done,) = engine.run_until_done()
    assert len(done.generated) == 1


def test_max_len_terminates_at_exact_token_count(small_model):
    """max_len=8, prompt of 3: prefill holds positions 0..1, decode starts
    at position 2 and must stop when the slot's next write would overrun —
    exactly 5 generated tokens, never 4 or 6."""
    cfg, model, params = small_model
    engine = ServingEngine(model, params, max_slots=2, max_len=8)
    engine.submit(Request(uid=0, prompt=[5, 9, 2], max_new_tokens=100))
    (done,) = engine.run_until_done()
    assert len(done.generated) == 5
    # and a max_new_tokens bound below the ceiling wins instead
    engine.submit(Request(uid=1, prompt=[5, 9, 2], max_new_tokens=3))
    done = engine.run_until_done()[-1]
    assert len(done.generated) == 3


def test_masked_prefill_leaves_other_slots_bit_identical(small_model):
    """Admission prefill is masked to the admitted slot: a resident slot's
    KV rows must survive another request's whole prefill chain untouched
    (the over-stepping regression), while the admitted slot's rows fill."""
    cfg, model, params = small_model
    engine = ServingEngine(model, params, max_slots=2, max_len=32,
                           prefill_chunk=4)
    engine.submit(Request(uid=0, prompt=[5, 9, 2, 7, 1], max_new_tokens=20))
    engine.step()  # admit + first decode: slot 0 now holds live KV state
    engine.executor.drain()
    before_k = np.asarray(engine.cache["k"][:, 0])
    before_v = np.asarray(engine.cache["v"][:, 0])
    assert before_k.any(), "slot 0 should hold prefill state already"
    # admit uid=1 alone (no decode step): only its prefill launches run
    engine.submit(Request(uid=1, prompt=[3, 3, 4, 4, 6, 6, 8], max_new_tokens=4))
    engine._admit()
    engine.executor.drain()
    np.testing.assert_array_equal(np.asarray(engine.cache["k"][:, 0]), before_k)
    np.testing.assert_array_equal(np.asarray(engine.cache["v"][:, 0]), before_v)
    assert np.asarray(engine.cache["k"][:, 1]).any(), \
        "slot 1's rows should have been written by its prefill"


def test_fused_descriptor_drops_tokens_leaf_and_pins_bytes(small_model):
    """The fused decode descriptor has no ``tokens`` leaf (ids are
    device-resident) and its wire size is pinned: positions 16 + live_mask 4
    + token_overrides 16 + override_mask 4 + invariants 12 = 52 bytes; the
    host-sampling descriptor carries tokens (4×int32) instead of the
    override pair: 48 bytes."""
    cfg, model, params = small_model

    def steady_desc(sampling):
        captured = []
        engine = ServingEngine(model, params, max_slots=4, max_len=16,
                               sampling=sampling, on_launch=captured.append)
        engine.submit(Request(uid=0, prompt=[3], max_new_tokens=4))
        engine.run_until_done()
        decode = [d for d in captured if "prefill_tokens" not in d]
        assert len(decode) == 4
        return decode[-1]

    fused = steady_desc("fused")
    assert "tokens" not in fused
    assert set(fused) == {"positions", "live_mask", "token_overrides",
                          "override_mask", "max_len", "eos_id", "n_slots"}
    assert sum(np.asarray(v).nbytes for v in fused.values()) == 52

    host = steady_desc("host")
    assert "token_overrides" not in host
    assert set(host) == {"positions", "live_mask", "tokens",
                         "max_len", "eos_id", "n_slots"}
    assert sum(np.asarray(v).nbytes for v in host.values()) == 48


def test_freed_slot_token_state_is_zeroed(small_model):
    """A finished request's slot must not leak its last token into later
    descriptors: the host mirror and the fused override both reset to 0,
    and the slot's next occupant decodes identically to a fresh engine."""
    cfg, model, params = small_model
    captured = []
    engine = ServingEngine(model, params, max_slots=1, max_len=32,
                           on_launch=captured.append)
    engine.submit(Request(uid=0, prompt=[7, 7], max_new_tokens=2))
    engine.submit(Request(uid=1, prompt=[5, 9], max_new_tokens=4))
    done = engine.run_until_done()
    assert [r.uid for r in done] == [0, 1]
    assert engine.tokens[0, 0] == 0 and engine._overrides[0] == 0
    # the freed slot's zeroing is visible on the wire: the decode launch
    # right after uid=0 retired carries uid=1's admission override, not
    # uid=0's stale last token
    decode = [d for d in captured if "prefill_tokens" not in d]
    stale = int(done[0].generated[-1])
    relaunch = decode[2]  # steps 0-1 served uid=0; step 2 admits uid=1
    assert relaunch["override_mask"][0]
    assert relaunch["token_overrides"][0] == 9 != stale

    fresh = ServingEngine(model, params, max_slots=1, max_len=32)
    fresh.submit(Request(uid=1, prompt=[5, 9], max_new_tokens=4))
    (want,) = fresh.run_until_done()
    assert done[1].generated == want.generated


@pytest.mark.parametrize("variant", [
    {"sampling": "host"},
    {"sampling": "fused", "sample_backend": "pallas_interpret"},
])
def test_sampling_modes_bit_identical_streams(small_model, variant):
    """Fused on-device sampling (XLA argmax or the Pallas kernel) and
    host-side argmax must produce bit-identical token streams — sampling
    placement is a boundary optimization, never a semantic change."""
    cfg, model, params = small_model
    prompts = [[5, 9, 2], [7, 1], [3, 3, 3, 3], [11]]

    def run(**kw):
        engine = ServingEngine(model, params, max_slots=2, max_len=32, **kw)
        for i, p in enumerate(prompts):
            engine.submit(Request(uid=i, prompt=list(p), max_new_tokens=6))
        return {r.uid: r.generated for r in engine.run_until_done()}

    assert run(sampling="fused") == run(**variant)


def test_int8_cache_halves_bytes(small_model):
    cfg, model, params = small_model
    cfg_q = dataclasses.replace(cfg, cache_quant="int8")
    model_q = Model(cfg_q)
    def nbytes(c):
        return sum(np.asarray(x).nbytes for x in jax.tree.leaves(c))
    full = nbytes(model.init_cache(4, 128))
    quant = nbytes(model_q.init_cache(4, 128))
    # int8 + bf16 scales (D=16 heads → scale overhead 2/16): ≈ 0.56×
    assert quant < 0.65 * full


# ------------------------------------------- a decode launch staged ahead

# two requests on two slots, long answers, so that most decode launches
# are staged ahead of the read of the one before; and the streams that the
# engine served for them when it read every launch before the next
STAGED = {0: ([5, 9, 2, 7, 1, 4], 24), 1: ([3, 1, 4], 30)}
SYNC_STREAMS = {
    0: [232, 194, 232, 194, 232, 223, 207, 171, 194, 232, 232, 232, 223, 223,
        205, 205, 124, 147, 147, 201, 201, 201, 147, 205],
    1: [223, 236, 223, 223, 62, 54, 253, 164, 219, 1, 136, 136, 205, 136, 205,
        136, 205, 81, 70, 219, 219, 219, 83, 83, 83, 202, 255, 255, 36, 223],
}
FAMILY_ARCH = {"dense": "qwen2-0.5b", "moe": "phi3.5-moe-42b-a6.6b",
               "hybrid": "jamba-1.5-large-398b", "ssm": "rwkv6-7b"}


def _staged_engine(model, params, **kw):
    engine = ServingEngine(model, params, max_slots=2, max_len=64,
                           prefill_chunk=4, **kw)
    reqs = [Request(uid=uid, prompt=list(p), max_new_tokens=n)
            for uid, (p, n) in STAGED.items()]
    for r in reqs:
        engine.submit(r)
    return engine, reqs


def test_staged_streams_bit_identical_to_host_and_synchronous(small_model):
    cfg, model, params = small_model
    engine, _ = _staged_engine(model, params)
    got = {r.uid: r.generated for r in engine.run_until_done()}
    # while both slots are live, every launch but the first is staged
    # ahead: 23 of the first 24; the last 6 serve request 1 alone
    assert engine.h2d["decode"].launches == 30
    assert engine.ahead.launches == 23 and engine.ahead.dropped_rows == 0
    host, _ = _staged_engine(model, params, sampling="host")
    assert {r.uid: r.generated for r in host.run_until_done()} == got
    assert host.ahead.launches == 0  # host sampling stays synchronous
    assert got == SYNC_STREAMS


def test_no_launch_is_staged_with_a_free_slot_or_a_waiting_request(small_model):
    cfg, model, params = small_model
    engine = ServingEngine(model, params, max_slots=2, max_len=64)
    engine.submit(Request(uid=0, prompt=[5, 9, 2], max_new_tokens=12))
    engine.run_until_done()
    assert engine.ahead.launches == 0  # one of two slots live

    engine = ServingEngine(model, params, max_slots=2, max_len=64)
    for uid, n in enumerate([4, 12, 12]):
        engine.submit(Request(uid=uid, prompt=[5 + uid, 2], max_new_tokens=n))
    while not engine.idle:
        before = engine.ahead.launches
        engine.step()
        if engine.queue:  # a request waited through the step
            assert engine.ahead.launches == before
    assert engine.ahead.launches > 0  # once none waits


@pytest.mark.parametrize("max_len", [64, 10])
def test_no_launch_is_staged_after_a_last_token_by_length(small_model, max_len):
    """Every row a decode launch computes goes to a request: none is
    staged after a launch that holds a request's last token, whether by
    ``max_new_tokens`` or by the slot's last row."""
    cfg, model, params = small_model
    captured = []
    engine = ServingEngine(model, params, max_slots=2, max_len=max_len,
                           on_launch=captured.append)
    reqs = [Request(uid=0, prompt=[5, 9, 2], max_new_tokens=5),
            Request(uid=1, prompt=[3, 1], max_new_tokens=9)]
    for r in reqs:
        engine.submit(r)
    engine.run_until_done()
    rows = sum(int(d["live_mask"].sum()) for d in captured if "live_mask" in d)
    assert rows == sum(len(r.generated) for r in reqs)
    assert engine.ahead.launches > 0 and engine.ahead.dropped_rows == 0
    # request 1 ends by its last row at max_len 10: prompt of 2, 8 tokens
    want = [5, 9] if max_len == 64 else [5, max_len - 2]
    assert [len(r.generated) for r in reqs] == want


def test_run_until_done_leaves_no_launch_unread(small_model):
    """Two requests that end on one EOS in the same launch leave a staged
    launch whose rows are both dropped; ``run_until_done`` reads it."""
    cfg, model, params = small_model
    first = ServingEngine(model, params, max_slots=2, max_len=64)
    for uid in range(2):
        first.submit(Request(uid=uid, prompt=[3, 1, 4], max_new_tokens=16))
    eos = first.run_until_done()[0].generated[4]
    engine = ServingEngine(model, params, max_slots=2, max_len=64, eos_id=eos)
    for uid in range(2):
        engine.submit(Request(uid=uid, prompt=[3, 1, 4], max_new_tokens=16))
    engine.step()
    assert engine._unread is not None  # staged, read in the next step
    done = engine.run_until_done()
    assert [len(r.generated) for r in done] == [5, 5]
    assert not engine.live_slots and engine._unread is None and engine.idle
    assert engine.ahead.dropped_rows == 2
    assert not engine.executor._inflight


def _family_model(family):
    cfg = dataclasses.replace(get(FAMILY_ARCH[family]).reduced(), remat="none")
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    model = Model(cfg)
    return model, model.init(jax.random.key(0))


@pytest.mark.parametrize("family", FAMILY_ARCH)
def test_eos_inside_a_staged_pair(family):
    """A request ends on an EOS read while the next launch, staged ahead,
    has decoded one more row for it: the row goes to no request and is
    counted, the other slot's stream is unchanged, and the request admitted
    into the freed slot before that launch is read decodes as in a fresh
    engine (its recurrent state, where the family has one, zeroed)."""
    model, params = _family_model(family)
    first, _ = _staged_engine(model, params)
    streams = {r.uid: r.generated for r in first.run_until_done()}
    late = [8, 8, 6]
    fresh = ServingEngine(model, params, max_slots=2, max_len=64, prefill_chunk=4)
    fresh.submit(Request(uid=2, prompt=late, max_new_tokens=12))
    (want,) = fresh.run_until_done()
    a, b = streams[0], streams[1]
    # a token first emitted mid-answer by request 0, and by no other
    m = next(i for i in range(4, len(a))
             if a[i] not in a[:i] + b + want.generated)
    engine, (ra, rb) = _staged_engine(model, params, eos_id=a[m])
    while not ra.done:
        engine.step()
    assert ra.generated == a[:m + 1]
    assert engine._unread is not None and engine.ahead.dropped_rows == 0
    rc = Request(uid=2, prompt=late, max_new_tokens=12)
    engine.submit(rc)
    engine.step()  # admits into the freed slot, then reads the staged launch
    assert engine.slot_req[0] is rc and engine.ahead.dropped_rows == 1
    engine.run_until_done()
    assert ra.generated == a[:m + 1] and rb.generated == b
    assert rc.generated == want.generated
    assert engine.ahead.dropped_rows == 1
