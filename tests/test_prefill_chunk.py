"""Chunked prefill: the dense trunk's one causal forward per chunk against
the scan of masked decode steps it replaces, and the scan that the other
families keep, through the engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models import layers as L
from repro.models.model import Model
from repro.serving import Request, ServingEngine

T = 8  # tokens a chunk
SLOTS, SLOT = 3, 1  # the admitted slot among three
# K/V rows written by the two paths. They run the same operations in the
# same dtypes, but the chunk's matmuls have T rows where each scan step's
# have B: a backend may tile and accumulate them differently, which moves a
# bf16 result by a rounding step (2**-8 relative), and a moved residual
# stream moves the next layer's rows by a few. 2**-6 of the rows' largest
# magnitude is four such steps; it also holds a one-step int8 flip (1/127
# of a row's absmax).
KV_TOL = 2.0**-6

# (first prompt position, prompt tokens prefilled, max_len)
CASES = {
    "full_chunk": (0, T, 32),
    "partial_last_chunk": (0, T + 5, 32),
    "nonzero_pos0": (12, T, 32),
    # a prompt of max_len - 1 tokens: its last chunk starts at 24 and would
    # run to 32, past max_len; a clamped 8-row update would shift it to 22
    "prompt_of_max_len_minus_1": (0, 30 - 2, 30),
}


@pytest.fixture(scope="module", params=["none", "int8"])
def dense(request):
    cfg = dataclasses.replace(get("qwen2-0.5b").reduced(), remat="none",
                              cache_quant=request.param)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    return model, params, jax.jit(model.prefill_chunk), jax.jit(model.prefill_scan)


def _filled_cache(model, max_len):
    """A cache whose every row holds something: rows a path must leave alone
    are then told apart from rows it wrote."""
    cache = model.init_cache(SLOTS, max_len)
    keys = jax.random.split(jax.random.key(7), len(cache))
    out = {}
    for key, (name, leaf) in zip(keys, sorted(cache.items())):
        if leaf.dtype == jnp.int8:
            out[name] = jax.random.randint(key, leaf.shape, -127, 127, jnp.int8)
        elif name.endswith("_scale"):
            out[name] = jax.random.uniform(key, leaf.shape, jnp.float32, 0.01,
                                           0.05).astype(leaf.dtype)
        else:
            out[name] = jax.random.normal(key, leaf.shape).astype(leaf.dtype)
    return out


def _prefill(fn, params, cache, prompt, start):
    """The engine's chain of chunks over ``prompt`` from ``start``: padded to
    T with tokens that must not count, one probe a chunk."""
    probes = []
    for i in range(0, len(prompt), T):
        chunk = prompt[i:i + T]
        buf = np.full((T,), 3, np.int32)  # padding the paths must ignore
        buf[:len(chunk)] = chunk
        pos0 = np.array([5, start + i, 9], np.int32)
        mask = np.arange(SLOTS) == SLOT
        probe, cache = fn(params, cache, buf, pos0, np.int32(len(chunk)), mask)
        probes.append(np.asarray(probe))
    return probes, jax.tree.map(np.asarray, cache)


def _kv(cache):
    """The cache's K and V as float32 values (int8 rows dequantized)."""
    if "k_scale" in cache:
        return [np.asarray(L.dequantize_kv(cache[n], cache[f"{n}_scale"]),
                           np.float32) for n in ("k", "v")]
    return [np.asarray(cache[n], np.float32) for n in ("k", "v")]


@pytest.mark.parametrize("case", CASES)
def test_chunk_matches_masked_decode_scan(dense, case):
    model, params, chunk_fn, scan_fn = dense
    assert model.prefill_path == "chunk"
    start, n, max_len = CASES[case]
    prompt = [int(t) for t in jax.random.randint(
        jax.random.key(n + start), (n,), 0, model.cfg.vocab_size)]
    before = jax.tree.map(np.asarray, _filled_cache(model, max_len))
    got_probes, got = _prefill(chunk_fn, params, before, prompt, start)
    want_probes, want = _prefill(scan_fn, params, before, prompt, start)

    # the probe: the last valid token's argmax, in the admitted slot alone
    for g, w in zip(got_probes, want_probes):
        np.testing.assert_array_equal(g, w)
        assert g.shape == (SLOTS, 1) and g.dtype == np.int32
        assert not g[np.arange(SLOTS) != SLOT].any()

    written = slice(start, start + n)
    for g, w in zip(_kv(got), _kv(want)):
        g, w = g[:, SLOT, written], w[:, SLOT, written]
        np.testing.assert_allclose(g, w, rtol=KV_TOL, atol=KV_TOL * np.abs(w).max())

    # everything else is the input, bit for bit: other slots, and the
    # admitted slot's rows before the prompt and from its end on
    for name in before:
        for out in (got, want):
            kept = np.delete(out[name], SLOT, axis=1)
            np.testing.assert_array_equal(kept, np.delete(before[name], SLOT, axis=1))
            np.testing.assert_array_equal(out[name][:, SLOT, :start],
                                          before[name][:, SLOT, :start])
            np.testing.assert_array_equal(out[name][:, SLOT, start + n:],
                                          before[name][:, SLOT, start + n:])


def test_no_admitted_slot_writes_nothing(dense):
    model, params, chunk_fn, _ = dense
    before = jax.tree.map(np.asarray, _filled_cache(model, 32))
    probe, after = chunk_fn(params, before, np.arange(T, dtype=np.int32),
                            np.zeros((SLOTS,), np.int32), np.int32(T),
                            np.zeros((SLOTS,), bool))
    assert not np.asarray(probe).any()
    for name in before:
        np.testing.assert_array_equal(np.asarray(after[name]), before[name])


# family -> (configuration, the path prefill takes)
FAMILIES = {
    "dense": ("qwen2-0.5b", "chunk"),
    "moe": ("phi3.5-moe-42b-a6.6b", "scan"),
    "hybrid": ("jamba-1.5-large-398b", "scan"),
    "ssm": ("rwkv6-7b", "scan"),
}


def _lockstep_reference(model, params, prompt, n_new, max_len):
    """One sequence, one decode step a token: the prompt, then greedy."""
    cache = model.init_cache(1, max_len)
    step = jax.jit(model.decode_step)
    out = []
    for pos in range(len(prompt) + n_new - 1):
        tok = prompt[pos] if pos < len(prompt) else out[-1]
        logits, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32),
                             jnp.int32(pos))
        if pos >= len(prompt) - 1:
            out.append(int(jnp.argmax(logits[0, 0])))
    return out


@pytest.mark.parametrize("family", FAMILIES)
def test_engine_serves_lockstep_tokens_on_each_path(family):
    arch, path = FAMILIES[family]
    cfg = dataclasses.replace(get(arch).reduced(), remat="none")
    if cfg.n_experts:
        # capacity routing drops different tokens at different token counts;
        # drop-free capacity routes the engine's batch as the reference's
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    assert model.prefill_path == path
    scans = []
    scan = model.prefill_scan
    model.prefill_scan = lambda *a: scans.append(1) or scan(*a)

    prompts = {0: [5, 9, 2, 7, 1, 4], 1: [3, 8, 6], 2: [2, 4, 6, 8, 1, 3, 5, 7, 9]}
    n_new, max_len = 4, 32
    engine = ServingEngine(model, params, max_slots=2, max_len=max_len,
                           prefill_chunk=4)
    for uid, prompt in prompts.items():
        engine.submit(Request(uid=uid, prompt=prompt, max_new_tokens=n_new))
    done = {r.uid: r.generated for r in engine.run_until_done()}
    assert bool(scans) == (path == "scan")  # traced once, when compiled
    for uid, prompt in prompts.items():
        assert done[uid] == _lockstep_reference(model, params, prompt, n_new,
                                                max_len), uid
