"""Smoke run of the serving main path on one TPU chip.

    python chip_smoke.py

Serves full-width qwen2-0.5b (24 layers, d_model 896, vocab 151936, seeded
random weights) through ``ServingEngine`` with fused Pallas sampling, then
checks what came out:

* every request finishes with exactly ``MAX_NEW_TOKENS`` tokens;
* the compiled decode step carries the Pallas sampling kernel
  (``tpu_custom_call``);
* the kernel's ids on a batch of decode logits are bit-identical to
  ``jnp.argmax``;
* a teacher-forced ``Model.forward`` over each prompt plus its generated
  tokens agrees with the engine's tokens on top-1, and its logits agree
  with the decode path's;
* nothing compiles inside the served window.

One process holds the chip for the whole run. The script fails, and prints
no result line, when JAX finds no TPU; there is no CPU mode. The last line
of standard output is the JSON result. The times it prints are from one
smoke run, not benchmark metrics.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
SEED = 0
N_REQUESTS = 12  # more than MAX_SLOTS, so freed slots are reused
PROMPT_LENS = (16, 384)  # inclusive range the prompt lengths are drawn from
MAX_NEW_TOKENS = 32
MAX_SLOTS = 8
MAX_LEN = 1024
PREFILL_CHUNK = 8
# decode-vs-forward tolerances of tests/test_decode_parity.py: both paths
# run in bf16 and accumulate in different orders, so near-ties may flip
RTOL, ATOL, MIN_TOP1 = 0.05, 0.15, 0.9

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def require(ok, message: str) -> None:
    """A failed check ends the run; unlike ``assert`` it holds under -O."""
    if not ok:
        raise RuntimeError(message)


class CompileCounter:
    """Counts the executables JAX builds while active (compiled, or loaded
    from the persistent cache) and the persistent-cache hits among them."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def make_requests(seed: int, vocab: int) -> list:
    """``N_REQUESTS`` requests with prompt lengths and tokens drawn from
    ``seed``."""
    import numpy as np

    from repro.serving import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=N_REQUESTS)
    return [
        Request(uid=i, prompt=rng.integers(0, vocab, size=n).tolist(),
                max_new_tokens=MAX_NEW_TOKENS)
        for i, n in enumerate(lens)
    ]


def reference_logits(model, params, seqs, starts):
    """Logits at positions ``starts[b] + j`` (j < MAX_NEW_TOKENS) of each
    row of ``seqs``, two ways: the teacher-forced training forward, and the
    serving decode step fed the same tokens one at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, s = seqs.shape
    rows = jnp.arange(b)
    gen = jnp.arange(MAX_NEW_TOKENS)

    @jax.jit
    def forward(params, seqs, starts):
        logits, _ = model.forward(params, {"tokens": seqs})
        return logits[rows[:, None], starts[:, None] + gen[None]]

    @jax.jit
    def decode(params, seqs, starts):
        def body(carry, i):
            cache, out = carry
            tok = lax.dynamic_slice_in_dim(seqs, i, 1, axis=1)
            logits, cache = model.decode_step(
                params, cache, tok, jnp.full((b,), i, jnp.int32))
            j = i - starts
            # steps before a row's window write out of range: dropped
            j = jnp.where(j >= 0, j, MAX_NEW_TOKENS)
            out = out.at[rows, j].set(logits[:, 0].astype(out.dtype), mode="drop")
            return (cache, out), None

        out = jnp.zeros((b, MAX_NEW_TOKENS, model.cfg.vocab_size), jnp.float32)
        (_, out), _ = lax.scan(body, (model.init_cache(b, MAX_LEN), out),
                               jnp.arange(s))
        return out

    return forward(params, seqs, starts), decode(params, seqs, starts)


def serve_and_check(cfg, *, sample_backend: str) -> dict:
    """Serve ``N_REQUESTS`` seeded requests through ``ServingEngine`` and
    check the results; raises on any failed check. Returns what the run
    measured and found."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kernel_ops
    from repro.models.model import Model
    from repro.serving import Request, ServingEngine

    model = Model(cfg)
    with CompileCounter() as setup:
        t0 = time.perf_counter()
        params = jax.block_until_ready(jax.jit(model.init)(jax.random.key(SEED)))
        engine = ServingEngine(
            model, params, max_slots=MAX_SLOTS, max_len=MAX_LEN,
            sampling="fused", sample_backend=sample_backend,
            prefill_chunk=PREFILL_CHUNK)
        # warm-up: one request takes every launch shape the window uses
        # (one prefill chunk, the decode step, the host-side id readback)
        engine.submit(Request(uid=-1, prompt=list(range(1, PREFILL_CHUNK + 2)),
                              max_new_tokens=2))
        engine.run_until_done()
        engine.finished.clear()
        warmup_s = time.perf_counter() - t0

    requests = make_requests(SEED, cfg.vocab_size)
    with CompileCounter() as window:
        t0 = time.perf_counter()
        for req in requests:
            engine.submit(req)
        finished = engine.run_until_done()
        window_s = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}

    require(window.compiles == 0,
            f"{window.compiles} compilation(s) inside the served window")
    require(len(finished) == N_REQUESTS,
            f"{len(finished)} of {N_REQUESTS} requests finished")
    short = [r.uid for r in finished if len(r.generated) != MAX_NEW_TOKENS]
    require(not short,
            f"requests without exactly {MAX_NEW_TOKENS} tokens: {short}")

    decode_hlo = engine._decode.lower(
        params, engine.cache, engine._dev_tokens,
        jnp.zeros((MAX_SLOTS,), jnp.int32), jnp.zeros((MAX_SLOTS,), bool),
        jnp.zeros((MAX_SLOTS,), jnp.int32), jnp.ones((MAX_SLOTS,), bool),
    ).compile().as_text()

    by_uid = sorted(finished, key=lambda r: r.uid)
    seqs = np.zeros((N_REQUESTS, max(len(r.prompt) for r in by_uid)
                     + MAX_NEW_TOKENS), np.int32)
    for i, r in enumerate(by_uid):
        seqs[i, :len(r.prompt) + MAX_NEW_TOKENS] = r.prompt + r.generated
    starts = np.asarray([len(r.prompt) - 1 for r in by_uid], np.int32)
    engine_ids = np.asarray([r.generated for r in by_uid])
    fwd, dec = reference_logits(model, params, jnp.asarray(seqs),
                                jnp.asarray(starts))

    batch = dec[:, 0]  # one batch of decode logits
    kernel_ids = np.asarray(kernel_ops.sample_op(batch, backend=sample_backend))
    argmax_ids = np.asarray(jnp.argmax(batch, axis=-1))
    require(np.array_equal(kernel_ids, argmax_ids),
            f"kernel ids {kernel_ids} != jnp.argmax ids {argmax_ids}")

    fwd = np.asarray(fwd, np.float32)
    dec = np.asarray(dec)
    require(np.isfinite(fwd).all() and np.isfinite(dec).all(),
            "non-finite logits")
    top1 = float((fwd.argmax(-1) == engine_ids).mean())
    require(top1 >= MIN_TOP1,
            f"teacher-forced top-1 agreement {top1} < {MIN_TOP1}")
    np.testing.assert_allclose(fwd, dec, rtol=RTOL, atol=ATOL)

    return {
        "requests": len(finished),
        "tokens": int(sum(len(r.generated) for r in finished)),
        "prompt_tokens": int(sum(len(r.prompt) for r in finished)),
        "warmup_s": warmup_s,
        "setup_compiles": setup.compiles,
        "compile_s": setup.compile_s,
        "cache_hits": setup.cache_hits,
        "window_s": window_s,
        "window_compiles": window.compiles,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "kernel_on_decode_path": "tpu_custom_call" in decode_hlo,
        "top1_agreement": top1,
        "max_abs_logit_diff": float(np.abs(fwd - dec).max()),
    }


def main() -> int:
    import importlib.metadata

    import jax

    from repro.configs import get
    from repro.launch.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()  # before anything compiles
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()} jax={jax.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"compile_cache={cache_dir}", flush=True)

    cfg = get(ARCH)
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} tied={cfg.tie_embeddings} seed={SEED}",
          flush=True)
    r = serve_and_check(cfg, sample_backend="pallas")
    require(r["kernel_on_decode_path"], "no tpu_custom_call in the decode step")

    print(f"smoke run (one run, not a benchmark): "
          f"{r['requests']}/{N_REQUESTS} requests finished with "
          f"{MAX_NEW_TOKENS} tokens each; {r['tokens']} tokens generated, "
          f"{r['prompt_tokens']} prompt tokens")
    print(f"smoke run: warm window {r['window_s']:.3f} s "
          f"({r['tokens'] / r['window_s']:.1f} generated tokens/s), "
          f"{r['window_compiles']} compilations in the window")
    print(f"smoke run: set-up {r['warmup_s']:.3f} s with "
          f"{r['setup_compiles']} compilations taking {r['compile_s']:.3f} s, "
          f"{r['cache_hits']} persistent-cache hits")
    print(f"smoke run: peak_bytes_in_use={r['peak_bytes_in_use']}")
    print(f"checks: pallas kernel on decode path={r['kernel_on_decode_path']}, "
          "kernel ids == jnp.argmax=True, "
          f"teacher-forced top-1 agreement={r['top1_agreement']:.4f} "
          f"(>= {MIN_TOP1}), max |forward - decode| logit="
          f"{r['max_abs_logit_diff']:.4f} (rtol {RTOL}, atol {ATOL})")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
