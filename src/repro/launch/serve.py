"""Batched serving driver: prefill-free decode loop with the paper's two
optimizations applied at the dispatch layer.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --batch 4 --steps 64 [--mode sequential|concurrent|fused]

Modes map to the configuration roofline (§4):
* ``sequential``  — block per token + re-send full descriptor: the paper's
                    sequential-configuration baseline.
* ``concurrent``  — async dispatch + deduped descriptors (only the position
                    scalar crosses the boundary): dedup + overlap.
* ``fused``       — k tokens per launch via ``lax.scan`` inside the jitted
                    step: configuration hoisting, I_OC × k (§4.2's rightward
                    move; the decisive serving-side win).
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen2-0.5b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--steps", type=int, default=64)
    p.add_argument("--cache-len", type=int, default=256)
    p.add_argument("--mode", default="concurrent",
                   choices=("sequential", "concurrent", "fused"))
    p.add_argument("--fuse", type=int, default=8, help="tokens per launch (fused)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.configs import get
    from repro.launch.compile_cache import setup_compile_cache
    from repro.models.model import Model

    setup_compile_cache()

    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    if cfg.family in ("vlm", "encdec"):
        raise SystemExit("serve driver targets decoder-only archs; "
                         "use examples/serve_decode.py for stubs")

    params = model.init(jax.random.key(0))
    cache = model.init_cache(args.batch, args.cache_len)
    tokens = jnp.ones((args.batch, 1), jnp.int32)

    decode = jax.jit(model.decode_step, donate_argnums=(1,))

    def fused_decode(params, cache, tokens, pos0, k):
        def body(carry, i):
            cache, toks = carry
            logits, cache = model.decode_step(params, cache, toks, pos0 + i)
            nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            return (cache, nxt), nxt[:, 0]
        (cache, _), out = jax.lax.scan(
            body, (cache, tokens), jnp.arange(k, dtype=jnp.int32)
        )
        return out, cache

    fused = jax.jit(fused_decode, static_argnames=("k",), donate_argnums=(1,))

    # warmup
    if args.mode == "fused":
        out, cache = fused(params, cache, tokens, jnp.int32(0), args.fuse)
        jax.block_until_ready(out)
        start = args.fuse
    else:
        logits, cache = decode(params, cache, tokens, jnp.int32(0))
        jax.block_until_ready(logits)
        start = 1

    t0 = time.perf_counter()
    produced = 0
    if args.mode == "sequential":
        for i in range(start, args.steps):
            logits, cache = decode(params, cache, tokens, jnp.int32(i))
            jax.block_until_ready(logits)  # host blocked per token
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            produced += 1
    elif args.mode == "concurrent":
        for i in range(start, args.steps):
            logits, cache = decode(params, cache, tokens, jnp.int32(i))
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # async
            produced += 1
        jax.block_until_ready(tokens)
    else:  # fused
        pos = start
        while pos < args.steps:
            k = min(args.fuse, args.steps - pos)
            out, cache = fused(params, cache, tokens, jnp.int32(pos), k)
            tokens = out[-1:, :].T.astype(jnp.int32)
            pos += k
            produced += k
        jax.block_until_ready(tokens)
    dt = time.perf_counter() - t0

    tps = produced * args.batch / dt
    print(f"[serve] arch={cfg.name} mode={args.mode} batch={args.batch} "
          f"steps={produced}: {dt*1e3:.1f} ms total, {tps:.0f} tok/s "
          f"({dt/max(produced,1)*1e3:.2f} ms/step)")


if __name__ == "__main__":
    main()
