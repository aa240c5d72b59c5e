"""Faults a serving cell can have, planted in the timed path.

Each is an engine hook (``Run(engine_hook=...)``): it gets the
``ServingEngine`` once warm-up is done and breaks what the window drives.
The check has to read every one of them as not correct; the tests plant
them on the CPU, and ``chipbench/control.py --fault <name>`` on the chip at
a cell's own size. Each compiles what it adds before the window opens, so
a faulty run reads no compile in its window and fails by its tokens alone.
"""

import jax
import jax.numpy as jnp


def altered_token(engine) -> None:
    """Every token the decode step samples is replaced by the next id."""
    decode, vocab = engine._decode, engine.model.cfg.vocab_size
    alter = jax.jit(lambda ids: (ids + 1) % vocab)
    alter(engine._dev_tokens).block_until_ready()

    def bad(*args):
        ids, cache = decode(*args)
        return alter(ids), cache

    engine._decode = bad


def half_slots(engine) -> None:
    """Half of the batch left out: the decode step's tokens are kept for
    the lower half of the slots only; the upper half gets its input token
    back instead of a new one."""
    decode = engine._decode
    b = engine.max_slots // 2
    keep = jax.jit(lambda ids, prev: jnp.concatenate([ids[:b], prev[b:]]))
    keep(engine._dev_tokens, engine._dev_tokens).block_until_ready()

    def bad(params, cache, prev, *args):
        ids, cache = decode(params, cache, prev, *args)
        return keep(ids, prev), cache

    engine._decode = bad


def stale_prefill(engine) -> None:
    """The prefill chunks leave the cache unchanged and put token 0 first."""
    probe = jnp.zeros((engine.max_slots, 1), jnp.int32)

    def bad(params, cache, *args):
        return probe, cache

    engine._prefill = bad


FAULTS = {f.__name__: f for f in (altered_token, half_slots, stale_prefill)}
