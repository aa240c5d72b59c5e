"""End-to-end training driver (CPU-runnable; mesh-portable).

    PYTHONPATH=src python -m repro.launch.train --arch paper-lm-100m \
        --steps 300 --batch 8 --seq 256 [--reduced] [--ckpt-dir /tmp/ckpt]

Runs the full stack: synthetic data pipeline with prefetch (configuration
overlap at the data layer), jitted donated train step, fault-tolerant
supervisor with async checkpoints, straggler monitoring, and a final loss
report. ``--arch`` accepts any pool architecture; ``--reduced`` swaps in the
same-family smoke-scale config so every arch trains on one CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import time


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="paper-lm-100m")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--log-every", type=int, default=10)
    args = p.parse_args()

    import jax
    import numpy as np

    from repro.checkpoint import CheckpointStore
    from repro.configs import get
    from repro.data import make_train_iterator
    from repro.launch.compile_cache import setup_compile_cache
    from repro.models.model import Model
    from repro.optim import AdamW, CosineSchedule
    from repro.runtime import TrainSupervisor

    setup_compile_cache()

    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, remat="none")
    model = Model(cfg)
    optimizer = AdamW(
        schedule=CosineSchedule(peak_lr=args.lr, warmup_steps=20, total_steps=args.steps)
    )

    key = jax.random.key(0)
    params = model.init(key)
    opt_state = optimizer.init(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] arch={cfg.name} params={n_params/1e6:.1f}M "
          f"batch={args.batch} seq={args.seq}")

    def add_frontend(batch):
        if cfg.family in ("vlm",):
            batch["frontend_embeds"] = np.zeros(
                (args.batch, cfg.frontend_tokens, cfg.d_model), np.float32
            )
        if cfg.family == "encdec":
            batch["frontend_embeds"] = np.zeros(
                (args.batch, cfg.encoder_seq_len, cfg.d_model), np.float32
            )
        return batch

    @jax.jit
    def train_step(state, batch):
        params, opt_state = state
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch
        )
        params, opt_state, om = optimizer.update(params, grads, opt_state)
        return (params, opt_state), {**metrics, **om, "loss": loss}

    data = make_train_iterator(cfg.vocab_size, args.seq, args.batch, prefetch=2)
    batches = {}

    def batch_fn(step):
        while True:
            s, b = next(data)
            batches[s] = add_frontend(b)
            if step in batches:
                return batches.pop(step)

    losses = []
    t0 = time.time()

    if args.ckpt_dir:
        store = CheckpointStore(args.ckpt_dir)

        def step_fn(state, batch):
            new_state, metrics = train_step(state, batch)
            losses.append(float(metrics["loss"]))
            return new_state

        sup = TrainSupervisor(step_fn, store, ckpt_every=args.ckpt_every)
        state = sup.run((params, opt_state), batch_fn, args.steps)
        params, opt_state = state
        print(f"[train] straggler events: {len(sup.monitor.flagged)}; "
              f"restarts: {sup.restarts}")
    else:
        state = (params, opt_state)
        for step in range(args.steps):
            state, metrics = train_step(state, batch_fn(step))
            losses.append(float(metrics["loss"]))
            if step % args.log_every == 0:
                print(f"  step {step:4d} loss={losses[-1]:.4f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"gnorm={float(metrics['grad_norm']):.3f}")
    data.close()

    dt = time.time() - t0
    print(f"[train] {args.steps} steps in {dt:.1f}s "
          f"({args.steps / dt:.2f} steps/s); "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "loss did not improve"


if __name__ == "__main__":
    main()
