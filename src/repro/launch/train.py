"""Training driver: real training on CPU (reduced configs) or any mesh.

  PYTHONPATH=src python -m repro.launch.train --arch gte_small --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1

Features exercised: data pipeline w/ prefetch, microbatch accumulation,
AdamW (+int8 moments on large configs), remat, checkpoint/restart
(RestartManager survives kill -9 between steps), step watchdog.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import RunConfig, ShapeConfig, TrainConfig
from repro.configs import get_config, get_reduced
from repro.data.pipeline import LMBatcher, Prefetcher
from repro.data.synthetic import lm_token_stream
from repro.data.tokenizer import HashTokenizer
from repro.dist.fault import RestartManager, StepWatchdog
from repro.models import model
from repro.train import trainer


def run(arch: str, *, reduced: bool = True, steps: int = 100, batch: int = 8,
        seq: int = 128, ckpt_dir: str = "", ckpt_interval: int = 50,
        lr: float = 3e-4, microbatches: int = 1, log_every: int = 10,
        seed: int = 0, kill_at: int = -1):
    cfg = get_reduced(arch) if reduced else get_config(arch)
    if cfg.family == "encdec":
        raise SystemExit("use examples/train_embedder.py families for LM "
                         "training; encdec has its own batch layout")
    shape = ShapeConfig("custom", seq, batch, "train")
    run_cfg = RunConfig(model=cfg, shape=shape,
                        train=TrainConfig(learning_rate=lr,
                                          warmup_steps=min(20, steps // 5)))
    tok = HashTokenizer(cfg.vocab_size)
    stream = lm_token_stream(tok, n_tokens=max(200_000, batch * seq * 4),
                             seed=seed)
    batcher = LMBatcher(stream, batch, seq, seed=seed)
    prefetch = Prefetcher(batcher.batch_at)

    train_step, nmb, mdtype = trainer.make_train_step(
        run_cfg, max_steps=steps, microbatches=microbatches, seq_sp=False)
    train_step = jax.jit(train_step, donate_argnums=(0, 1))
    params, opt_state = trainer.make_states(run_cfg,
                                            key=jax.random.PRNGKey(seed))
    n_params = model.count_params(params)
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"microbatches={nmb}, moments={mdtype}")

    start = 0
    rm = None
    if ckpt_dir:
        rm = RestartManager(ckpt_dir, interval=ckpt_interval)
        (params, opt_state), start = rm.maybe_restore((params, opt_state))
        if start:
            print(f"[train] restored checkpoint, resuming at step {start}")
    wd = StepWatchdog()
    losses = []
    for step in range(start, steps):
        b = prefetch.next()
        wd.start()
        params, opt_state, metrics = train_step(
            params, opt_state, {k: jnp.asarray(v) for k, v in b.items()})
        loss = float(metrics["loss"])
        rep = wd.stop(step)
        losses.append(loss)
        if rep.is_straggler:
            print(f"[watchdog] step {step} straggler: {rep.step_time_s:.2f}s"
                  f" vs mean {rep.mean_s:.2f}s")
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step {step} loss={loss:.4f} "
                  f"gnorm={float(metrics.get('grad_norm', 0)):.2f} "
                  f"({rep.step_time_s:.2f}s)")
        if rm:
            rm.on_step(step, (params, opt_state))
        if kill_at == step:  # fault-injection hook for tests
            print(f"[train] simulated crash at step {step}", flush=True)
            import os
            os._exit(42)
    prefetch.stop()
    if rm:
        rm.finalize(steps - 1, (params, opt_state))
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen25_0_5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--kill-at", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    losses = run(args.arch, reduced=args.reduced, steps=args.steps,
                 batch=args.batch, seq=args.seq, ckpt_dir=args.ckpt_dir,
                 ckpt_interval=args.ckpt_interval, lr=args.lr,
                 microbatches=args.microbatches, kill_at=args.kill_at,
                 seed=args.seed)
    print(f"[train] final loss {losses[-1]:.4f} (from {losses[0]:.4f})")


if __name__ == "__main__":
    main()
