"""End-to-end training driver (CPU-runnable at reduced scale, mesh-ready).

Example (the (b) deliverable end-to-end run):
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
      --steps 300 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

``--no-reduced`` takes the published config with bf16 params built by
``LM.init`` (inside jit, a group at a time); ``--layers N`` cuts it to
its first N layers.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, cut_layers, get_config, get_reduced
from repro.data.pipeline import DataPipeline, PipelineConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import LM
from repro.optim.optimizer import AdamWConfig
from repro.training.checkpoint import Checkpointer
from repro.training.train_loop import TrainConfig, make_train_step
from repro.optim.optimizer import adamw_init


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="yi-9b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced CPU-runnable config (--no-reduced: the "
                         "published one, bf16 params)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the decoder to its first N layers")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--dense", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()

    cfg = (get_reduced if args.reduced else get_config)(
        args.arch, sparse=not args.dense)
    if args.layers is not None:
        cfg = cut_layers(cfg, args.layers)
    lm = LM(cfg)
    tcfg = TrainConfig(
        opt=AdamWConfig(lr=args.lr, warmup_steps=20, total_steps=args.steps),
        microbatches=args.microbatches, remat=args.remat)
    step_fn = jax.jit(make_train_step(lm, tcfg))

    opt_state = adamw_init(params)
    pipe = DataPipeline(PipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch))
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None

    losses = []
    t0 = time.time()
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
        if cfg.encoder_plan is not None:
            batch["enc_input"] = jnp.zeros(
                (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if (step + 1) % args.log_every == 0:
            print(f"step {step+1:5d} loss {losses[-1]:.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.2f} "
                  f"({(time.time()-t0)/ (step+1):.2f}s/step)", flush=True)
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, {"params": params, "opt": opt_state},
                      extra={"data": pipe.state()}, async_=True)
    if ckpt:
        ckpt.wait()
    print(f"first-10 mean loss {np.mean(losses[:10]):.4f} -> "
          f"last-10 mean loss {np.mean(losses[-10:]):.4f}")
    assert np.mean(losses[-10:]) < np.mean(losses[:10]), "loss did not drop"


if __name__ == "__main__":
    main()
