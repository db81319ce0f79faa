"""End-to-end training driver.

Small-scale real run (CPU/laptop):
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b --reduced \
      --steps 100 --batch 8 --seq 128

On real hardware the same driver takes ``--mesh-data/--mesh-model`` to build
a device mesh and shard via the production policy. Fault tolerance:
``--ckpt-dir`` enables periodic checkpoints + resume; ``--kill-at-step``
injects a failure to exercise restart; ``--compress-grads sp2_8`` enables
SPx gradient compression with error feedback (cross-pod DP reduction).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.data.tokens import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import encdec as ed
from repro.models import lm as lm_mod
from repro.runtime import Runtime
from repro.training import (GradCompressor, TrainConfig, TrainLoop,
                            make_optimizer)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="family-preserving small config (CPU-runnable)")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["sgd", "adamw", "adamw_q8"])
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--kill-at-step", type=int, default=None)
    ap.add_argument("--compress-grads", default=None,
                    help="SPx scheme for gradient compression, e.g. sp2_8")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, d_model=args.d_model,
                      n_layers=args.layers or None)
    rt = Runtime(impl="auto", q_chunk=min(1024, args.seq))

    data = TokenStream(cfg.vocab_size, args.batch, args.seq, seed=args.seed)
    loss_mod = ed.encdec_loss if cfg.enc_dec else lm_mod.lm_loss

    def loss_fn(params, batch, rt):
        if cfg.enc_dec and "frames" not in batch:
            b = batch["tokens"].shape[0]
            batch = dict(batch, frames=jnp.zeros(
                (b, cfg.enc_seq_len, cfg.d_model), jnp.float32))
        loss, metrics = loss_mod(params, batch, cfg, rt)
        return loss, metrics

    def init_params():
        key = jax.random.PRNGKey(args.seed)
        if cfg.enc_dec:
            return ed.encdec_init(key, cfg)
        return lm_mod.lm_init(key, cfg)

    comp = GradCompressor(args.compress_grads) if args.compress_grads else None
    tc = TrainConfig(max_steps=args.steps, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every, log_every=10,
                     accum_steps=args.accum, kill_at_step=args.kill_at_step,
                     compress_grads=args.compress_grads)
    loop = TrainLoop(loss_fn, make_optimizer(args.optimizer, lr=args.lr),
                     init_params, iter(data), tc, compressor=comp, rt=rt)
    try:
        params, hist = loop.run()
        uniform = float(jnp.log(jnp.float32(cfg.vocab_size)))
        print(f"[train] done: {hist[-1]['loss']:.4f} final loss "
              f"(uniform={uniform:.2f})")
        return hist
    finally:
        data.close()


if __name__ == "__main__":
    main()
