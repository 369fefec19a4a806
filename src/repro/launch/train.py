"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant driver on whatever devices exist (the e2e example
trains a ~100M-param model for a few hundred steps on CPU; on a real pod the
same entry point uses the production mesh + sharded step).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="tinyllama-1.1b")
    p.add_argument("--preset", default="byp",
                   help="linkage preset: linux|base|byp|ret_byp|nss|"
                        "ret_byp_shortcut|nss_shortcut")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--smoke", action="store_true",
                   help="reduced same-family config (CPU-sized)")
    p.add_argument("--scale", type=float, default=1.0,
                   help="width multiplier on the smoke config (e2e example "
                        "uses ~8 for a ~100M model)")
    p.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--nss-steps", type=int, default=4)
    p.add_argument("--data-mesh", type=int, default=0,
                   help="shard batch over this many devices (0 = single)")
    p.add_argument("--report-json", default=None)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.core import LinkageConfig, build_train_step, init_train_state, preset
    from repro.data import DataConfig, Pipeline
    from repro.models import ModelOptions
    from repro.optim import AdamWConfig
    from repro.runtime import DriverConfig, train

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
        if args.scale != 1.0:
            s = args.scale
            cfg = dataclasses.replace(
                cfg,
                name=cfg.name + f"-x{s:g}",
                d_model=int(cfg.d_model * s),
                d_ff=int(cfg.d_ff * s),
                d_head=cfg.d_head if cfg.n_heads == 0 else int(cfg.d_model * s) // cfg.n_heads,
                vocab_size=max(cfg.vocab_size, 8192),
                num_blocks=min(get_config(args.arch).num_blocks, 8),
            )
    lk = preset(args.preset)
    if lk.nss_steps != args.nss_steps:
        lk = dataclasses.replace(lk, nss_steps=args.nss_steps)
    opts = ModelOptions(attn_impl="ref", scan_impl="ref", dtype=jnp.float32)
    if lk.shortcut:
        opts = lk.model_options(opts, on_tpu=jax.default_backend() == "tpu")
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                       total_steps=args.steps)

    n_params_cfg = cfg.param_count()
    print(f"arch={cfg.name} params={n_params_cfg/1e6:.1f}M "
          f"linkage={args.preset} steps={args.steps}")

    state = init_train_state(jax.random.PRNGKey(0), cfg, ocfg)
    step = build_train_step(cfg, opts, ocfg, lk)
    pipe = Pipeline(cfg, DataConfig(global_batch=args.global_batch,
                                    seq_len=args.seq_len))
    dcfg = DriverConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                        ckpt_dir=args.ckpt_dir)
    t0 = time.time()
    rep = train(step.fn, state, pipe, lk, dcfg)
    dt = time.time() - t0
    tok_s = rep.steps_run * args.global_batch * args.seq_len / dt
    print(f"done: steps={rep.steps_run} wall={dt:.1f}s tokens/s={tok_s:.0f} "
          f"first_loss={rep.losses[0]:.4f} last_loss={rep.losses[-1]:.4f} "
          f"restarts={rep.restarts}")
    if args.report_json:
        with open(args.report_json, "w") as f:
            json.dump({"arch": cfg.name, "preset": args.preset,
                       "steps": rep.steps_run, "wall_s": dt,
                       "tokens_per_s": tok_s, "losses": rep.losses,
                       "restarts": rep.restarts}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
