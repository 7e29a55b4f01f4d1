"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b \
        --steps 200 --batch 32 --seq 1024 [--mca --alpha 0.2] \
        [--mesh 1,4] [--n-micro 4] [--ckpt-dir ckpts/run1]

``--mesh D,M`` lays the local devices out as D-way data x M-way model
(tensor) parallelism; the default with several devices is pure data
parallelism ``(n_devices, 1)``.  On CPU it trains reduced configs for
smoke/examples.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging

import jax

from repro.configs import get_config
from repro.core.policy import MCAConfig
from repro.data import SyntheticLM
from repro.dist import context as dctx
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh, parse_mesh
from repro.models import build_model, reduced
from repro.optim import adamw
from repro.train import Trainer, TrainerConfig
from repro.train.step import (jit_train_step, make_train_step,
                              train_step_shardings)


def train(model, opt_cfg, data, tcfg: TrainerConfig, *, mesh=None,
          n_micro: int = 1, seed: int = 0):
    """Build the train step and a ``Trainer`` and run it.

    With a multi-device ``mesh`` the step is jitted with the ``repro.dist``
    shardings and params / AdamW moments are created directly in them, so
    no device ever holds the whole training state.  Params come from
    ``seed``.  Returns ``(trainer, Trainer.run() result)``.
    """
    # the finite-check skip/rollback path reuses pre-step buffers, which
    # donation would have freed on device — only donate when the guard is
    # off (Trainer rejects the inconsistent combination at init)
    donate = not tcfg.finite_checks
    key = jax.random.PRNGKey(seed)
    if mesh is None or mesh.size == 1:
        step = jax.jit(make_train_step(model, opt_cfg, n_micro=n_micro,
                                       seed=seed),
                       donate_argnums=(0, 1) if donate else ())
        trainer = Trainer(model, opt_cfg, data, step, tcfg,
                          init_params=jax.jit(model.init)(key),
                          step_donates=donate)
        return trainer, trainer.run()
    with dctx.use_mesh(mesh):
        a_batch = jax.eval_shape(
            lambda: jax.tree.map(jax.numpy.asarray, data.batch(0)))
        step = jit_train_step(mesh, model, opt_cfg, a_batch,
                              n_micro=n_micro, seed=seed, donate=donate)
        (p_sh, o_sh, _), _ = train_step_shardings(mesh, model, a_batch)
        params = jax.jit(model.init, out_shardings=p_sh)(key)
        trainer = Trainer(model, opt_cfg, data, step, tcfg,
                          init_params=params, step_donates=donate)
        # AdamW's step counter starts on one device; the step returns it
        # on the mesh, and that change of input sharding would compile
        # the step a second time at step 2
        trainer.opt_state = jax.device_put(trainer.opt_state, o_sh)
        return trainer, trainer.run()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--mca", action="store_true")
    ap.add_argument("--alpha", type=float, default=0.2)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke-size) config")
    ap.add_argument("--mesh", default=None,
                    help="data,model mesh sizes, e.g. 1,4 (default: "
                         "n_devices,1)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-file", default=None,
                    help="optional memmap token file (data/write_token_file)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    enable_compile_cache()

    mca = MCAConfig(enabled=args.mca, alpha=args.alpha, sites=("v_proj",))
    cfg = get_config(args.arch, mca=mca)
    if args.reduced:
        # d_model 128: a 16-wide block leaves MCA several blocks to sample
        cfg = reduced(cfg, mca=dataclasses.replace(mca, block=16))
    model = build_model(cfg)

    if args.data_file:
        from repro.data import MemmapLM
        data = MemmapLM(args.data_file, cfg.vocab_size, args.seq,
                        args.batch, seed=args.seed)
    else:
        data = SyntheticLM(cfg.vocab_size, args.seq, args.batch,
                           seed=args.seed)

    opt_cfg = adamw.AdamWConfig(
        lr=args.lr, schedule=adamw.cosine_schedule(
            warmup=max(args.steps // 20, 1), total=args.steps))
    tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every, log_every=10)
    mesh = make_mesh(parse_mesh(args.mesh, jax.device_count()))
    _, out = train(model, opt_cfg, data, tcfg, mesh=mesh,
                   n_micro=args.n_micro, seed=args.seed)
    print(f"finished {out['steps']} steps in {out['wall_s']:.1f}s; "
          f"final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
