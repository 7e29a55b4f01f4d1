"""Training cells: the program's own path (``launch.train.train`` ->
``Trainer`` -> ``make_train_step``) fed by the mix.

Set-up builds one trainer with ``train(..., total_steps=0)``, hands it the
benchmark's weights, and drives it through its first ``check.steps`` steps
with ``Trainer.run()``: those steps compile the step program and are what
the reference follows.  The window then calls the same ``run()`` one step
at a time until ``seconds`` have passed, so its rate counts every step and
all the time between them (data, dispatch, the loss read back, the
trainer's own bookkeeping).
"""
from __future__ import annotations

import functools
import time

import numpy as np

from . import traffic as gen


class TimedRows:
    """The trainer's data source: rows from the seed, each ``batch()``
    timed and annotated, and the first ``keep`` batches kept for the
    reference."""

    def __init__(self, mix: dict, seed: int, vocab: int, keep: int):
        self.rows = gen.TrainRows(mix, seed, vocab)
        self.keep = keep
        self.kept = []
        self.seconds = []

    def batch(self, step: int):
        from jax.profiler import TraceAnnotation
        t = time.perf_counter()
        with TraceAnnotation("bench.data_batch"):
            b = self.rows.next()
        self.seconds.append(time.perf_counter() - t)
        if len(self.kept) < self.keep:
            self.kept.append({k: v.copy() for k, v in b.items()})
        return b


def leaf_norms(tree) -> dict:
    """{path: float32 Frobenius norm} of every leaf, in one jitted call."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = _norms()(tuple(a for _, a in flat))
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, np.asarray(norms))}


@functools.cache
def _norms():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda leaves: jnp.stack(
        [jnp.linalg.norm(a.astype(jnp.float32).ravel()) for a in leaves]))


class TrainCell:
    def __init__(self, model_dict: dict, arch: str, mix: dict, seed: int):
        import jax

        from repro.configs import get_config
        from repro.core.policy import MCAConfig
        from repro.launch.train import train
        from repro.models import build_model
        from repro.optim import adamw
        from repro.train import TrainerConfig

        from . import weights

        self.mix, self.seed = mix, seed
        self.m = model_dict
        mca = MCAConfig(enabled=bool(mix["mca"]), alpha=mix.get("alpha", 0.2),
                        sites=("v_proj",))
        model = build_model(get_config(arch, mca=mca, **model_dict))
        self.opt = dict(mix["optimizer"])
        n_check = mix["check"]["steps"]
        self.rows = TimedRows(mix, seed, model_dict["vocab_size"], n_check)
        tcfg = TrainerConfig(total_steps=0, log_every=1 << 30,
                             **mix.get("trainer", {}))
        self.trainer, _ = train(model, adamw.AdamWConfig(**self.opt),
                                self.rows, tcfg, seed=seed)
        self.abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        p0 = weights.make(self.abstract, seed)
        self.trainer.params = p0
        # the first steps: step 1, the gradient as the optimizer got it
        # (its first moment after one step), then the rest
        self.steps(1)
        b1 = self.opt["b1"]
        self.grad_norms = {k: v / (1.0 - b1) for k, v in
                           leaf_norms(self.trainer.opt_state["m"]).items()}
        self.steps(n_check - 1)
        self.losses = [h["loss"] for h in self.trainer.history]
        self.change_norms = leaf_norms(jax.tree.map(
            lambda a, b: a.astype(np.float32) - b.astype(np.float32),
            self.trainer.params, p0))
        del p0

    def steps(self, n: int) -> None:
        """``n`` more steps through ``Trainer.run()``."""
        from jax.profiler import TraceAnnotation
        if n > 0:
            self.trainer.cfg.total_steps = n
            with TraceAnnotation("bench.trainer_run"):
                self.trainer.run()

    def window(self, seconds: float, on_open=None, on_close=None) -> dict:
        n_hist = len(self.trainer.history)
        n_data = len(self.rows.seconds)
        if on_open:
            on_open()
        t0 = t = time.perf_counter()
        steps = 0
        while t - t0 < seconds:
            self.steps(1)
            steps += 1
            t = time.perf_counter()
        if on_close:
            on_close()
        hist = self.trainer.history[n_hist:]
        return {"t0": t0, "t_end": t, "steps": steps,
                "statuses": [h["status"] for h in hist],
                "data_s": self.rows.seconds[n_data:],
                "tokens": steps * self.mix["batch"] * self.mix["seq"]}
