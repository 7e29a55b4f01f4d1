"""Serving launcher: batched generation with the continuous batcher.

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b \
        --reduced --requests 8 --max-new 16 [--mca --alpha 0.2] \
        [--per-slot [--check-every 8]]

``--per-slot`` serves with the ``SlotBatcher`` (per-request prefill
insertion + sync-free decode bursts) instead of the legacy wave batcher.
Prints the request count per terminal status and exits 1 if any request
ended ``failed``.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import sys
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.policy import MCAConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model, reduced
from repro.serve import ContinuousBatcher, Engine, Request, SlotBatcher
from repro.serve.engine import FAILED


def build_server(model, params, *, slots: int, max_len: int, mca: bool,
                 per_slot: bool = True, check_every: int = 8,
                 seed: int = 0):
    """The serving stack: an ``Engine`` over ``slots`` decode slots and the
    batcher in front of it.  Returns ``(engine, batcher)``."""
    engine = Engine(model, params, batch_size=slots, max_len=max_len,
                    mca_enabled=mca, seed=seed)
    batcher = (SlotBatcher(engine, check_every=check_every) if per_slot
               else ContinuousBatcher(engine))
    return engine, batcher


def status_counts(batcher) -> dict:
    """Requests per terminal status (``ok``, ``degraded``, ``failed``...)."""
    return dict(collections.Counter(batcher.status.values()))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--mca", action="store_true")
    ap.add_argument("--alpha", type=float, default=0.2)
    ap.add_argument("--per-slot", action="store_true",
                    help="use the per-slot SlotBatcher")
    ap.add_argument("--check-every", type=int, default=8,
                    help="decode burst length for --per-slot")
    args = ap.parse_args()
    enable_compile_cache()

    mca = MCAConfig(enabled=args.mca, alpha=args.alpha, sites=("v_proj",))
    cfg = get_config(args.arch, mca=mca)
    if args.reduced:
        # d_model 128: a 16-wide block leaves MCA several blocks to sample
        cfg = reduced(cfg, mca=dataclasses.replace(mca, block=16))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, batcher = build_server(model, params, slots=args.batch,
                              max_len=args.max_len, mca=args.mca,
                              per_slot=args.per_slot,
                              check_every=args.check_every)

    rng = np.random.default_rng(0)
    t0 = time.time()
    for uid in range(args.requests):
        batcher.submit(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len),
            max_new=args.max_new))
    done = batcher.run()
    dt = time.time() - t0
    tokens = sum(len(v) for v in done.values())
    counts = status_counts(batcher)
    print(f"served {len(done)} requests / {tokens} tokens "
          f"in {dt:.2f}s ({tokens / dt:.1f} tok/s); by status: {counts}")
    for uid in sorted(done)[:3]:
        print(f"  req {uid}: {done[uid][:8]}...")
    if counts.get(FAILED):
        sys.exit(1)


if __name__ == "__main__":
    main()
