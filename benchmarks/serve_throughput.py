"""Serving throughput: per-slot continuous batching vs the wave batcher.

A ragged Zipf-ish workload (mostly short prompts, a heavy tail of long
ones — the regime continuous batching exists for) is served twice on the
same engine shape:

* ``wave`` — ``ContinuousBatcher``: every wave prefills at the wave's max
  prompt length across all slots and decodes to the wave's max ``max_new``.
* ``per_slot`` — ``SlotBatcher``: each request prefills once (batch=1,
  pow-2 bucket) into its own slot; nothing is re-encoded.

Each row reports end-to-end ``tokens_per_s``, the prefill token count
(``prefill_tokens`` — proportional to prefill FLOPs at fixed model shape),
``prefill_flops_ratio`` (wave tokens / this row's tokens; the acceptance
bar is >= 1.5x for per_slot), insertion counters, and ``parity_ok``:
every request's tokens must be identical to a solo batch=1 generation
(MCA off — capacity routing couples batch rows by design, so token
identity is only defined for the exact path).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.models import build_model, reduced
from repro.serve import ContinuousBatcher, Engine, Request, SlotBatcher

BATCH = 4
MAX_LEN = 96
N_REQ = 12
SEED = 3          # Zipf draw with a long prompt per wave-of-4 (see module
                  # docstring; ratio is workload-dependent by design)


def _workload(vocab):
    rng = np.random.default_rng(SEED)
    lens = np.minimum(3 + rng.zipf(1.5, N_REQ), 48)
    max_news = 4 + rng.integers(0, 7, N_REQ)
    prompts = [rng.integers(1, vocab, size=int(n)).astype(np.int32)
               for n in lens]
    return prompts, [int(m) for m in max_news]


def _serve(batcher_cls, eng, prompts, max_news, **kw):
    reg = obs.Registry()
    with obs.scoped(reg):
        b = batcher_cls(eng, **kw)
        for i, (p, m) in enumerate(zip(prompts, max_news)):
            assert b.submit(Request(uid=i, prompt=p, max_new=m)) == "queued"
        t0 = time.perf_counter()
        out = b.run()
        wall = time.perf_counter() - t0
    snap = reg.snapshot()
    assert all(b.status[i] == "ok" for i in range(len(prompts))), b.status
    if obs.tracing_enabled():
        # the serve spans landed in this scoped registry; copy them out to
        # the ambient one so run.py's --trace-out export sees the chains
        ambient = obs.get_registry()
        for s in reg.spans():
            ambient.add_span(s)
    return out, wall, snap["counters"]


def run(fast: bool = True, smoke: bool = False):
    del fast, smoke          # one scale: the workload IS the benchmark
    cfg = reduced(get_config("starcoder2-3b"), n_layers=2, vocab_size=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts, max_news = _workload(cfg.vocab_size)
    n_tokens = sum(max_news)

    # solo reference: each request alone on a batch=1 engine (ground truth
    # for parity — continuous batching must not change anyone's tokens)
    solo = Engine(model, params, batch_size=1, max_len=MAX_LEN)
    ref = {i: solo.generate(p[None, :], m, mca=False)[0].tolist()
           for i, (p, m) in enumerate(zip(prompts, max_news))}

    rows = []
    walls = {}
    for name, cls, kw in (("wave", ContinuousBatcher, {}),
                          ("per_slot", SlotBatcher, {"check_every": 8})):
        eng = Engine(model, params, batch_size=BATCH, max_len=MAX_LEN)
        # warmup pass populates the engine's jit caches (per-bucket
        # insertion, burst) so tokens_per_s is steady-state, not compile
        _serve(cls, eng, prompts, max_news, **kw)
        out, wall, c = _serve(cls, eng, prompts, max_news, **kw)
        walls[name] = wall
        rows.append({
            "batcher": name,
            "tokens_per_s": n_tokens / wall,
            "prefill_tokens": c.get("serve.prefill_tokens", 0.0),
            "prefill_tokens_saved": c.get("serve.prefill_tokens_saved",
                                          0.0),
            "insertions": c.get("serve.insertions", 0.0),
            "slot_idle_steps": c.get("serve.slot_idle_steps", 0.0),
            "slot_utilization": 1.0 - c.get("serve.slot_idle_steps", 0.0)
            / c["serve.slot_steps"],
            "parity_ok": all(out.get(i) == ref[i] for i in ref),
        })
    wave_tokens = rows[0]["prefill_tokens"]
    for r in rows:
        r["prefill_flops_ratio"] = (wave_tokens
                                    / max(r["prefill_tokens"], 1.0))
    return {"n_requests": N_REQ, "n_tokens": n_tokens, "batch": BATCH,
            "max_len": MAX_LEN, "rows": rows}


if __name__ == "__main__":
    import json
    print(json.dumps(run(), indent=1, default=float))
