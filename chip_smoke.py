"""Smoke run of the main path on a TPU chip.

    python chip_smoke.py              # one chip: serve, reference, train
    python chip_smoke.py --chips 4    # four chips: sharded training only

One chip (the default):

1. Device: refuses to run unless JAX's first device is a TPU.
2. Serve: StarCoder2-3B at its published widths (30 layers, bf16), random
   weights from ``--seed``, behind ``launch.serve.build_server``: a 4-slot
   ``Engine`` (``max_len`` 1024) and a ``SlotBatcher`` (``check_every``
   8).  8 seeded requests (prompts of 100-500 tokens, two prefill
   buckets, 32 new tokens each) are served with MCA on (alpha 0.2 on
   ``v_proj``), then with MCA off.  Every request must end ``ok``, no
   ``resilience.*`` counter may move, and the KV write must take its
   Pallas kernel with no fallback.
3. Reference: for all 8 prompts, the MCA-off insertion logits must match
   a float32 full forward of the same weights (``forward_hidden`` plus the
   head, matmul precision "highest") within a bf16 tolerance, and the
   first token served must be the insertion's argmax.  The same forward
   over the prompt and the served tokens checks all 32 served tokens of
   each request, 31 of them decoded through the ``kv_slot_update``
   kernel: each must be the reference's best next token or within the
   tolerance of it (random weights make near ties, which bf16 rounding
   may break either way), and at least ``MIN_DECISIVE`` positions must
   have a winner the tolerance cannot overturn.
4. Train: BERT-base at its published widths through ``launch.train.train``
   (``make_train_step`` + ``Trainer``), MCA on, batch 16 x 512, 3 steps:
   finite losses, no skipped step, no rollback.

``--chips 4`` runs the sharded training path of ``repro.dist`` and what it
is compared with, nothing else: a 2-layer full-width cut of StarCoder2-3B
whose losses over two steps (the forward, then the forward after the
mesh's gradient reduction and sharded AdamW update) on one chip must
match those on a (data 1 x model 4) mesh, then the full 30-layer model on that mesh at batch
8 x 1024 for 3 steps with every parameter and Adam leaf spread over the
four chips.  Per-device peak memory is printed.

Times printed along the way are smoke numbers, not benchmark numbers.
The last line of standard output is one JSON object naming the device;
any failure exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import pathlib
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"

# the MCA-off insertion runs the bf16 model (bf16 weights and activations,
# f32 accumulation); the reference runs the same weights in f32.  bf16
# rounds to 2^-9 relative per op, compounded over 30 residual layers: allow
# 5% of the reference logits' largest magnitude
LOGIT_RTOL = 0.05
# served positions (of 8 x 32) where the reference's top-2 gap exceeds the
# logit tolerance, so that only its best token passes: about a third of
# them on random weights; fewer would leave the decode check near vacuous
MIN_DECISIVE = 32
# losses of the 2-layer cut, one chip vs the 4-chip mesh: the same bf16
# math with a different reduction order across the model axis (3.5e-6
# measured on the first step); a wrong gradient reduction or update moves
# the second step's loss by far more
LOSS_RTOL = 1e-4


class SmokeFailure(RuntimeError):
    """A phase found a wrong or missing result."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), from JAX's own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ------------------------------------------------------------------ serve
def _prompts(rng, vocab: int):
    """4 prompts in the 128 bucket (100-128 tokens) and 4 in the 512 bucket
    (257-500 tokens)."""
    import numpy as np
    lens = ([int(n) for n in rng.integers(100, 129, 4)]
            + [int(n) for n in rng.integers(257, 501, 4)])
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


@contextlib.contextmanager
def scatter_kv_writes():
    """Route every KV write traced inside to the XLA scatter fallback."""
    from repro.kernels import cache_update
    keep = cache_update.row_dma_ok
    cache_update.row_dma_ok = lambda shape, dtype: False
    try:
        yield
    finally:
        cache_update.row_dma_ok = keep


def kv_write_check(seed: int) -> None:
    """The KV write kernel vs the scatter it replaces, bit for bit, on a
    2-layer stack of a served layer's cache shape, over an 8-step scan
    like a decode burst."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.kernels import kv_slot_update

    layers, b, s, heads, d_head, steps = 2, 4, 1024, 2, 128, 8
    kc, kn = jax.random.split(jax.random.PRNGKey(seed))
    cache = jax.random.normal(kc, (layers, b, s, heads, d_head),
                              jnp.bfloat16)
    new = jax.random.normal(kn, (steps, layers, b, 1, heads, d_head),
                            jnp.bfloat16)
    pos = jnp.asarray([0, 15, 511, s - steps], jnp.int32)

    def run():                      # a fresh trace per call
        def burst(c):
            def step(c, x):
                i, n = x
                return kv_slot_update(c, n, pos + i), ()
            return jax.lax.scan(step, c, (jnp.arange(steps), new))[0]
        return np.asarray(jax.jit(burst)(cache))

    with obs.scoped() as reg:
        got = run()
        with scatter_kv_writes():
            want = run()
        counters = reg.snapshot()["counters"]
    calls = (counters.get("kernels.kv_slot_update.kernel_calls", 0),
             counters.get("kernels.kv_slot_update.fallback_calls", 0))
    _check(calls == (1, 1), f"kv write check: kernel/fallback calls {calls}")
    _check(np.array_equal(got.view(np.uint16), want.view(np.uint16)),
           "kv write check: the kernel's cache differs from the scatter's")
    _log("kv write check: kernel == scatter bit for bit over 8 steps")


def serve_pass(model, params, prompts, *, mca: bool, seed: int,
               slots: int = 4, max_len: int = 1024, max_new: int = 32,
               kv_kernel: bool = True, clock: CompileClock):
    """Serve ``prompts`` once through the launcher's stack; returns the
    engine and the tokens served per request, for the reference check.
    ``kv_kernel=False`` expects KV writes on the scatter fallback (see
    ``scatter_kv_writes``)."""
    from repro import obs
    from repro.launch.serve import build_server, status_counts
    from repro.serve import Request

    tag = ("on" if mca else "off") + ("" if kv_kernel else ", scatter")
    c0 = clock.seconds
    with obs.scoped() as reg:
        engine, batcher = build_server(model, params, slots=slots,
                                       max_len=max_len, mca=mca,
                                       check_every=8, seed=seed)
        for uid, p in enumerate(prompts):
            _check(batcher.submit(Request(uid=uid, prompt=p,
                                          max_new=max_new)) == "queued",
                   f"serve mca={tag}: request {uid} was not admitted")
        t0 = time.perf_counter()
        done = batcher.run()
        wall = time.perf_counter() - t0
        snap = reg.snapshot()
    counters = snap["counters"]
    counts = status_counts(batcher)
    _check(counts == {"ok": len(prompts)},
           f"serve mca={tag}: statuses {counts}, want all ok")
    _check(all(len(done[u]) == max_new for u in range(len(prompts))),
           f"serve mca={tag}: a request returned the wrong token count")
    moved = {k: v for k, v in counters.items()
             if k.startswith("resilience.") and v}
    _check(not moved, f"serve mca={tag}: resilience counters moved: {moved}")
    kern = counters.get("kernels.kv_slot_update.kernel_calls", 0)
    fall = counters.get("kernels.kv_slot_update.fallback_calls", 0)
    want = (kern >= 1 and fall == 0) if kv_kernel else (kern == 0
                                                         and fall >= 1)
    _check(want, f"serve mca={tag}: kv_slot_update kernel_calls={kern} "
                 f"fallback_calls={fall}")
    compile_s = clock.seconds - c0
    tokens = sum(len(v) for v in done.values())
    _log(f"serve mca={tag}: {len(done)} requests ok, {tokens} tokens, "
         f"wall {wall:.2f}s of which compile {compile_s:.2f}s; smoke "
         f"tokens/s excluding compile "
         f"{tokens / max(wall - compile_s, 1e-9):.1f}; kv_slot_update "
         f"kernel_calls={kern:g} fallback_calls={fall:g}")
    return engine, done


def insertion_logits(engine, prompts, max_new: int = 32):
    """Last-position logits of a fresh insertion of each prompt."""
    import numpy as np
    out = []
    for p in prompts:
        _, logits, _ = engine.prefill_into(p, engine.init_slot_state(), 0,
                                           max_new, mca=False)
        out.append(np.asarray(logits, np.float32))
    return out


def _peak_memory(tag: str) -> None:
    import jax
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
        _log(f"{tag}: device {d.id} peak_bytes_in_use {peak} "
             f"bytes_limit {limit}")
        _check(peak is not None and limit is not None and peak < limit,
               f"{tag}: device {d.id} peak memory {peak} vs {limit}")


def reference_check(cfg, params_host, prompts, got, served) -> None:
    """Insertion logits ``got`` and the tokens ``served`` for ``prompts``
    vs a float32 full forward of the same weights, rebuilt on the device
    from the host copy ``params_host``.  The forward runs over each prompt
    followed by its served tokens, so position j's logits are the
    reference's scores for served token j given the ones before it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.policy import MCAConfig
    from repro.models import build_model
    from repro.models.api import _logits

    cfg32 = cfg.replace(dtype="float32", mca=MCAConfig())
    model32 = build_model(cfg32)
    vocab = cfg32.vocab_size
    n_new = len(served[0])
    params32 = jax.tree.map(
        lambda a: jax.device_put(np.asarray(a, np.float32)), params_host)
    # causal: right padding to one length changes no scored position, and
    # the forward compiles once
    width = -(-max(len(p) for p in prompts) // 64) * 64 + n_new

    @jax.jit
    def forward(p, tokens, n):
        hidden, _, _ = model32.forward_hidden(p, {"tokens": tokens})
        h = jax.lax.dynamic_slice_in_dim(hidden[0], n - 1, n_new)
        return _logits(p, cfg32, h)[:, :vocab]

    refs = []
    with jax.default_matmul_precision("highest"):
        for p, toks in zip(prompts, served):
            seq = np.zeros((1, width), np.int32)
            seq[0, :len(p) + n_new - 1] = np.concatenate(
                [p, np.asarray(toks[:-1], np.int32)])
            refs.append(np.asarray(forward(params32, jnp.asarray(seq),
                                           jnp.int32(len(p)))))
    del params32
    decisive, worst = 0, 0.0
    for p, g, r, toks in zip(prompts, got, refs, served):
        err = float(np.max(np.abs(g - r[0])))
        tol = LOGIT_RTOL * float(np.max(np.abs(r[0])))
        _check(err <= tol, f"reference: prompt of {len(p)} tokens: "
                           f"insertion logits differ by {err} > {tol}")
        _check(toks[0] == int(g.argmax()),
               "reference: served first token is not the insertion argmax")
        best = r.max(axis=-1)
        behind = best - r[np.arange(n_new), toks]   # 0 where toks is best
        gap = best - np.sort(r, axis=-1)[:, -2]
        decisive += int(np.sum(gap > tol))
        worst = max(worst, float(behind.max()))
        _log(f"reference: prompt of {len(p)} tokens: insertion max |logit "
             f"- f32 ref| = {err:.4f} (limit {tol:.4f} = {LOGIT_RTOL} x "
             f"max|ref|); served tokens that are the ref's best "
             f"{int(np.sum(behind == 0))}/{n_new}, decisive positions "
             f"{int(np.sum(gap > tol))}, furthest behind the ref's best "
             f"{float(behind.max()):.4f}")
        _check(bool(np.all(behind <= tol)),
               f"reference: prompt of {len(p)} tokens: served token at "
               f"position {int(behind.argmax())} is {float(behind.max())} "
               f"behind the f32 forward's best, over the tolerance {tol}")
    _check(decisive >= MIN_DECISIVE,
           f"reference: only {decisive} decisive positions, want "
           f">= {MIN_DECISIVE}")
    _log(f"reference: {len(prompts)} prompts x {n_new} served tokens within "
         f"tolerance; {decisive} decisive positions all the ref's best; "
         f"furthest behind {worst:.4f}")


def serve_phase(seed: int, clock: CompileClock) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.policy import MCAConfig
    from repro.models import build_model

    cfg = get_config("starcoder2-3b",
                     mca=MCAConfig(enabled=True, alpha=0.2,
                                   sites=("v_proj",)))
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    prompts = _prompts(np.random.default_rng(seed), cfg.vocab_size)
    kv_write_check(seed)
    serve_pass(model, params, prompts, mca=True, seed=seed, clock=clock)
    engine, done = serve_pass(model, params, prompts, mca=False, seed=seed,
                              clock=clock)
    _peak_memory("serve")
    # the same requests with every KV write on the scatter: the decoded
    # tokens must not depend on which path wrote the cache
    with scatter_kv_writes():
        scattered = serve_pass(model, params, prompts, mca=False, seed=seed,
                               kv_kernel=False, clock=clock)[1]
    differ = [u for u in done if done[u] != scattered[u]]
    _check(not differ, f"serve: requests {differ} decoded differently with "
                       "the scatter KV write")
    _log(f"serve: all {len(done)} requests decode the same tokens with the "
         "KV write kernel and with the scatter")
    got = insertion_logits(engine, prompts)
    # the f32 weights take twice the bf16 bytes: drop every device copy of
    # the bf16 weights first and rebuild from the host
    params_host = jax.device_get(params)
    del engine, params
    reference_check(cfg, params_host, prompts, got,
                    [done[u] for u in range(len(prompts))])


# ------------------------------------------------------------------ train
def _train(arch: str, *, steps: int, batch: int, seq: int, seed: int,
           mca: bool, mesh=None, finite_checks: bool = True, **overrides):
    from repro import obs
    from repro.configs import get_config
    from repro.core.policy import MCAConfig
    from repro.data import SyntheticLM
    from repro.launch.train import train
    from repro.models import build_model
    from repro.optim import adamw
    from repro.train import TrainerConfig

    cfg = get_config(arch, mca=MCAConfig(enabled=mca, alpha=0.2,
                                         sites=("v_proj",)), **overrides)
    model = build_model(cfg)
    data = SyntheticLM(cfg.vocab_size, seq, batch, seed=seed)
    tcfg = TrainerConfig(total_steps=steps, log_every=1,
                         finite_checks=finite_checks)
    with obs.scoped() as reg:
        trainer, out = train(model, adamw.AdamWConfig(lr=1e-4), data, tcfg,
                             mesh=mesh, seed=seed)
        counters = reg.snapshot()["counters"]
    losses = [h["loss"] for h in out["history"]]
    _check(len(losses) == steps and all(math.isfinite(x) for x in losses),
           f"train {arch}: losses {losses}, want {steps} finite")
    return trainer, out, counters


def train_phase(seed: int, clock: CompileClock) -> None:
    c0 = clock.seconds
    trainer, out, counters = _train("bert-base", steps=3, batch=16, seq=512,
                                    seed=seed, mca=True)
    skipped = counters.get("train.skipped_steps", 0)
    rollbacks = counters.get("resilience.train.rollbacks", 0)
    _check(skipped == 0 and rollbacks == 0 and trainer.rollbacks == 0,
           f"train: skipped_steps={skipped} rollbacks={rollbacks}")
    hist = out["history"]
    _log(f"train bert-base mca=on 16x512: losses "
         f"{[round(h['loss'], 4) for h in hist]}; compile "
         f"{clock.seconds - c0:.2f}s; smoke step seconds (steps 2-3) "
         f"{[round(h['dt'], 4) for h in hist[1:]]}")


# --------------------------------------------------------------- 4 chips
def sharded_phase(seed: int, clock: CompileClock) -> None:
    import jax

    from repro.launch.mesh import make_mesh, parse_mesh

    _check(jax.device_count() == 4,
           f"--chips 4 needs 4 devices, found {jax.device_count()}")
    mesh = make_mesh(parse_mesh("1,4", jax.device_count()))

    # full width on the mesh first, so the peaks below are its own;
    # donation (finite checks off, losses checked here) keeps one copy of
    # the 30 GB training state, split over the 4 chips
    c0 = clock.seconds
    trainer, out, _ = _train("starcoder2-3b", steps=3, batch=8, seq=1024,
                             seed=seed, mca=True, mesh=mesh,
                             finite_checks=False)
    hist = out["history"]
    state = {"params": trainer.params, "m": trainer.opt_state["m"],
             "v": trainer.opt_state["v"]}
    leaves = jax.tree_util.tree_leaves_with_path(state)
    absent = [jax.tree_util.keystr(p) for p, a in leaves
              if len(a.sharding.device_set) != 4]
    _check(not absent, f"sharded: leaves not on all 4 devices: {absent[:5]}")
    # the weight matrices and their moments split four ways; norm scales
    # and biases (a few MB) stay replicated by the repro.dist rules
    whole = [(jax.tree_util.keystr(p), a.nbytes) for p, a in leaves
             if a.addressable_shards[0].data.nbytes * 4 != a.nbytes]
    big = [k for k, n in whole if n > 2 ** 20]
    _check(not big, f"sharded: leaves over 1 MiB not split 4 ways: {big[:5]}")
    total = sum(a.nbytes for _, a in leaves)
    per_dev = sum(a.addressable_shards[0].data.nbytes for _, a in leaves)
    _log(f"sharded: starcoder2-3b {trainer.model.cfg.n_layers} layers "
         f"mca=on mesh(1x4) 8x1024: losses "
         f"{[round(h['loss'], 4) for h in hist]}; compile "
         f"{clock.seconds - c0:.2f}s; smoke step seconds (steps 2-3) "
         f"{[round(h['dt'], 4) for h in hist[1:]]}; params+Adam "
         f"{total / 1e9:.2f} GB total, {per_dev / 1e9:.2f} GB on device 0; "
         f"{len(leaves) - len(whole)} of {len(leaves)} leaves split 4 ways, "
         f"the rest replicated ({sum(n for _, n in whole) / 1e6:.2f} MB)")
    _check(per_dev <= 0.26 * total,
           "sharded: training state is not split four ways")
    _peak_memory("sharded")
    del trainer, state, leaves

    # losses of a 2-layer full-width cut over two steps: one chip vs the
    # mesh; step 2 follows the gradient reduction and the AdamW update
    # (MCA off: the mesh draws different Monte-Carlo samples per shard)
    cut = dict(steps=2, batch=8, seq=1024, seed=seed, mca=False, n_layers=2)
    one = [h["loss"] for h in _train("starcoder2-3b", **cut)[1]["history"]]
    four = [h["loss"] for h in
            _train("starcoder2-3b", mesh=mesh, **cut)[1]["history"]]
    for step, (a, b) in enumerate(zip(one, four), 1):
        rel = abs(a - b) / abs(a)
        _log(f"sharded: 2-layer cut step {step} loss one chip {a:.6f} vs "
             f"mesh(1x4) {b:.6f}; relative difference {rel:.2e} (limit "
             f"{LOSS_RTOL:g})")
        _check(rel <= LOSS_RTOL,
               f"sharded: step {step} loss differs by {rel:.2e}")


# ------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _log(f"device {device}; compile cache {cache_dir}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1

    clock = CompileClock()
    t0 = time.perf_counter()
    phases = ([sharded_phase] if args.chips == 4
              else [serve_phase, train_phase])
    try:
        for phase in phases:
            p0 = time.perf_counter()
            phase(args.seed, clock)
            _log(f"{phase.__name__} passed in "
                 f"{time.perf_counter() - p0:.1f}s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _log(f"all phases passed in {time.perf_counter() - t0:.1f}s; compile "
         f"(trace + lower + compile or cache load) {clock.seconds:.1f}s, "
         f"persistent-cache hits {clock.cache_hits}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
