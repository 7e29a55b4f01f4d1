"""Serving: prefill + decode engine with per-slot continuous batching.

The engine wraps Model.prefill/Model.decode into jitted, cache-donating
steps.  Two batchers multiplex requests onto fixed decode slots:

* ``ContinuousBatcher`` — the legacy *wave* batcher: whenever a slot
  frees, prefill is re-run for the whole wave (every in-flight request is
  re-encoded).  Kept as the reference implementation and degradation
  oracle.
* ``SlotBatcher`` — real per-slot continuous batching (vLLM-style):
  ``Engine.prefill_into`` encodes ONE request (batch=1, MCA on, with the
  existing ragged masking/RoPE offsets) and splices its K/V pages and
  position state into the shared decode cache at a fixed slot index
  (``models.api.cache_insert_slot`` + the ``kernels.kv_slot_update``
  slot-sliced cache write), so occupied slots keep decoding while a freed
  slot admits the next queued request without touching anyone else's
  state.  The decode loop is sync-free on the hot path: per-row position,
  max-new countdown and finite flags live on device inside a
  ``lax.scan`` burst of K steps (``check_every``; K=1 under active chaos
  so fault-detection semantics match the per-step engine), and the host
  syncs once per burst to harvest tokens, admit queued work and check
  deadlines.

Ragged prompts are LEFT-padded with ``pad_id`` and per-row ``pos_offset``
amounts are threaded through prefill/decode: padding keys are masked out
of attention and RoPE/positions count from the first real token, so a
short prompt batched with a long one generates exactly what it would
alone (MCA off; with MCA on, capacity routing couples rows of a batch by
design).

Robustness (see ROADMAP.md § Robustness):

* **Admission control** — ``submit`` validates prompt length against the
  KV-cache capacity (``len(prompt) + max_new <= max_len``) and a bounded
  queue; rejected requests get ``status="rejected"`` with a reason and a
  ``serve.rejected.<reason>`` counter instead of crashing a wave later.
  Waves are assembled capacity-aware: a wave runs at the max prompt
  length / max ``max_new`` over its members, so requests that would
  jointly overrun ``max_len`` are deferred to the next wave rather than
  batched into a guaranteed failure.
* **Deadlines** — a request carrying ``deadline_s`` that has not finished
  within that budget of submission is dropped with ``status="timeout"``.
* **Degradation ladder** — a wave that raises or produces non-finite
  logits is retried (with backoff) with MCA *disabled*: exact attention
  reconstructs what the Monte-Carlo estimator corrupted (requests finish
  ``degraded`` rather than ``failed``).  Only when the exact retry also
  fails is the wave marked ``failed`` — the batcher never crashes.
* Per-request terminal status: ``ok | degraded | timeout | rejected |
  failed`` (on ``Request.status`` and ``ContinuousBatcher.status``).

Serving metrics land in the ``repro.obs`` registry: ``serve.prefill_seconds``,
``serve.decode_step_seconds``, ``serve.generated_tokens``,
``serve.prefill_tokens``, ``serve.insertions``,
``serve.prefill_tokens_saved``, ``serve.slot_idle_steps``,
``serve.flops_reduction``, ``serve.tier_occupancy.t{i}``, per-wave
``serve.wave_seconds`` / ``serve.slot_utilization`` (live-slot occupancy:
the fraction of slot-steps spent decoding real requests), admission
counters ``serve.rejected.*`` and recovery counters ``resilience.serve.*``.
Dummy padding slots in a partial wave are excluded from token and MCA
FLOPs accounting.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, resilience
from repro.models.api import Model, _logits, cache_insert_slot

log = logging.getLogger("repro.serve")

# terminal request statuses
OK, DEGRADED, TIMEOUT, REJECTED, FAILED = (
    "ok", "degraded", "timeout", "rejected", "failed")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # [S] int32
    max_new: int = 16
    deadline_s: Optional[float] = None    # wall budget from submit()
    out: Optional[List[int]] = None
    status: str = "queued"
    reason: Optional[str] = None          # set when rejected/failed
    submit_t: float = 0.0
    submit_pc: float = 0.0                # perf_counter stamp (tracing)


@dataclasses.dataclass
class SlotState:
    """Device-resident per-slot decode state for ``SlotBatcher``.

    All bookkeeping a decode step needs lives here so the hot loop never
    syncs to host: ``tok`` is each slot's last accepted token, ``t`` its
    next cache write position, ``steps_left`` its remaining decode-step
    budget (0 = idle slot; idle rows emit ``pad_id`` and do not advance).
    """

    cache: Any
    tok: jax.Array           # [B, 1] int32
    t: jax.Array             # [B] int32
    steps_left: jax.Array    # [B] int32


class Engine:
    def __init__(self, model: Model, params, batch_size: int, max_len: int,
                 mca_enabled: bool = False, seed: int = 0, pad_id: int = 0,
                 decode_obs_every: int = 8):
        self.model = model
        self.params = params
        self.batch = batch_size
        self.max_len = max_len
        self.pad_id = pad_id
        self.mca_enabled = mca_enabled
        self.decode_obs_every = max(1, decode_obs_every)
        self.key = jax.random.PRNGKey(seed) if mca_enabled else None

        cfg = model.cfg

        def make_prefill(key):
            def prefill(params, batch_in):
                cache, hidden, stats = model.prefill(params, batch_in,
                                                     max_len, key)
                return cache, _logits(params, cfg, hidden[:, -1:]), stats
            return jax.jit(prefill)

        def decode(params, tok, cache, t):
            return model.decode(params, tok, cache, t)

        def decode_step(params, tok, cache, t, bad):
            # fused decode + argmax + finite-flag accumulation: the host
            # never has to pull logits to pick the next token or check
            # health, so the loop is dispatch-bound
            logits, cache = model.decode(params, tok, cache, t)
            nxt = jnp.argmax(logits[..., :cfg.vocab_size],
                             axis=-1).astype(jnp.int32)
            bad = bad | ~jnp.all(jnp.isfinite(logits))
            return nxt, cache, t + jnp.int32(1), bad

        def make_prefill_into(key):
            def prefill_into(params, prompt, pos_offset, cache, tok, t,
                             steps_left, slot, new_steps):
                batch_in = {"tokens": prompt, "pos_offset": pos_offset}
                new_cache, hidden, stats = model.prefill(params, batch_in,
                                                         max_len, key)
                logits = _logits(params, cfg, hidden[:, -1:])
                cache = cache_insert_slot(cache, new_cache, slot)
                tok0 = jnp.argmax(logits[..., :cfg.vocab_size],
                                  axis=-1).astype(jnp.int32)
                tok = jax.lax.dynamic_update_slice(tok, tok0, (slot, 0))
                t = jax.lax.dynamic_update_slice(
                    t, jnp.full((1,), prompt.shape[1], jnp.int32), (slot,))
                steps_left = jax.lax.dynamic_update_slice(
                    steps_left, new_steps[None], (slot,))
                return cache, tok, t, steps_left, logits, stats
            return jax.jit(prefill_into, donate_argnums=(3, 4, 5, 6))

        def kill(steps_left, slot):
            return jax.lax.dynamic_update_slice(
                steps_left, jnp.zeros((1,), jnp.int32), (slot,))

        self._prefill = make_prefill(self.key)
        # exact-attention fallback path for the degradation ladder (same
        # trace as an MCA-off engine, so fallback output is token-identical)
        self._prefill_exact = (self._prefill if self.key is None
                               else make_prefill(None))
        self._decode = jax.jit(decode, donate_argnums=(2,))
        self._decode_step = jax.jit(decode_step, donate_argnums=(2, 3, 4))
        self._prefill_into = make_prefill_into(self.key)
        self._prefill_into_exact = (self._prefill_into if self.key is None
                                    else make_prefill_into(None))
        self._kill = jax.jit(kill)
        self._bursts: Dict = {}          # (k, eos_id) -> jitted scan burst
        # perf_counter windows of the most recent prefill / decode loop /
        # insertion / burst — batchers read these to attribute per-request
        # tracing spans without re-timing the jit calls
        self.last_prefill_t = (0.0, 0.0)
        self.last_decode_t = (0.0, 0.0)
        self.last_insert_t = (0.0, 0.0)
        self.last_burst_t = (0.0, 0.0)

    def _record_mca(self, stats, frac: float) -> None:
        """frac: fraction of batch rows that are real requests — dummy
        padding slots must not inflate MCA FLOPs accounting."""
        reg = obs.get_registry()
        exact = float(stats["exact_flops"]) * frac
        mca = float(stats["mca_flops"]) * frac
        reg.counter("serve.mca_exact_flops").inc(exact)
        reg.counter("serve.mca_flops").inc(mca)
        # no MCA accounting (disabled / exact-only sites) -> neutral 1x
        reg.gauge("serve.flops_reduction").set(
            exact / mca if mca > 0 else 1.0)
        hist = np.asarray(stats["tier_hist"])
        for i, c in enumerate(hist):
            reg.counter(f"serve.tier_occupancy.t{i}").inc(float(c) * frac)

    def generate(self, prompts: np.ndarray, max_new: int,
                 greedy: bool = True,
                 prompt_lens: Optional[np.ndarray] = None,
                 n_real: Optional[int] = None,
                 mca: bool = True,
                 check_finite: bool = True) -> np.ndarray:
        """prompts: [B, S] (left-padded if ragged). Returns [B, max_new]
        generated ids.  prompt_lens: optional [B] real prompt lengths —
        rows shorter than S get position offsets so left-padding is
        invisible to the model.  n_real: rows that are real requests (the
        rest are dummy padding slots, excluded from token/FLOPs metrics).
        mca=False forces the exact-attention prefill (degradation ladder).
        Raises :class:`resilience.NonFiniteError` if check_finite is set
        and logits come back NaN/Inf."""
        reg = obs.get_registry()
        b, s = prompts.shape
        assert b == self.batch
        if s + max_new > self.max_len:
            raise ValueError(
                f"prompt length {s} + max_new {max_new} overruns the "
                f"KV cache (max_len={self.max_len})")
        n_real = b if n_real is None else n_real
        batch_in = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if prompt_lens is not None:
            lens = np.asarray(prompt_lens, np.int32)
            assert lens.shape == (b,)
            if (lens < s).any():
                batch_in["pos_offset"] = jnp.asarray(s - lens, jnp.int32)
        prefill = self._prefill if mca else self._prefill_exact
        t0p = time.perf_counter()
        with obs.trace("engine.prefill"):
            cache, logits, stats = prefill(self.params, batch_in)
            logits = jax.block_until_ready(logits)
        t1p = time.perf_counter()
        reg.histogram("serve.prefill_seconds").observe(t1p - t0p)
        self.last_prefill_t = (t0p, t1p)
        obs.record_span("prefill", t0p, t1p, cat="serve.engine",
                        track="engine",
                        args={"batch": b, "s": int(s), "mca": bool(mca)})
        logits = resilience.inject("serve.prefill", logits)
        if check_finite:
            resilience.check_finite(logits, "prefill logits")
        self._record_mca(stats, n_real / b)
        reg.counter("serve.prefill_tokens").inc(b * s)
        # int32 cast hoisted out of the loop; position and finite flags stay
        # on device — the only host syncs are the K-step latency observes
        tok = jnp.argmax(jnp.asarray(logits)[..., :self.model.cfg.vocab_size],
                         axis=-1).astype(jnp.int32)
        outs = [tok]
        t_dev = jnp.asarray(s, jnp.int32)
        bad = jnp.zeros((), bool)
        hist = reg.histogram("serve.decode_step_seconds")
        obs_every = self.decode_obs_every
        since = 0
        t0d = t_last = time.perf_counter()
        with obs.trace("engine.decode_loop"):
            resilience.inject("serve.decode")
            for _ in range(max_new - 1):
                tok, cache, t_dev, bad = self._decode_step(
                    self.params, tok, cache, t_dev, bad)
                outs.append(tok)
                since += 1
                if since == obs_every:
                    jax.block_until_ready(tok)
                    now = time.perf_counter()
                    hist.observe((now - t_last) / since)
                    t_last, since = now, 0
            tok = jax.block_until_ready(tok)
        if since:
            hist.observe((time.perf_counter() - t_last) / since)
        t1d = time.perf_counter()
        self.last_decode_t = (t0d, t1d)
        obs.record_span("decode_loop", t0d, t1d, cat="serve.engine",
                        track="engine", args={"steps": max_new - 1})
        if max_new > 1 and check_finite and bool(bad):
            raise resilience.NonFiniteError(
                "non-finite values in decode logits")
        reg.counter("serve.generated_tokens").inc(n_real * max_new)
        return np.concatenate([np.asarray(t) for t in outs], axis=1)

    # ------------------------------------------- per-slot insertion path
    def init_slot_state(self) -> SlotState:
        """Fresh all-idle slot state for a ``SlotBatcher`` session."""
        return SlotState(
            cache=self.model.init_cache(self.batch, self.max_len),
            tok=jnp.zeros((self.batch, 1), jnp.int32),
            t=jnp.zeros((self.batch,), jnp.int32),
            steps_left=jnp.zeros((self.batch,), jnp.int32))

    def prefill_bucket(self, prompt_len: int, max_new: int) -> int:
        """Pow-2 padded prompt length, so insertion compiles once per
        bucket instead of once per prompt length (clamped so the slot's
        decode positions still fit the cache)."""
        s_pad = 8
        while s_pad < prompt_len:
            s_pad *= 2
        return max(prompt_len, min(s_pad, self.max_len - max_new))

    def prefill_into(self, prompt: np.ndarray, state: SlotState, slot: int,
                     max_new: int, mca: bool = True):
        """Encode ONE request (batch=1, left-padded to a pow-2 bucket,
        MCA on unless ``mca=False``) and donate/write its K/V pages and
        position state into the shared decode cache at ``slot``.

        Returns ``(state, logits, s_pad)``: ``logits`` is the host copy of
        the last prompt position's logits over the real vocab (its argmax
        is the first generated token).  Raises
        :class:`resilience.NonFiniteError` when the insertion logits come
        back non-finite (the ``serve.insert`` injection point taps the
        logits first) — the slot's state is still consistently
        overwritten, so an exact-attention retry into the same slot is
        safe.  Other slots' device state is untouched either way.
        """
        reg = obs.get_registry()
        n = len(prompt)
        if n + max_new > self.max_len:
            raise ValueError(
                f"prompt length {n} + max_new {max_new} overruns the "
                f"KV cache (max_len={self.max_len})")
        s_pad = self.prefill_bucket(n, max_new)
        padded = np.full((1, s_pad), self.pad_id, np.int32)
        padded[0, s_pad - n:] = prompt
        fn = self._prefill_into if mca else self._prefill_into_exact
        t0 = time.perf_counter()
        with obs.trace("engine.insert"):
            cache, tok, t, steps_left, logits, stats = fn(
                self.params, jnp.asarray(padded),
                jnp.asarray([s_pad - n], jnp.int32), state.cache,
                state.tok, state.t, state.steps_left,
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(max_new - 1, jnp.int32))
            logits = jax.block_until_ready(logits)
        t1 = time.perf_counter()
        reg.histogram("serve.prefill_seconds").observe(t1 - t0)
        self.last_insert_t = (t0, t1)
        obs.record_span("insert", t0, t1, cat="serve.engine", track="engine",
                        args={"slot": slot, "s_pad": s_pad, "mca": bool(mca)})
        state = SlotState(cache, tok, t, steps_left)
        reg.counter("serve.insertions").inc()
        reg.counter("serve.prefill_tokens").inc(s_pad)
        self._record_mca(stats, 1.0)
        try:
            logits_np = resilience.inject("serve.insert", np.asarray(logits))
            resilience.check_finite(logits_np, "insert logits")
        except Exception as e:
            # the old state was donated into the jit call — hand callers
            # the (consistent) new state so they can retry into the slot
            e.slot_state = state
            raise
        return state, logits_np[0, 0, :self.model.cfg.vocab_size], s_pad

    def _make_burst(self, k: int, eos_id: Optional[int]):
        model, cfg = self.model, self.model.cfg
        pad_id = self.pad_id

        def burst(params, tok, cache, t, steps_left):
            def step(carry, _):
                tok, cache, t, steps_left = carry
                live = steps_left > 0
                logits, cache = model.decode(params, tok, cache, t)
                nxt = jnp.argmax(logits[..., :cfg.vocab_size],
                                 axis=-1).astype(jnp.int32)       # [B, 1]
                ok = jnp.all(jnp.isfinite(
                    logits.reshape(logits.shape[0], -1)), axis=-1)
                # idle rows emit pad, keep their token/position frozen
                # (their stale cache row is fully rewritten on insertion)
                nxt = jnp.where(live[:, None], nxt, jnp.int32(pad_id))
                tok = jnp.where(live[:, None], nxt, tok)
                t = t + live.astype(jnp.int32)
                steps_left = jnp.where(
                    live, jnp.maximum(steps_left - 1, 0), steps_left)
                if eos_id is not None:
                    steps_left = jnp.where(live & (nxt[:, 0] == eos_id),
                                           0, steps_left)
                return (tok, cache, t, steps_left), (nxt[:, 0], live & ~ok,
                                                     live)

            (tok, cache, t, steps_left), (toks, bads, lives) = jax.lax.scan(
                step, (tok, cache, t, steps_left), None, length=k)
            return (tok, cache, t, steps_left, toks.T,
                    jnp.any(bads, axis=0), jnp.sum(lives))

        return jax.jit(burst, donate_argnums=(1, 2, 3, 4))

    def decode_burst(self, state: SlotState, k: int,
                     eos_id: Optional[int] = None):
        """Run ``k`` decode steps over all slots without touching the
        host: per-row position, max-new countdown, EOS and finite flags
        are device-side inside one ``lax.scan``.  Returns
        ``(state, toks [B, k], bad [B], live_steps)`` — reading the
        returned arrays is the single device→host sync per burst."""
        fn = self._bursts.get((k, eos_id))
        if fn is None:
            fn = self._bursts[(k, eos_id)] = self._make_burst(k, eos_id)
        t0 = time.perf_counter()
        with obs.trace("engine.decode_burst"):
            tok, cache, t, steps_left, toks, bad, live = fn(
                self.params, state.tok, state.cache, state.t,
                state.steps_left)
        state = SlotState(cache, tok, t, steps_left)
        toks, bad, live = np.asarray(toks), np.asarray(bad), int(live)
        t1 = time.perf_counter()
        self.last_burst_t = (t0, t1)
        obs.record_span("decode_burst", t0, t1, cat="serve.engine",
                        track="engine", args={"k": k, "live_steps": live})
        return state, toks, bad, live

    def kill_slot(self, state: SlotState, slot: int) -> SlotState:
        """Zero a slot's decode budget (deadline expiry) on device."""
        return dataclasses.replace(
            state, steps_left=self._kill(state.steps_left,
                                         jnp.asarray(slot, jnp.int32)))


class ContinuousBatcher:
    """Slot-based continuous batching with admission control, deadlines
    and a graceful-degradation ladder (see module docstring).  Finished
    slots immediately take the next queued request (prefill is re-run for
    the whole slot batch at toy scale; production would use per-slot
    prefill insertion).

    When tracing is enabled (``obs.enable_tracing``), each request gets a
    span chain ``queue → prefill → decode → finish`` on the track
    ``<trace_cat>/req<uid>``."""

    trace_cat = "serve.wave"

    def __init__(self, engine: Engine, max_queue: Optional[int] = None,
                 max_retries: int = 1, backoff_s: float = 0.02):
        self.engine = engine
        self.max_queue = max_queue
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.queue: List[Request] = []
        self.done: Dict[int, List[int]] = {}
        self.status: Dict[int, str] = {}

    def _reject(self, req: Request, reason: str) -> str:
        req.status = REJECTED
        req.reason = reason
        self.status[req.uid] = REJECTED
        reg = obs.get_registry()
        reg.counter(f"serve.rejected.{reason}").inc()
        reg.counter("serve.rejected").inc()
        return REJECTED

    def submit(self, req: Request) -> str:
        """Admission control: validate against cache capacity and queue
        bound.  Returns the request's status ("queued" or "rejected")."""
        eng = self.engine
        if len(req.prompt) == 0:
            return self._reject(req, "empty_prompt")
        if len(req.prompt) + req.max_new > eng.max_len:
            return self._reject(req, "prompt_too_long")
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            return self._reject(req, "queue_full")
        req.submit_t = time.monotonic()
        req.submit_pc = time.perf_counter()
        req.status = "queued"
        self.queue.append(req)
        return req.status

    def _track(self, req: Request) -> str:
        return f"{self.trace_cat}/req{req.uid}"

    def _finish(self, req: Request, status: str,
                tokens: Optional[List[int]] = None) -> None:
        req.status = status
        self.status[req.uid] = status
        obs.mark("finish", cat=self.trace_cat, track=self._track(req),
                 args={"status": status})
        if tokens is not None:
            req.out = tokens
            self.done[req.uid] = tokens
            obs.get_registry().counter("serve.requests_completed").inc()

    def _expired(self, req: Request, now: float) -> bool:
        return (req.deadline_s is not None
                and now - req.submit_t > req.deadline_s)

    def _run_wave(self, prompts, max_new, lens, n_real):
        """Degradation ladder: normal attempt, then retries with MCA
        disabled (exact attention).  Returns (gen, degraded) or raises the
        last error after max_retries exact retries."""
        reg = obs.get_registry()
        eng = self.engine
        try:
            return eng.generate(prompts, max_new, prompt_lens=lens,
                                n_real=n_real), False
        except ValueError:
            raise        # deterministic (capacity/shape): retrying can't help
        except Exception as e:                             # noqa: BLE001
            last = e
        for attempt in range(self.max_retries):
            reg.counter("resilience.serve.wave_retries").inc()
            log.warning("wave failed (%s); retry %d/%d with exact "
                        "attention", last, attempt + 1, self.max_retries,
                        exc_info=last)
            time.sleep(self.backoff_s * (2 ** attempt))
            try:
                gen = eng.generate(prompts, max_new, prompt_lens=lens,
                                   n_real=n_real, mca=False)
                if eng.mca_enabled:
                    reg.counter("resilience.serve.degraded_waves").inc()
                return gen, eng.mca_enabled
            except ValueError:
                raise
            except Exception as e:                         # noqa: BLE001
                last = e
        raise last

    def run(self) -> Dict[int, List[int]]:
        reg = obs.get_registry()
        b = self.engine.batch
        pad_id = self.engine.pad_id
        while self.queue:
            # deadline check at wave assembly: drop already-expired work
            now = time.monotonic()
            live = []
            for r in self.queue:
                if self._expired(r, now):
                    self._finish(r, TIMEOUT)
                    reg.counter("resilience.serve.timeouts").inc()
                else:
                    live.append(r)
            self.queue = live
            if not self.queue:
                break
            # capacity-aware wave assembly: a wave runs at s = max prompt
            # length and max_new = max over its members, so two
            # individually-admissible requests can jointly overrun the
            # cache — only add a request if the *joint* shape still fits;
            # the rest keep their order and go in the next wave.  (The
            # first pick always fits: submit validated it individually.)
            wave, rest = [], []
            s_max = new_max = 0
            for r in self.queue:
                cand_s = max(s_max, len(r.prompt))
                cand_new = max(new_max, r.max_new)
                if (len(wave) < b
                        and cand_s + cand_new <= self.engine.max_len):
                    wave.append(r)
                    s_max, new_max = cand_s, cand_new
                else:
                    rest.append(r)
            self.queue = rest
            n_real = len(wave)
            real = list(wave)
            while len(wave) < b:                       # pad with a dummy
                wave.append(Request(uid=-1, prompt=wave[0].prompt,
                                    max_new=wave[0].max_new))
            s = max(len(r.prompt) for r in wave)
            # left-pad with the designated pad id; pos_offset (below) makes
            # the padding invisible to attention and positions
            prompts = np.stack([
                np.pad(r.prompt, (s - len(r.prompt), 0),
                       constant_values=pad_id)
                for r in wave])
            lens = np.asarray([len(r.prompt) for r in wave], np.int32)
            max_new = max(r.max_new for r in wave)
            t0 = time.perf_counter()
            if obs.tracing_enabled():
                for r in real:       # queued-until-wave-start per request
                    obs.record_span("queue", r.submit_pc, t0,
                                    cat=self.trace_cat, track=self._track(r))
            try:
                gen, degraded = self._run_wave(prompts, max_new, lens,
                                               n_real)
            except Exception as e:                         # noqa: BLE001
                log.error("wave failed after retries: %s", e, exc_info=e)
                for r in real:
                    r.reason = str(e)
                    self._finish(r, FAILED)
                    reg.counter("resilience.serve.failed_requests").inc()
                continue
            t1 = time.perf_counter()
            reg.histogram("serve.wave_seconds").observe(t1 - t0)
            if obs.tracing_enabled():
                # attribute the wave's engine windows to every member so
                # each request track shows its own prefill/decode spans
                obs.record_span("wave", t0, t1, cat=self.trace_cat,
                                track="waves",
                                args={"n_real": n_real,
                                      "degraded": degraded})
                for r in real:
                    obs.record_span("prefill", *self.engine.last_prefill_t,
                                    cat=self.trace_cat,
                                    track=self._track(r),
                                    args={"degraded": degraded})
                    obs.record_span("decode", *self.engine.last_decode_t,
                                    cat=self.trace_cat,
                                    track=self._track(r),
                                    args={"steps": max_new - 1})
            # live-slot occupancy: fraction of slot-steps this wave spent
            # decoding real requests (dummy slots and rows idling past
            # their own max_new count as idle) — agrees with the
            # SlotBatcher's serve.slot_idle_steps accounting
            reg.gauge("serve.slot_utilization").set(
                sum(min(r.max_new, max_new) for r in real) / (b * max_new))
            reg.counter("serve.waves").inc()
            now = time.monotonic()
            for i, r in enumerate(real):
                if self._expired(r, now):
                    self._finish(r, TIMEOUT)
                    reg.counter("resilience.serve.timeouts").inc()
                else:
                    self._finish(r, DEGRADED if degraded else OK,
                                 gen[i, :r.max_new].tolist())
        return self.done


class SlotBatcher(ContinuousBatcher):
    """Per-slot continuous batching: freed slots admit queued requests via
    ``Engine.prefill_into`` (one batch=1 prefill spliced into the shared
    cache) while occupied slots keep decoding — nothing is re-encoded.

    Inherits the wave batcher's admission control / deadline / status
    surface; the degradation ladder moves to per-REQUEST granularity:

    * insertion failure (raise or non-finite via the ``serve.insert``
      injection point) retries that ONE request with exact attention —
      other slots never notice; past ``max_retries`` only that request is
      ``failed``.
    * a slot whose decode turns non-finite is re-inserted from its prompt
      with exact attention (``resilience.serve.decode_restarts``) and its
      output regenerated from scratch.
    * decode-step faults (``serve.decode`` injection) retry the burst;
      past ``max_retries`` the whole in-flight set fails and the device
      state is rebuilt fresh.

    The decode loop runs ``check_every``-step device bursts; under active
    chaos plans the burst shrinks to 1 step so fault detection matches the
    per-step engine semantics.
    """

    trace_cat = "serve.per_slot"

    def __init__(self, engine: Engine, max_queue: Optional[int] = None,
                 max_retries: int = 1, backoff_s: float = 0.02,
                 check_every: int = 8, eos_id: Optional[int] = None):
        super().__init__(engine, max_queue=max_queue,
                         max_retries=max_retries, backoff_s=backoff_s)
        self.check_every = max(1, check_every)
        self.eos_id = eos_id

    def _insert(self, state: SlotState, slot: int, req: Request,
                occupied_pads: List[int]):
        """Prefill one request into ``slot`` with the per-request
        degradation ladder.  Returns ``(state, meta_or_None)``."""
        reg = obs.get_registry()
        eng = self.engine
        last = None
        for attempt in range(self.max_retries + 1):
            use_mca = attempt == 0
            if attempt:
                reg.counter("resilience.serve.insert_retries").inc()
                log.warning("insert failed (%s); retry %d/%d with exact "
                            "attention", last, attempt, self.max_retries,
                            exc_info=last)
                time.sleep(self.backoff_s * (2 ** (attempt - 1)))
            try:
                state, logits, s_pad = eng.prefill_into(
                    req.prompt, state, slot, req.max_new, mca=use_mca)
            except ValueError:
                raise    # deterministic (capacity): retrying can't help
            except Exception as e:                         # noqa: BLE001
                # recover the post-insertion state (the pre-insertion
                # buffers were donated into the failed attempt)
                state = getattr(e, "slot_state", state)
                last = e
                continue
            first = int(logits.argmax())
            degraded = attempt > 0 and eng.mca_enabled
            if degraded:
                reg.counter("resilience.serve.degraded_requests").inc()
            obs.record_span("prefill", *eng.last_insert_t,
                            cat=self.trace_cat, track=self._track(req),
                            args={"slot": slot, "s_pad": s_pad,
                                  "degraded": degraded})
            # what a wave batcher would have re-prefilled right now: every
            # OTHER occupied slot's padded prompt
            reg.counter("serve.prefill_tokens_saved").inc(
                sum(occupied_pads))
            done = (self.eos_id is not None
                    and first == self.eos_id) or req.max_new == 1
            return state, {"req": req, "s_pad": s_pad,
                           "remaining": 0 if done else req.max_new - 1,
                           "out": [first], "degraded": degraded}
        req.reason = str(last)
        self._finish(req, FAILED)
        reg.counter("resilience.serve.failed_requests").inc()
        # the failed insertion may have armed the slot's decode budget
        return eng.kill_slot(state, slot), None

    def _finish_slot(self, meta) -> None:
        req = meta["req"]
        self._finish(req, DEGRADED if meta["degraded"] else OK,
                     meta["out"][:req.max_new])
        obs.get_registry().counter("serve.generated_tokens").inc(
            len(meta["out"][:req.max_new]))

    def run(self) -> Dict[int, List[int]]:
        reg = obs.get_registry()
        eng = self.engine
        b = eng.batch
        state = eng.init_slot_state()
        slots: List[Optional[dict]] = [None] * b
        decode_failures = 0
        cum_live = cum_total = 0
        while self.queue or any(s is not None for s in slots):
            now = time.monotonic()
            # drop expired queued work before it wastes an insertion
            live_q = []
            for r in self.queue:
                if self._expired(r, now):
                    self._finish(r, TIMEOUT)
                    reg.counter("resilience.serve.timeouts").inc()
                else:
                    live_q.append(r)
            self.queue = live_q
            # admit queued requests into free slots, one insertion each
            for slot in range(b):
                if slots[slot] is not None or not self.queue:
                    continue
                req = self.queue.pop(0)
                obs.record_span("queue", req.submit_pc, time.perf_counter(),
                                cat=self.trace_cat, track=self._track(req))
                pads = [m["s_pad"] for m in slots if m is not None]
                state, meta = self._insert(state, slot, req, pads)
                if meta is None:
                    continue
                if meta["remaining"] <= 0:
                    self._finish_slot(meta)
                else:
                    slots[slot] = meta
            if not any(s is not None for s in slots):
                continue        # failures drained work; check queue again
            # K-step sync-free burst; K=1 under chaos so injected faults
            # surface with per-step granularity
            eff_k = 1 if resilience.active() else self.check_every
            t0 = time.perf_counter()
            try:
                resilience.inject("serve.decode")
                state, toks, bad, live_steps = eng.decode_burst(
                    state, eff_k, self.eos_id)
            except Exception as e:                         # noqa: BLE001
                decode_failures += 1
                reg.counter("resilience.serve.decode_retries").inc()
                if decode_failures > self.max_retries:
                    log.error("decode failed after retries: %s", e,
                              exc_info=e)
                    for slot in range(b):
                        if slots[slot] is None:
                            continue
                        req = slots[slot]["req"]
                        req.reason = str(e)
                        self._finish(req, FAILED)
                        reg.counter(
                            "resilience.serve.failed_requests").inc()
                        slots[slot] = None
                    state = eng.init_slot_state()
                    decode_failures = 0
                else:
                    log.warning("decode burst failed (%s); retry %d/%d",
                                e, decode_failures, self.max_retries,
                                exc_info=e)
                    time.sleep(self.backoff_s * (2 ** decode_failures))
                continue
            decode_failures = 0
            reg.histogram("serve.decode_step_seconds").observe(
                (time.perf_counter() - t0) / eff_k)
            if obs.tracing_enabled():
                for s_meta in slots:      # one decode span per live slot
                    if s_meta is not None:
                        obs.record_span("decode", *eng.last_burst_t,
                                        cat=self.trace_cat,
                                        track=self._track(s_meta["req"]),
                                        args={"k": eff_k})
            reg.counter("serve.slot_idle_steps").inc(
                eff_k * b - live_steps)
            cum_live += live_steps
            cum_total += eff_k * b
            reg.gauge("serve.slot_utilization").set(cum_live / cum_total)
            now = time.monotonic()
            for slot in range(b):
                meta = slots[slot]
                if meta is None:
                    continue
                req = meta["req"]
                take = min(meta["remaining"], eff_k)
                got = toks[slot, :take].tolist()
                if self.eos_id is not None and self.eos_id in got:
                    got = got[:got.index(self.eos_id) + 1]
                meta["out"].extend(got)
                meta["remaining"] -= len(got)
                if bool(bad[slot]):
                    state, meta = self._restart_exact(state, slot, req)
                    if meta is not None and meta["remaining"] <= 0:
                        self._finish_slot(meta)
                        meta = None
                    slots[slot] = meta
                elif self._expired(req, now):
                    self._finish(req, TIMEOUT)
                    reg.counter("resilience.serve.timeouts").inc()
                    state = eng.kill_slot(state, slot)
                    slots[slot] = None
                elif (meta["remaining"] <= 0
                      or (self.eos_id is not None
                          and got and got[-1] == self.eos_id)):
                    self._finish_slot(meta)
                    slots[slot] = None
        return self.done

    def _restart_exact(self, state: SlotState, slot: int, req: Request):
        """A slot's decode went non-finite: rebuild it from its prompt
        with exact attention and regenerate from scratch.  Returns
        ``(state, meta_or_None)`` — None means the request failed."""
        reg = obs.get_registry()
        eng = self.engine
        reg.counter("resilience.serve.decode_restarts").inc()
        log.warning("slot %d produced non-finite logits; restarting with "
                    "exact attention", slot)
        try:
            state, logits, s_pad = eng.prefill_into(
                req.prompt, state, slot, req.max_new, mca=False)
        except Exception as e:                             # noqa: BLE001
            state = getattr(e, "slot_state", state)
            req.reason = str(e)
            self._finish(req, FAILED)
            reg.counter("resilience.serve.failed_requests").inc()
            return eng.kill_slot(state, slot), None
        first = int(logits.argmax())
        degraded = eng.mca_enabled
        if degraded:
            reg.counter("resilience.serve.degraded_requests").inc()
        obs.record_span("prefill", *eng.last_insert_t, cat=self.trace_cat,
                        track=self._track(req),
                        args={"slot": slot, "restart": True,
                              "degraded": degraded})
        done = (self.eos_id is not None
                and first == self.eos_id) or req.max_new == 1
        return state, {"req": req, "s_pad": s_pad,
                       "remaining": 0 if done else req.max_new - 1,
                       "out": [first], "degraded": degraded}
