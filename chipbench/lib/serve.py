"""Serving cells: the program's own stack (``launch.serve.build_server``
-> ``SlotBatcher.submit/run`` -> ``Engine``) driven by the mix.

The program keeps no per-request timestamps, so the harness wraps the
engine instance's two public calls and stamps what they return:

* ``prefill_into`` (one request's insertion): its start is the end of the
  request's queue wait; its return is the request's first token;
* ``decode_burst`` (``check_every`` decode steps of every slot): its return
  delivers the burst's tokens; a request finishes at the return of the
  burst that produced its last token.

New arrivals are handed to ``SlotBatcher.submit`` from the same wrappers
(and between ``run()`` calls when the batcher is idle), so the batcher sees
requests as a server would: while it is busy.

* open loop: requests arrive at their scheduled times whatever the server
  does; each is timed from its scheduled arrival.
* closed backlog: the queue is topped up to ``backlog_per_slot`` x slots
  after every call; the window opens when the first burst returns (every
  slot full) and closes at the first burst return past ``seconds``; the
  queue is then emptied and the batcher finishes what it holds.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from . import traffic as gen

WARM_UID = 1 << 40              # warm-up requests: uids from here up


@dataclasses.dataclass
class Rec:
    """One request's life, on the host's perf_counter clock."""
    uid: int
    prompt_len: int
    max_new: int
    due: float = 0.0              # scheduled arrival (open loop) / submit
    submit: float = 0.0
    insert_start: Optional[float] = None
    first: Optional[float] = None
    finish: Optional[float] = None
    produced: int = 0


@dataclasses.dataclass
class Burst:
    start: float
    end: float
    k: int
    live_steps: int
    kv_positions: int            # cached positions read over its steps


@dataclasses.dataclass
class Insert:
    start: float
    end: float
    bucket: int
    uid: int


class ServeCell:
    """Set up once (weights, server, warm-up), then ``window()``."""

    def __init__(self, model_dict: dict, arch: str, mix: dict, seed: int):
        import jax

        from repro.configs import get_config
        from repro.core.policy import MCAConfig
        from repro.launch.serve import build_server
        from repro.models import build_model

        from . import weights

        self.mix, self.seed = mix, seed
        self.m = model_dict
        sv = mix["serve"]
        mca = MCAConfig(enabled=bool(sv["mca"]), alpha=sv.get("alpha", 0.2),
                        sites=("v_proj",))
        self.model = build_model(get_config(arch, mca=mca, **model_dict))
        self.abstract = jax.eval_shape(self.model.init,
                                       jax.random.PRNGKey(0))
        self.params = weights.make(self.abstract, seed)
        self.engine, self.batcher = build_server(
            self.model, self.params, slots=sv["slots"],
            max_len=sv["max_len"], mca=bool(sv["mca"]),
            check_every=sv["check_every"], seed=seed)
        self.k = sv["check_every"]
        self.slots = sv["slots"]

    # ------------------------------------------------------------ set-up
    def buckets(self, reqs) -> List[int]:
        return sorted({self.engine.prefill_bucket(len(r.prompt), r.max_new)
                       for r in reqs})

    def warm(self, reqs) -> None:
        """Serve requests of every insertion bucket that ``reqs`` use, so
        that every program the window runs is compiled (or loaded) here:
        each bucket once as the first insertion of a ``run()`` (into the
        fresh slot state that ``run()`` makes) and once after another."""
        from repro.serve import Request

        b = self.buckets(reqs)
        rng = gen.rng_for(self.seed, 9)
        uid = WARM_UID

        def req(s):
            nonlocal uid
            uid += 1
            return Request(uid=uid, prompt=rng.integers(
                0, self.m["vocab_size"], s, dtype=np.int32),
                max_new=self.k + 2)

        for group in [[s] for s in b] + [b[:1] + b]:
            for s in group:
                self.batcher.submit(req(s))
            self.batcher.run()
        bad = {u: s for u, s in self.batcher.status.items() if s != "ok"}
        if bad:
            raise RuntimeError(f"warm-up requests did not end ok: {bad}")

    # ------------------------------------------------------------ window
    def window(self, reqs: List[gen.Req], seconds: float,
               on_open=None, on_close=None) -> dict:
        """Serve ``reqs`` under the mix; returns the window's records.
        ``on_open`` / ``on_close`` run as the window opens and closes
        (the traced run starts and stops the profiler there)."""
        from repro import obs
        from repro.serve import Request

        eng, bat = self.engine, self.batcher
        reg = obs.get_registry()

        def opened():
            st["snap0"] = reg.snapshot(include_device=False)
            if on_open:
                on_open()

        def closed():
            if on_close:
                on_close()
            st["snap1"] = reg.snapshot(include_device=False)
        closed_loop = self.mix["kind"] == "closed_backlog"
        by_prompt = {id(r.prompt): r for r in reqs}
        recs: Dict[int, Rec] = {}
        inserts: List[Insert] = []
        bursts: List[Burst] = []
        slot_uid: List[Optional[int]] = [None] * self.slots
        st = {"t0": None, "t_end": None, "next": 0, "closed": False}
        target = self.mix.get("backlog_per_slot", 0) * self.slots

        def submit(r: gen.Req, now: float, due: float) -> None:
            recs[r.uid] = Rec(r.uid, len(r.prompt), r.max_new, due=due,
                              submit=now)
            if bat.submit(Request(uid=r.uid, prompt=r.prompt,
                                  max_new=r.max_new)) != "queued":
                raise RuntimeError(f"request {r.uid} was refused")

        def pump(now: float) -> None:
            if closed_loop:
                if st["t0"] is not None and now >= st["t0"] + seconds \
                        and not st["closed"]:
                    st["closed"], st["t_end"] = True, now
                    bat.queue.clear()         # harness-owned, not started
                    closed()
                while not st["closed"] and len(bat.queue) < target:
                    if st["next"] >= len(reqs):
                        raise RuntimeError("the backlog's pool ran dry: "
                                           "raise the mix's 'pool'")
                    r = reqs[st["next"]]
                    st["next"] += 1
                    submit(r, now, now)
                return
            t0 = st["t0"]
            while st["next"] < len(reqs) and \
                    t0 + reqs[st["next"]].arrival <= now:
                r = reqs[st["next"]]
                st["next"] += 1
                submit(r, now, t0 + r.arrival)
            if not st["closed"] and now >= t0 + seconds:
                st["closed"], st["t_end"] = True, t0 + seconds
                closed()

        orig_insert, orig_burst = eng.prefill_into, eng.decode_burst

        def prefill_into(prompt, state, slot, max_new, mca=True):
            r = by_prompt.get(id(prompt))
            t_a = time.perf_counter()
            out = orig_insert(prompt, state, slot, max_new, mca=mca)
            t_b = time.perf_counter()
            if r is not None:
                rec = recs[r.uid]
                if rec.insert_start is None:
                    rec.insert_start = t_a
                rec.first, rec.produced = t_b, 1
                slot_uid[slot] = r.uid
                inserts.append(Insert(t_a, t_b, out[2], r.uid))
            pump(t_b)
            return out

        def decode_burst(state, k, eos_id=None):
            lens = [(recs[u].prompt_len + recs[u].produced,
                     min(k, recs[u].max_new - recs[u].produced))
                    for u in slot_uid if u is not None]
            t_a = time.perf_counter()
            out = orig_burst(state, k, eos_id)
            t_b = time.perf_counter()
            for s, u in enumerate(slot_uid):
                if u is None:
                    continue
                rec = recs[u]
                rec.produced += min(k, rec.max_new - rec.produced)
                if rec.produced >= rec.max_new:
                    rec.finish = t_b
                    slot_uid[s] = None
            bursts.append(Burst(t_a, t_b, k, int(out[3]),
                                sum(n * t + t * (t - 1) // 2
                                    for n, t in lens)))
            if closed_loop and st["t0"] is None:
                opened()                       # every slot now holds work
                st["t0"] = time.perf_counter()
            pump(t_b)
            return out

        eng.prefill_into, eng.decode_burst = prefill_into, decode_burst
        from jax.profiler import TraceAnnotation
        try:
            if closed_loop:
                pump(time.perf_counter())
                with TraceAnnotation("bench.batcher_run"):
                    bat.run()
            else:
                opened()
                st["t0"] = time.perf_counter()
                pump(st["t0"])
                while True:
                    if bat.queue:
                        with TraceAnnotation("bench.batcher_run"):
                            bat.run()
                        continue
                    if st["next"] >= len(reqs):
                        break
                    due = st["t0"] + reqs[st["next"]].arrival
                    with TraceAnnotation("bench.wait_arrival"):
                        time.sleep(max(0.0, due - time.perf_counter()))
                    pump(time.perf_counter())
                if not st["closed"]:
                    with TraceAnnotation("bench.wait_arrival"):
                        time.sleep(max(0.0, st["t0"] + seconds
                                       - time.perf_counter()))
                    pump(time.perf_counter())
        finally:
            del eng.prefill_into, eng.decode_burst
        return {"t0": st["t0"], "t_end": st["t_end"], "recs": recs,
                "snap0": st["snap0"], "snap1": st["snap1"],
                "inserts": inserts, "bursts": bursts,
                "status": dict(bat.status), "done": dict(bat.done)}
