"""Serving engine tests: generation consistency + continuous batching."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, reduced
from repro.serve import ContinuousBatcher, Engine, Request

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def engine_setup():
    cfg = reduced(get_config("starcoder2-3b"), n_layers=2, vocab_size=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, batch_size=2, max_len=64)
    return cfg, model, params, eng


def test_generate_shapes_and_determinism(engine_setup):
    cfg, model, params, eng = engine_setup
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8))
    out1 = eng.generate(prompts, max_new=6)
    out2 = eng.generate(prompts, max_new=6)
    assert out1.shape == (2, 6)
    np.testing.assert_array_equal(out1, out2)
    assert out1.max() < cfg.vocab_size


def test_generate_matches_stepwise_forward(engine_setup):
    """Greedy generation equals repeated full-forward argmax (KV-cache
    correctness across multiple decode steps)."""
    cfg, model, params, eng = engine_setup
    prompts = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 8))
    gen = eng.generate(prompts, max_new=4)

    from repro.models.api import _logits
    toks = jnp.asarray(prompts, jnp.int32)
    for i in range(4):
        hidden, _, _ = model.forward_hidden(params, {"tokens": toks})
        nxt = jnp.argmax(_logits(params, cfg, hidden[:, -1:])
                         [..., :cfg.vocab_size], axis=-1)
        np.testing.assert_array_equal(np.asarray(nxt)[:, 0], gen[:, i])
        toks = jnp.concatenate([toks, nxt.astype(jnp.int32)], axis=1)


def test_continuous_batcher_serves_all(engine_setup):
    cfg, model, params, eng = engine_setup
    rng = np.random.default_rng(2)
    batcher = ContinuousBatcher(eng)
    for uid in range(5):
        batcher.submit(Request(uid=uid,
                               prompt=rng.integers(0, cfg.vocab_size, 8),
                               max_new=4))
    done = batcher.run()
    assert sorted(done) == [0, 1, 2, 3, 4]
    assert all(len(v) == 4 for v in done.values())


def test_batcher_ragged_prompt_parity(engine_setup):
    """Regression: ragged prompts used to be left-padded with mode="edge",
    replicating the first token as real context — a short prompt batched
    with a long one generated different tokens than it would alone.  With
    pad-id padding + position offsets the outputs must match exactly."""
    cfg, model, params, eng = engine_setup
    rng = np.random.default_rng(3)
    long_p = rng.integers(1, cfg.vocab_size, 12).astype(np.int32)
    short_p = rng.integers(1, cfg.vocab_size, 5).astype(np.int32)

    def solo(p, max_new):
        return eng.generate(np.stack([p, p]), max_new)[0].tolist()

    want = {0: solo(long_p, 6), 1: solo(short_p, 6)}
    batcher = ContinuousBatcher(eng)
    batcher.submit(Request(uid=0, prompt=long_p, max_new=6))
    batcher.submit(Request(uid=1, prompt=short_p, max_new=6))
    done = batcher.run()
    assert done[0] == want[0], "long prompt drifted under batching"
    assert done[1] == want[1], "short (padded) prompt != solo generation"


def test_generate_explicit_prompt_lens_matches_solo(engine_setup):
    """Engine.generate with prompt_lens on a pre-padded batch gives the
    same rows as each prompt generated unpadded."""
    cfg, model, params, eng = engine_setup
    rng = np.random.default_rng(4)
    a = rng.integers(1, cfg.vocab_size, 10).astype(np.int32)
    b = rng.integers(1, cfg.vocab_size, 7).astype(np.int32)
    s = 10
    padded = np.stack([a, np.pad(b, (s - len(b), 0))])
    out = eng.generate(padded, max_new=5,
                       prompt_lens=np.asarray([len(a), len(b)]))
    solo_b = eng.generate(np.stack([b, b]), max_new=5)[0]
    np.testing.assert_array_equal(out[1], solo_b)


def test_engine_records_obs_metrics(engine_setup):
    from repro import obs
    cfg, model, params, eng = engine_setup
    rng = np.random.default_rng(5)
    with obs.scoped() as reg:
        batcher = ContinuousBatcher(eng)
        for uid in range(3):
            batcher.submit(Request(
                uid=uid, prompt=rng.integers(1, cfg.vocab_size, 6),
                max_new=4))
        batcher.run()
        snap = reg.snapshot()
    assert snap["counters"]["serve.requests_completed"] == 3
    assert snap["counters"]["serve.waves"] == 2          # batch=2 -> 2 waves
    # 3 real requests x 4 tokens: the dummy slot padding wave 2 is excluded
    assert snap["counters"]["serve.generated_tokens"] == 3 * 4
    assert snap["histograms"]["serve.prefill_seconds"]["count"] == 2
    assert snap["histograms"]["serve.wave_seconds"]["count"] == 2
    assert snap["gauges"]["serve.slot_utilization"] == 0.5   # last wave 1/2
    # MCA disabled: stats still flow, reduction is exactly 1x
    assert snap["gauges"]["serve.flops_reduction"] == 1.0


def test_engine_mca_stats_tier_occupancy():
    """With MCA on, the engine surfaces tier occupancy + flops reduction."""
    from repro import obs
    from repro.core.policy import MCAConfig
    cfg = reduced(get_config("starcoder2-3b"), n_layers=2, vocab_size=128,
                  mca=MCAConfig(enabled=True, alpha=0.4, block=16,
                                sites=("v_proj",)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, batch_size=2, max_len=32, mca_enabled=True)
    prompts = np.random.default_rng(6).integers(1, cfg.vocab_size, (2, 8))
    with obs.scoped() as reg:
        eng.generate(prompts, max_new=3)
        snap = reg.snapshot()
    assert snap["gauges"]["serve.flops_reduction"] > 1.0
    occ = [v for k, v in snap["counters"].items()
           if k.startswith("serve.tier_occupancy.t")]
    assert occ and sum(occ) > 0


# ---------------------------------------------------------------- per-slot
def test_slot_batcher_parity_vs_solo_and_wave(engine_setup):
    """The tentpole contract: per-slot insertion generates token-identical
    output to (a) each request run alone and (b) the wave batcher, for
    ragged prompts with different max_new — nothing about sharing the
    decode cache may leak between slots."""
    from repro.serve import SlotBatcher
    cfg, model, params, eng = engine_setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 4, 12, 6, 5)]
    max_news = [5, 7, 3, 6, 4]

    def solo(p, max_new):
        return eng.generate(np.stack([p, p]), max_new)[0].tolist()

    want = {i: solo(p, m) for i, (p, m) in enumerate(zip(prompts,
                                                         max_news))}
    sb = SlotBatcher(eng, check_every=3)
    for i, (p, m) in enumerate(zip(prompts, max_news)):
        assert sb.submit(Request(uid=i, prompt=p, max_new=m)) == "queued"
    got = sb.run()
    for i in want:
        assert sb.status[i] == "ok"
        assert got[i] == want[i], f"slot-batched req {i} != solo"

    wave = ContinuousBatcher(eng)
    for i, (p, m) in enumerate(zip(prompts, max_news)):
        wave.submit(Request(uid=100 + i, prompt=p, max_new=m))
    wdone = wave.run()
    for i in want:
        assert wdone[100 + i] == got[i], f"wave vs per-slot drift, req {i}"


def test_slot_batcher_metrics(engine_setup):
    """Insertion counters: one batch=1 prefill per request, tokens saved
    vs the wave batcher accounted, idle-slot steps and live-slot
    utilization agree."""
    from repro import obs
    from repro.serve import SlotBatcher
    cfg, model, params, eng = engine_setup
    rng = np.random.default_rng(8)
    with obs.scoped() as reg:
        sb = SlotBatcher(eng, check_every=4)
        for uid in range(3):
            sb.submit(Request(uid=uid,
                              prompt=rng.integers(1, cfg.vocab_size, 6),
                              max_new=4))
        done = sb.run()
        snap = reg.snapshot()
    assert all(len(done[i]) == 4 for i in range(3))
    c = snap["counters"]
    assert c["serve.insertions"] == 3                 # one prefill each
    assert c["serve.requests_completed"] == 3
    assert c["serve.generated_tokens"] == 3 * 4
    # prompts pad to the 8-bucket; the third insertion happens while one
    # slot is still occupied, so >= one occupied pad is "saved" prefill
    assert c["serve.prefill_tokens"] == 3 * 8
    assert c["serve.prefill_tokens_saved"] >= 8
    util = snap["gauges"]["serve.slot_utilization"]
    idle = c.get("serve.slot_idle_steps", 0)
    assert 0 < util <= 1
    # utilization + idle fraction account for every slot-step burst
    hist = snap["histograms"]["serve.decode_step_seconds"]
    total = hist["count"] * 4 * eng.batch
    assert abs(util - (total - idle) / total) < 1e-9


def test_slot_batcher_eos_and_deadline(engine_setup):
    """EOS stops a slot early (device-side countdown) and an expired
    deadline times the request out without touching other slots."""
    from repro.serve import SlotBatcher
    cfg, model, params, eng = engine_setup
    rng = np.random.default_rng(9)
    p = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
    ref = eng.generate(np.stack([p, p]), 8)[0].tolist()
    # EOS = the first decoded (not inserted) token that is new in ref, so
    # generation must stop exactly there and not at an earlier repeat
    i = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    sb = SlotBatcher(eng, check_every=3, eos_id=ref[i])
    sb.submit(Request(uid=0, prompt=p, max_new=8))
    done = sb.run()
    assert done[0] == ref[:i + 1], "generation must stop at (and include) EOS"

    sb2 = SlotBatcher(eng, check_every=3)
    sb2.submit(Request(uid=1, prompt=p, max_new=8, deadline_s=-1.0))
    out = sb2.run()
    assert sb2.status[1] == "timeout" and 1 not in out
