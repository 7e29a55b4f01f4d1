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
    # wave 1: 2 x 4 slot-steps all live; wave 2: 1 real request of 4 steps
    # beside a dummy slot -> 16 slot-steps, 4 idle
    assert snap["counters"]["serve.slot_steps"] == 16
    assert snap["counters"]["serve.slot_idle_steps"] == 4
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


def test_slot_batcher_kernel_buckets_parity_and_count():
    """With whole-lane heads, insertions at buckets of 128 and 256 run
    attention on the flash kernel and one at 64 on the scan: each request
    still generates what it does alone (solo prefills at 100 and 130
    positions run the scan), and ``serve.insert_kernel_insertions``
    counts the two kernel-path insertions, never the scan's."""
    from repro import obs
    from repro.serve import SlotBatcher
    cfg = reduced(get_config("starcoder2-3b"), n_layers=2, vocab_size=128,
                  d_head=128)
    model = build_model(cfg)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)), batch_size=2,
                 max_len=272)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (100, 130, 60)]
    assert [eng.prefill_bucket(len(p), 5) for p in prompts] == [128, 256, 64]
    want = {i: eng.generate(np.stack([p, p]), 5)[0].tolist()
            for i, p in enumerate(prompts)}
    with obs.scoped() as reg:
        sb = SlotBatcher(eng, check_every=3)
        for i, p in enumerate(prompts):
            sb.submit(Request(uid=i, prompt=p, max_new=5))
        got = sb.run()
        c = reg.snapshot()["counters"]
    assert got == want
    assert c["serve.insertions"] == 3
    assert c["serve.insert_kernel_insertions"] == 2


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
    # d_head 32 is not whole lanes: every bucket runs the scan, and says so
    assert c["serve.insert_kernel_insertions"] == 0
    assert c["serve.requests_completed"] == 3
    assert c["serve.generated_tokens"] == 3 * 4
    # prompts pad to the 8-bucket; the third insertion happens while one
    # slot is still occupied, so >= one occupied pad is "saved" prefill
    assert c["serve.prefill_tokens"] == 3 * 8
    assert c["serve.prefill_tokens_saved"] >= 8
    idle = c.get("serve.slot_idle_steps", 0)
    util = 1 - idle / c["serve.slot_steps"]
    assert 0 < util <= 1
    # the slot-steps counted are every burst's: k steps of every slot
    hist = snap["histograms"]["serve.decode_step_seconds"]
    assert c["serve.slot_steps"] == hist["count"] * 4 * eng.batch


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


# ------------------------------------------- in-place layer-stacked decode
def _ref_gqa_decode(p, cfg, x, c, t_vec, off):
    """The per-layer decode as it was before the cache rode in the layer
    scan's carry: write the new K/V row into the layer's own cache first,
    then attend over the whole of it."""
    from repro.models.attention import NEG_INF, _split_heads
    from repro.models.common import apply_rope, rmsnorm
    b = x.shape[0]
    hkv, g, dh = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.d_head
    slots = c["k"].shape[1]
    q = _split_heads(x @ p["wq"], cfg.n_heads, dh)
    k1 = _split_heads(x @ p["wk"], hkv, dh)
    v1 = _split_heads(x @ p["wv"], hkv, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k1 = rmsnorm(k1, p["k_norm"], cfg.norm_eps)
    posb = t_vec[:, None] - off[:, None]
    q = apply_rope(q, posb, cfg.rope_theta, cfg.rotary_pct)
    k1 = apply_rope(k1, posb, cfg.rope_theta, cfg.rotary_pct)
    slot = t_vec % slots if cfg.window > 0 else t_vec
    rows = jnp.arange(b)
    kc = c["k"].at[rows, slot].set(k1[:, 0])
    vc = c["v"].at[rows, slot].set(v1[:, 0])
    spos = c["slot_pos"].at[rows, slot].set(t_vec)
    valid = (spos >= 0) & (spos >= off[:, None])
    s = jnp.einsum("bqhgd,bshd->bhgqs", q.reshape(b, 1, hkv, g, dh), kc,
                   preferred_element_type=jnp.float32) * dh ** -0.5
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", a.astype(vc.dtype), vc)
    y = out.reshape(b, 1, cfg.n_heads * dh) @ p["wo"]
    return y, {"k": kc, "v": vc, "slot_pos": spos}


def _ref_mla_decode(p, cfg, x, c, t_vec, off):
    """Write-first absorbed MLA decode of one layer's own latent cache."""
    from repro.models.attention import NEG_INF, _split_heads
    from repro.models.common import apply_rope, rmsnorm
    b, h = x.shape[0], cfg.n_heads
    dn, dr, dv = cfg.mla_qk_nope, cfg.mla_qk_rope, cfg.mla_v_dim
    dl = cfg.mla_kv_lora
    q = _split_heads(rmsnorm(x @ p["w_dq"], p["q_ln"], cfg.norm_eps)
                     @ p["w_uq"], h, dn + dr)
    posb = t_vec[:, None] - off[:, None]
    q_nope, q_rope = q[..., :dn], apply_rope(q[..., dn:], posb,
                                             cfg.rope_theta)
    ckv1 = rmsnorm(x @ p["w_dkv"], p["kv_ln"], cfg.norm_eps)
    kr1 = apply_rope((x @ p["w_kr"])[:, :, None, :], posb,
                     cfg.rope_theta)[:, :, 0, :]
    rows = jnp.arange(b)
    ckv = c["ckv"].at[rows, t_vec].set(ckv1[:, 0])
    kr = c["kr"].at[rows, t_vec].set(kr1[:, 0])
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope, p["w_uk"].reshape(dl, h, dn))
    s = (jnp.einsum("bqhl,bsl->bhqs", q_lat, ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhd,bsd->bhqs", q_rope, kr,
                      preferred_element_type=jnp.float32)) * (dn + dr) ** -0.5
    idx = jnp.arange(ckv.shape[1])
    valid = (idx[None] <= t_vec[:, None]) & (idx[None] >= off[:, None])
    a = jax.nn.softmax(jnp.where(valid[:, None, None, :], s, NEG_INF), -1)
    out_lat = jnp.einsum("bhqs,bsl->bqhl", a.astype(ckv.dtype), ckv)
    out = jnp.einsum("bqhl,lhv->bqhv", out_lat, p["w_uv"].reshape(dl, h, dv))
    return out.reshape(b, 1, h * dv) @ p["wo"], {"ckv": ckv, "kr": kr}


def _ref_decode(params, cfg, tokens, cache, t):
    """Step-by-step reference: every layer decodes on its own slice of the
    cache, and the new slices are stacked again afterwards."""
    from repro.models import ffn as ffn_mod
    from repro.models import ssm, stack
    from repro.models.api import _logits
    from repro.models.common import apply_norm, embed_tokens
    b = tokens.shape[0]
    kind = stack.layer_kind(cfg)
    t_vec = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))
    off = cache["pos_off"]
    x = embed_tokens(params["embed"], tokens)
    new = []
    for i in range(cfg.n_layers):
        p_l = jax.tree.map(lambda a: a[i], params["layers"])
        c_l = jax.tree.map(lambda a: a[i], cache["layers"])
        h = apply_norm(p_l["ln1"], cfg, x)
        if kind == "ssm":
            y, c_l = ssm.mamba2_decode(p_l["mixer"], cfg, h, c_l)
            x = x + y
        else:
            attend = (_ref_mla_decode if cfg.attn_type == "mla"
                      else _ref_gqa_decode)
            y, c_l = attend(p_l["mixer"], cfg, h, c_l, t_vec, off)
            x = x + y
            h = apply_norm(p_l["ln2"], cfg, x)
            x = x + (ffn_mod.moe_ffn(p_l["ffn"], cfg, h)[0]
                     if kind == "attn_moe" else ffn_mod.ffn(p_l["ffn"], cfg, h))
        new.append(c_l)
    x = apply_norm(params["final_norm"], cfg, x)
    layers = jax.tree.map(lambda *a: jnp.stack(a), *new)
    return _logits(params, cfg, x), {"layers": layers, "pos_off": off}


_INPLACE_CASES = {
    "gqa": ("starcoder2-3b", {}),
    # [kv_heads, 128] rows: the Pallas KV write and decode attention
    "gqa-kernels": ("starcoder2-3b", {"d_head": 128}),
    "gqa-window": ("starcoder2-3b", {"window": 8}),
    "mla": ("minicpm3-4b", {}),
    "moe": ("olmoe-1b-7b", {}),
    "ssm": ("mamba2-2.7b", {}),
}


@pytest.mark.parametrize("per_row", [False, True], ids=["scalar-t", "row-t"])
@pytest.mark.parametrize("case", sorted(_INPLACE_CASES))
def test_inplace_decode_matches_per_layer_reference(case, per_row):
    """Several decode steps with the layer-stacked cache kept in place give
    the tokens, logits and caches of the per-layer write-first decode,
    within bf16 round-off.  The per-row case puts the
    rows at different positions (the second rewrites a slot it already
    holds) and left-pads one row; the window case wraps the rolling
    cache."""
    arch, over = _INPLACE_CASES[case]
    cfg = reduced(get_config(arch), dtype="bfloat16", **over)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    b, s, steps = 2, 12, 4
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (b, s), 0,
                                          cfg.vocab_size)}
    if per_row and case != "ssm":          # a recurrent state has no padding
        batch["pos_offset"] = jnp.asarray([0, 3], jnp.int32)
    cache, hidden, _ = model.prefill(params, batch, 24)
    from repro.models.api import _logits
    tok = jnp.argmax(_logits(params, cfg, hidden[:, -1:])
                     [..., :cfg.vocab_size], axis=-1).astype(jnp.int32)
    t = jnp.asarray([s, s - 2], jnp.int32) if per_row else jnp.int32(s)
    ref_cache, decode = cache, jax.jit(model.decode)
    ref = jax.jit(_ref_decode, static_argnums=1)
    for _ in range(steps):
        logits, cache = decode(params, tok, cache, t)
        ref_logits, ref_cache = ref(params, cfg, tok, ref_cache, t)
        _assert_bf16_close(logits, ref_logits)
        _assert_same_token(logits[:, 0, :cfg.vocab_size],
                           ref_logits[:, 0, :cfg.vocab_size])
        ref_tok = jnp.argmax(ref_logits[..., :cfg.vocab_size], axis=-1)
        tok, t = ref_tok.astype(jnp.int32), t + 1
    for got, want in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
        assert got.shape == want.shape and got.dtype == want.dtype
        _assert_bf16_close(got, want)


def _assert_same_token(got, want):
    """Each row picks the reference's best token, or one whose reference
    logit is within bf16 round-off of the best (random weights make near
    ties, which rounding may break either way)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    picked = want[np.arange(len(want)), got.argmax(-1)]
    tol = 4 * float(jnp.finfo(jnp.bfloat16).eps) * np.abs(want).max()
    assert np.all(want.max(-1) - picked <= tol), (want.max(-1) - picked, tol)


def _assert_bf16_close(got, want):
    """Within bf16 round-off: the error's norm is at most two bf16 epsilons
    of the reference's (a scan and a Python loop over the same layers
    already differ by about one)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    assert (np.linalg.norm(got - want)
            <= 2 * eps * np.linalg.norm(want)), (
        np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("arch,path", [("starcoder2-3b", "kernel_calls"),
                                       ("minicpm3-4b", "fallback_calls")])
def test_inplace_decode_kv_write_accounting(arch, path):
    """The in-place decode still writes B rows per cache leaf per layer per
    step through ``kv_slot_update``, one call per leaf and step for all
    layers: the device telemetry counts them, and the dispatch counter
    names the path that wrote them (the GQA cache's [kv_heads, 128] rows
    are whole tiles, the MLA latent's are not)."""
    from repro import obs
    from repro.obs import devtel
    cfg = reduced(get_config(arch), d_head=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, steps = 2, 3
    with obs.scoped() as reg, devtel.enabled_scope():
        @jax.jit
        def run(cache, tok, t):
            def step(c, i):
                return model.decode(params, tok, c, t + i)[1], ()
            return jax.lax.scan(step, cache, jnp.arange(steps))[0]
        base = devtel.totals()
        jax.block_until_ready(run(model.init_cache(b, 16),
                                  jnp.ones((b, 1), jnp.int32),
                                  jnp.asarray([0, 5], jnp.int32)))
        d = devtel.since(base)
        counters = reg.snapshot()["counters"]
    # one write per cache leaf per step, of B rows in each of the layers
    assert d["kernels.kv_slot_update.device_launches"] == steps * 2
    assert (d["kernels.kv_slot_update.device_rows_written"]
            == steps * 2 * cfg.n_layers * b)
    assert counters[f"kernels.kv_slot_update.{path}"] == 2
    other = {"kernel_calls": "fallback_calls",
             "fallback_calls": "kernel_calls"}[path]
    assert counters.get(f"kernels.kv_slot_update.{other}", 0) == 0
