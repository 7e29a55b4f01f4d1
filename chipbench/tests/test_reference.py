"""The plain references agree with the program at a tiny size on the CPU
(float32, exact attention): the semantics match, so a gap on the chip is
precision or a fault, not a different model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import reference, weights
from tiny import DECODER, ENCODER


def _program(m):
    from repro.configs import get_config
    from repro.models import build_model
    arch = "bert-base" if not m["causal"] else "starcoder2-3b"
    return build_model(get_config(arch, **dict(m, dtype="float32")))


def _params(model, seed):
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return weights.make(abstract, seed)


def test_decoder_logits_match_the_program():
    from repro.models.api import _logits
    m = dict(DECODER, dtype="float32")
    model = _program(m)
    params = _params(model, 5)
    toks = jax.random.randint(jax.random.PRNGKey(1), (64,), 0,
                              m["vocab_size"])
    with jax.default_matmul_precision("highest"):
        h, _, _ = model.forward_hidden(params, {"tokens": toks[None]})
        want = _logits(params, model.cfg, h[0])[:, :m["vocab_size"]]
    got = reference.decoder_logits(params, m, toks, 10, 40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[10:50]),
                               atol=2e-4)


def test_decoder_padding_past_the_sequence_changes_nothing():
    m = dict(DECODER, dtype="float32")
    params = _params(_program(m), 6)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (64,), 0,
                                         m["vocab_size"]))
    padded = toks.copy()
    padded[40:] = 0
    a = reference.decoder_logits(params, m, jnp.asarray(toks), 20, 20)
    b = reference.decoder_logits(params, m, jnp.asarray(padded), 20, 20)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_encoder_loss_and_gradient_match_the_program():
    m = dict(ENCODER, dtype="float32")
    model = _program(m)
    params = _params(model, 7)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 33), 0,
                              m["vocab_size"])
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: model.loss(p, batch, None)[0])(params)
        lr, gr = jax.value_and_grad(
            lambda p: reference.encoder_loss(p, batch, m, "f32"))(params)
    assert float(lr) == pytest.approx(float(lp), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-3)


def test_reference_adamw_step_matches_the_programs_optimizer():
    from repro.optim import adamw
    m = dict(ENCODER, dtype="float32")
    model = _program(m)
    params = _params(model, 8)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 33), 0,
                              m["vocab_size"])
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    opt = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "weight_decay": 0.1, "clip_norm": 1.0}
    with jax.default_matmul_precision("highest"):
        g = jax.grad(lambda p: model.loss(p, batch, None)[0])(params)
        want, _, _ = adamw.apply_updates(
            adamw.AdamWConfig(**opt), params, g, adamw.init_state(params))
        got, _, _, _ = reference.train_step(
            params, reference.adam_state(params), batch,
            tuple(sorted(m.items())), "f32", tuple(sorted(opt.items())))
    # Adam's first step moves each weight by about lr * sign(g): where g is
    # all but zero (near eps) the two sides' last bits decide the size, so
    # a few elements may differ; the updates as a whole must agree
    for a, b, p in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(params)):
        a, b, p = (np.asarray(t, np.float64) for t in (a, b, p))
        assert np.mean(np.abs(a - b) > 1e-6) < 1e-3
        assert np.linalg.norm(a - b) < 1e-2 * np.linalg.norm(b - p)


def test_the_float8_control_departs_from_float32():
    m = dict(DECODER, dtype="float32")
    params = _params(_program(m), 9)
    toks = jax.random.randint(jax.random.PRNGKey(5), (64,), 0,
                              m["vocab_size"])
    a = reference.decoder_logits(params, m, toks, 0, 64, "f32")
    b = reference.decoder_logits(params, m, toks, 0, 64, "fp8")
    rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
    assert 0.01 < rel < 0.5
