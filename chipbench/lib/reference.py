"""Plain references of the two architectures the benchmark runs, written
from their published descriptions in straightforward ``jax.numpy``.

* decoder (StarCoder2-style): token embedding; per layer a pre-LayerNorm,
  grouped-query causal self-attention with rotary positions (NeoX
  split-half), a residual, a pre-LayerNorm, a tanh-GeLU MLP and a residual;
  a final LayerNorm and the output head tied to the embedding.
* encoder (BERT-style): the same block without rotary positions or the
  causal mask, sinusoidal absolute positions added to the embedding.

What each configuration departs from the published model in is stated in
its file under chipbench/configs; the reference follows the file.  The
reference never imports the program.  It computes in float32 under
``jax.default_matmul_precision("highest")`` (``prec="f32"``), or, as the
correctness control, with every matmul operand rounded to float8 e4m3
with a per-tensor scale (``prec="fp8"``): the step below the
configuration's bfloat16 that a later change might be tempted to take.

Parameters are read by the program's names (``embed/table``,
``layers/ln1``, ``layers/mixer/wq`` ...), stacked over layers on axis 0,
stored in whatever dtype they come in and widened to float32 layer by
layer, so a 30-layer model runs in the memory of its bfloat16 weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0                 # largest finite float8 e4m3fn


@jax.custom_vjp
def _round8(x):
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


# the rounding passes gradients straight through: the backward pass
# multiplies float32 cotangents by the rounded forward operands
_round8.defvjp(lambda x: (_round8(x), None), lambda _, g: (g,))


def q8(x, prec: str):
    """``x`` rounded to float8 e4m3 with a per-tensor scale (``fp8``), or
    unchanged (``f32``)."""
    return x if prec == "f32" else _round8(x)


def ein(spec: str, a, b, prec: str):
    return jnp.einsum(spec, q8(a, prec), q8(b, prec),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def layernorm(x, p, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, pos, theta: float, pct: float):
    """Rotary positions, NeoX split-half: the first ``pct`` of each head's
    dims, as pairs (i, i + half).  x: [..., S, H, dh]; pos: [S]."""
    dh = x.shape[-1]
    rot = int(dh * pct)
    rot -= rot % 2
    if rot == 0:
        return x
    half = rot // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq          # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def sinusoid(s: int, d: int):
    """Absolute positions: [sin | cos] of pos * exp(-ln(1e4) i / (d/2 - 1))."""
    half = d // 2
    freq = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = jnp.arange(s)[:, None] * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def attention(q, k, v, causal: bool, prec: str, q_block: int):
    """Softmax attention of one sequence.  q: [S, H, dh]; k, v: [S, Hkv,
    dh]; query head h reads kv head h // (H / Hkv).  Queries go in blocks
    of ``q_block`` so the score matrix stays small."""
    s, h, dh = q.shape
    hkv = k.shape[1]
    q_block = min(q_block, s)
    assert s % q_block == 0, (s, q_block)
    qg = q.reshape(s, hkv, h // hkv, dh)
    nb = s // q_block

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * q_block, q_block, 0)
        sc = ein("qhgd,khd->hgqk", qb, k, prec) / math.sqrt(dh)
        if causal:
            qpos = i * q_block + jnp.arange(q_block)
            sc = jnp.where(qpos[:, None] >= jnp.arange(s)[None, :], sc,
                           -jnp.inf)
        a = jax.nn.softmax(sc, axis=-1)
        return ein("hgqk,khd->qhgd", a, v, prec)

    out = jax.lax.map(block, jnp.arange(nb))        # [nb, q_block, ...]
    return out.reshape(s, h * dh)


def block_forward(x, p, m: dict, prec: str, q_block: int):
    """One residual block on one sequence.  x: [S, d] float32."""
    s = x.shape[0]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    h = layernorm(x, p["ln1"], m["norm_eps"])
    at = p["mixer"]
    q = ein("sd,de->se", h, at["wq"], prec).reshape(s, m["n_heads"],
                                                    m["d_head"])
    k = ein("sd,de->se", h, at["wk"], prec).reshape(s, m["n_kv_heads"],
                                                    m["d_head"])
    v = ein("sd,de->se", h, at["wv"], prec).reshape(s, m["n_kv_heads"],
                                                    m["d_head"])
    pos = jnp.arange(s)
    q = rope(q, pos, m["rope_theta"], m["rotary_pct"])
    k = rope(k, pos, m["rope_theta"], m["rotary_pct"])
    o = attention(q, k, v, m["causal"], prec, q_block)
    x = x + ein("se,ed->sd", o, at["wo"], prec)
    h = layernorm(x, p["ln2"], m["norm_eps"])
    f = p["ffn"]
    x = x + ein("sf,fd->sd", gelu_tanh(ein("sd,df->sf", h, f["w_up"], prec)),
                f["w_down"], prec)
    return x


def hidden(params, tokens, m: dict, prec: str, q_block: int = 512):
    """Final-LayerNorm hidden states of one sequence: [S, d] float32."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    if m["add_sinusoidal_pos"]:
        x = x + sinusoid(tokens.shape[0], m["d_model"])

    # recompute each block in the backward pass: a training reference
    # then keeps one row's block inputs, not every intermediate
    @jax.checkpoint
    def body(x, p):
        return block_forward(x, p, m, prec, q_block), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    fn = jax.tree.map(lambda a: a.astype(jnp.float32), params["final_norm"])
    return layernorm(x, fn, m["norm_eps"])


def head(params, h, m: dict, prec: str):
    """Logits over the real vocabulary of the tied output head."""
    table = params["embed"]["table"][:m["vocab_size"]].astype(jnp.float32)
    return ein("sd,vd->sv", h, table, prec)


@functools.partial(jax.jit, static_argnames=("m_items", "prec", "n_out"))
def _decoder_logits(params, tokens, start, m_items, prec, n_out):
    m = dict(m_items)
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, m, prec)
        h = jax.lax.dynamic_slice_in_dim(h, start, n_out, 0)
        return head(params, h, m, prec)


def decoder_logits(params, m: dict, tokens, start: int, n_out: int,
                   prec: str = "f32"):
    """Logits at positions ``start .. start + n_out - 1`` of the causal
    decoder over ``tokens`` ([W] int32; positions past the last real token
    are padding and, the mask being causal, change none of these)."""
    return _decoder_logits(params, tokens, jnp.int32(start),
                           tuple(sorted(m.items())), prec, n_out)


# ------------------------------------------------------------- training
def encoder_loss(params, batch, m: dict, prec: str):
    """Mean next-token cross entropy over every position of the batch,
    one row at a time."""
    @jax.checkpoint
    def one(tokens, labels):
        h = hidden(params, tokens, m, prec)
        logits = head(params, h, m, prec)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.mean(lse - jnp.take_along_axis(
            logits, labels[:, None], axis=-1)[:, 0])

    return jnp.mean(jax.lax.map(lambda tl: one(*tl),
                                (batch["tokens"], batch["labels"])))


def _decays(path) -> bool:
    """AdamW decays weight matrices and the token table, never a norm's
    scale or bias."""
    return str(path[-1].key) not in ("scale", "bias")


@functools.partial(jax.jit, static_argnames=("m_items", "prec", "opt_items"))
def train_step(params, state, batch, m_items, prec, opt_items):
    """One AdamW step (Loshchilov & Hutter; gradient clipped to a global
    norm first) of the reference.  ``params`` keep their stored dtypes:
    the update is taken in float32 and rounded back, as the configuration
    stores them.  Returns (params, state, loss, clipped gradient)."""
    m, o = dict(m_items), dict(opt_items)
    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(
            lambda p: encoder_loss(p, batch, m, prec))(
                jax.tree.map(lambda a: a.astype(jnp.float32), params))
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(
        1.0, o["clip_norm"] / jnp.maximum(norm, 1e-12)), g)
    t = state["t"] + 1
    mo = jax.tree.map(lambda a, x: o["b1"] * a + (1 - o["b1"]) * x,
                      state["m"], g)
    ve = jax.tree.map(lambda a, x: o["b2"] * a + (1 - o["b2"]) * x * x,
                      state["v"], g)
    c1 = 1 - o["b1"] ** t.astype(jnp.float32)
    c2 = 1 - o["b2"] ** t.astype(jnp.float32)

    def upd(path, p, a, b):
        step = (a / c1) / (jnp.sqrt(b / c2) + o["eps"])
        p32 = p.astype(jnp.float32)
        if _decays(path):
            step = step + o["weight_decay"] * p32
        return (p32 - o["lr"] * step).astype(p.dtype)

    new = jax.tree_util.tree_map_with_path(upd, params, mo, ve)
    return new, {"m": mo, "v": ve, "t": t}, loss, g


def adam_state(params):
    z = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    return {"m": z, "v": jax.tree.map(jnp.copy, z),
            "t": jnp.zeros((), jnp.int32)}
