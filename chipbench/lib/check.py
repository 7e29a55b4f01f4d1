"""What decides ``correct``: the timed path's output against the plain
reference (chipbench/lib/reference.py), with weights remade from the seed
after the program's state is freed.

The reference is the module that the configuration file names under
``reference`` (``chipbench/lib/<name>.py``).

Serving: a sample of the window's finished requests, drawn from the seed
and holding the longest, is scored by the reference over each prompt and
its served tokens.  The number compared is the widest gap by which a
served (greedy) token's logit lies below the reference's best at its
position.  The control reads, at the same positions, the gap of the token
that the reference computed in float8 puts first.

Training: the reference follows the first steps on the same rows.  Three
numbers: the worst relative gap of a step's loss; of a leaf's first
gradient norm (as the optimizer got it); of a leaf's parameter change
after the last of those steps.  Norm gaps are taken against the larger of
that leaf's reference norm and the median leaf's.  Leaves whose reference
gradient is under a thousandth of the median leaf's move by round-off
alone and are left out of the change.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from . import traffic, weights

GRAD_FLOOR = 1e-3


def sample(recs: Sequence, n: int, seed: int) -> List:
    """The longest finished request and ``n - 1`` more drawn from the
    seed.  ``recs`` are the finished requests' records."""
    recs = sorted(recs, key=lambda r: r.uid)
    if len(recs) <= n:
        return list(recs)
    longest = max(recs, key=lambda r: (r.prompt_len + r.max_new, r.uid))
    rest = [r for r in recs if r is not longest]
    pick = traffic.rng_for(seed, 7).choice(len(rest), n - 1, replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def serve_width(mix: dict) -> Tuple[int, int]:
    """(padded sequence width, logits read per request) of the mix: one
    reference program serves every request of a cell."""
    n_out = traffic.max_new_limit(mix)
    w = mix["serve"]["max_len"] + n_out
    return -(-w // 512) * 512, n_out


def serve_gaps(ref, m: dict, abstract, seed: int, items, mix: dict,
               precs: Sequence[str] = ("f32",)) -> Dict[str, float]:
    """``items``: [(prompt, served tokens)].  Returns the widest gap of the
    served tokens (``served``), and for each control precision in
    ``precs`` past the first the widest gap of that precision's top token
    (``control.<prec>``), with the count of tokens compared (``tokens``).
    ``ref`` is the configuration's reference module."""
    import jax.numpy as jnp

    params = weights.make(abstract, seed)
    width, n_out = serve_width(mix)
    out = {"served": 0.0, "tokens": 0}
    for prompt, served in items:
        served = np.asarray(served, np.int32)
        seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
        toks = np.zeros(width, np.int32)
        toks[:len(seq)] = seq
        n = len(served)
        want = ref.decoder_logits(params, m, jnp.asarray(toks),
                                  len(prompt) - 1, n_out, "f32")[:n]
        best = jnp.max(want, axis=-1)
        gap = best - jnp.take_along_axis(want, jnp.asarray(served)[:, None],
                                         axis=-1)[:, 0]
        out["served"] = max(out["served"], float(jnp.max(gap)))
        out["tokens"] += n
        for prec in precs[1:]:
            low = ref.decoder_logits(params, m, jnp.asarray(toks),
                                     len(prompt) - 1, n_out, prec)[:n]
            pick = jnp.argmax(low, axis=-1)
            g = best - jnp.take_along_axis(want, pick[:, None],
                                           axis=-1)[:, 0]
            key = f"control.{prec}"
            out[key] = max(out.get(key, 0.0), float(jnp.max(g)))
        del want
    del params
    return out


def train_reference(ref, m: dict, abstract, seed: int, batches, opt: dict,
                    prec: str = "f32"):
    """The reference's (losses, first-gradient leaf norms, leaf norms of
    the parameter change) over ``batches``; ``ref`` is the configuration's
    reference module."""
    import jax
    import jax.numpy as jnp

    from .train import leaf_norms

    p0 = weights.make(abstract, seed)
    params, state = p0, ref.adam_state(p0)
    losses, grads = [], None
    m_items, o_items = tuple(sorted(m.items())), tuple(sorted(opt.items()))
    for b in batches:
        b = {k: jnp.asarray(v) for k, v in b.items()}
        params, state, loss, g = ref.train_step(
            params, state, b, m_items, prec, o_items)
        losses.append(float(loss))
        if grads is None:
            grads = leaf_norms(g)
        del g
    change = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, p0))
    return losses, grads, change


def train_gaps(prog, ref) -> Dict[str, float]:
    """Worst gaps of ``prog`` = (losses, grad norms, change norms) against
    ``ref`` (the same, from the reference)."""
    (pl, pg, pc), (rl, rg, rc) = prog, ref
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(pl, rl))
    med_g = float(np.median(list(rg.values())))
    grad_gap = max(abs(pg[k] - rg[k]) / max(rg[k], med_g) for k in rg)
    moving = [k for k in rg if rg[k] >= GRAD_FLOOR * med_g]
    med_c = float(np.median([rc[k] for k in moving]))
    change_gap = max(abs(pc[k] - rc[k]) / max(rc[k], med_c) for k in moving)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}
