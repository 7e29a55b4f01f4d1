"""Random weights made by the benchmark, from the seed, on the device.

The program is handed these; the plain reference remakes them from the same
seed after the window, so it takes nothing that the program has made.  The
tree has the program's parameter layout (read from ``jax.eval_shape`` of
its ``init``: names and shapes only), and every leaf comes from this
file's own draws, in the dtype the program serves it in:

* a matrix ``[..., d_in, d_out]``: normal with standard deviation
  ``1 / sqrt(d_in)``;
* the token table: normal with standard deviation 0.02;
* a norm's ``scale``: 1 plus normal noise of 0.05, and its ``bias``
  normal noise of 0.05, so a reference that skipped either would not agree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _leaf(key, path: str, shape, dtype):
    if path.endswith("['scale']"):
        x = 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)
    elif path.endswith("['bias']"):
        x = 0.05 * jax.random.normal(key, shape, jnp.float32)
    elif path.endswith("['table']"):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif len(shape) >= 2:
        x = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    else:
        raise ValueError(f"no rule for parameter {path} {shape}")
    return x.astype(dtype)


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed) & (2 ** 63 - 1)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


def make(abstract_tree, seed: int, device=None):
    """Weights for ``abstract_tree`` (ShapeDtypeStructs) from ``seed``, in
    one jitted call on ``device`` (default: the first device)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    specs = [(a.shape, a.dtype) for _, a in flat]

    def build(key):
        keys = jax.random.split(key, len(specs))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf(k, p, s, d)
                      for k, p, (s, d) in zip(keys, paths, specs)])

    dev = device or jax.devices()[0]
    key = jax.device_put(seed_key(seed), dev)
    return jax.jit(build)(key)
