"""Weights from the seed, made on the device in one jitted call.

The layout (the tree of parameter names and shapes) is the program's,
read once with ``jax.eval_shape``; the values are the benchmark's own:
norm scales are ones, the embedding and the LM head N(0, 0.02^2), every
other matrix N(0, 1/fan_in).  Leaf ``i`` draws from
``fold_in(seed_high, seed_low, i)``, so one leaf can be made again alone,
bit for bit, after the program's copy is gone.
"""
from __future__ import annotations

from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.traffic import seed_words


def _leaf_names(shape_tree) -> List[Tuple[str, ...]]:
    paths, _ = jax.tree_util.tree_flatten_with_path(shape_tree)
    return [tuple(getattr(k, "key", str(k)) for k in path) for path, _ in paths]


def _make_leaf(names: Tuple[str, ...], shape, dtype, key) -> jax.Array:
    name = names[-1]
    if name.startswith("ln") or name.endswith("norm"):
        return jnp.ones(shape, dtype)
    if name in ("embed", "lm_head"):
        std = 0.02
    else:
        std = 1.0 / np.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


class Weights:
    """Seeded weights in the layout ``shape_tree``."""

    def __init__(self, shape_tree: Any, out_shardings: Any = None):
        self.names = _leaf_names(shape_tree)
        self.leaves, self.treedef = jax.tree_util.tree_flatten(shape_tree)
        self._make = jax.jit(self._make_all, out_shardings=out_shardings)
        self._change = jax.jit(self._change_norms)

    def _keys(self, hi, lo):
        base = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), hi), lo)
        return [jax.random.fold_in(base, i) for i in range(len(self.leaves))]

    def _make_all(self, hi, lo):
        keys = self._keys(hi, lo)
        vals = [_make_leaf(n, s.shape, s.dtype, k)
                for n, s, k in zip(self.names, self.leaves, keys)]
        return jax.tree_util.tree_unflatten(self.treedef, vals)

    def _change_norms(self, params, hi, lo):
        keys = self._keys(hi, lo)
        out = []
        for n, s, k, p in zip(self.names, self.leaves, keys, jax.tree.leaves(params)):
            p0 = _make_leaf(n, s.shape, s.dtype, k).astype(jnp.float32)
            out.append(jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32) - p0))))
        return jnp.stack(out)

    @staticmethod
    def _words(seed: int):
        hi, lo = seed_words(seed)
        return jnp.uint32(hi), jnp.uint32(lo)

    def make(self, seed: int):
        """All parameters for ``seed``, on the device."""
        return self._make(*self._words(seed))

    def change_norms(self, params, seed: int) -> np.ndarray:
        """Per-leaf ``||params - initial(seed)||``, with the initial weights
        made again inside the call."""
        return np.asarray(self._change(params, *self._words(seed)))


@jax.jit
def leaf_norms(tree) -> jax.Array:
    """Per-leaf Euclidean norms in float32, in tree-flatten order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


BLOCKS = 64
MIN_BLOCK = 1 << 20


def _num_blocks(n: int) -> int:
    """The most blocks, at most ``BLOCKS`` and a power of two, into which
    ``n`` elements cut evenly with at least ``MIN_BLOCK`` in each."""
    k = BLOCKS
    while k > 1 and (n % k or n // k < MIN_BLOCK):
        k //= 2
    return k


@jax.jit
def block_norms(tree) -> jax.Array:
    """Euclidean norms in float32 of contiguous blocks of each leaf, in
    tree-flatten order: a leaf is cut into ``_num_blocks`` equal blocks in
    its row-major order.  At GPT-A's widths that is 64 blocks of whole
    rows of the embedding, the LM head and the FFN matrices, 32 of the
    attention matrices and one of each norm scale; at a test's widths,
    one block a leaf."""
    out = []
    for x in jax.tree.leaves(tree):
        x = x.astype(jnp.float32).reshape(_num_blocks(x.size), -1)
        out.append(jnp.sqrt(jnp.sum(jnp.square(x), axis=1)))
    return jnp.concatenate(out)
