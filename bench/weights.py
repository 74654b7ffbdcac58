"""Weights made from the seed, by the benchmark and not by the program.

Every leaf of the program's parameter tree gets its values from a key made
of the seed, a hash of the leaf's path and, for the stacked layer leaves,
the layer index. So the harness makes the whole tree on the device in one
jitted call, and the reference makes any single matrix again, bit for bit,
after the program's state is gone.

Values, in the leaf's own dtype: LayerNorm scales ``1 + 0.1 n``, LayerNorm
and projection biases ``0.05 n``, the embedding ``n``, every matrix
``n / sqrt(fan_in)`` (``n`` standard normal).
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Path = Tuple[str, ...]


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size (seeds may exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _leaf(key, path: Path, shape: Sequence[int], dtype):
    name = path[-1]
    n = jax.random.normal(key, tuple(shape), dtype)
    if name.endswith("_scale"):
        return 1 + jnp.asarray(0.1, dtype) * n
    if name.endswith(("_bias", "_b")):
        return jnp.asarray(0.05, dtype) * n
    if name == "embedding":
        return n
    return n * jnp.asarray(1.0 / np.sqrt(shape[-2]), dtype)


def _path_key(key, path: Path):
    return jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))


def _stacked(path: Path) -> bool:
    return path[0] == "blocks"


def leaf_value(key, path: Path, shape, dtype, layer: Optional[int] = None):
    """One leaf (``layer`` given: that layer's slice of a stacked leaf)."""
    k = _path_key(key, path)
    if not _stacked(path):
        return _leaf(k, path, shape, dtype)
    if layer is not None:
        return _leaf(jax.random.fold_in(k, layer), path, shape[1:], dtype)
    return jnp.stack([_leaf(jax.random.fold_in(k, i), path, shape[1:], dtype)
                      for i in range(shape[0])])


def tree_spec(shapes) -> Dict[Path, Tuple[Tuple[int, ...], str]]:
    """{path: (shape, dtype name)} of an abstract parameter tree."""
    out = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        path = tuple(k.key for k in kp)
        out[path] = (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
    return out


def make_params(shapes, seed: int, shardings=None):
    """The whole parameter tree, made on the device in one jitted call
    (laid out by ``shardings``, a tree like ``shapes``, where given)."""
    spec = tree_spec(shapes)
    treedef = jax.tree_util.tree_structure(shapes)
    paths = list(spec)
    out = (None if shardings is None
           else jax.tree_util.tree_leaves(shardings))

    @functools.partial(jax.jit, out_shardings=out)
    def build(key):
        return [leaf_value(key, p, *spec[p]) for p in paths]

    leaves = build(base_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)


class WeightSource:
    """``get(path, layer)`` for the reference: one leaf, made again."""

    def __init__(self, spec: Dict[Path, Tuple[Tuple[int, ...], str]],
                 seed: int):
        self.spec = spec
        self.key = base_key(seed)
        self._fns = {}

    def get(self, path: Path, layer: Optional[int]):
        shape, dtype = self.spec[path]
        fn = self._fns.get(path)
        if fn is None:
            fn = jax.jit(lambda key, layer, p=path, s=shape, d=dtype:
                         _leaf(jax.random.fold_in(_path_key(key, p), layer),
                               p, s[1:], d) if _stacked(p)
                         else _leaf(_path_key(key, p), p, s, d))
            self._fns[path] = fn
        return fn(self.key, 0 if layer is None else layer)
