"""Seeded random weights, drawn on the device in the type they are served
in (bf16).

Every leaf is drawn from its own key, `fold_in(fold_in(seed key,
crc32(path)), layer)`, so the program's whole tree is made in one jitted
call, and the reference can draw the same values again layer by layer
once the program's state is freed. How each leaf is drawn (its standard
deviation) is the configuration's architecture's to say: its reference
module's `INIT` table, keyed by leaf name.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> np.ndarray:
    """A raw threefry key from a seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    return np.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                      np.uint32)


def leaf_std(init: dict, path: str, shape: tuple) -> float:
    """Standard deviation of a leaf (its shape without the layer axis):
    ``{"std": s}``, or ``{"fan_in_axes": k}`` for 1/sqrt of the product of
    its first k axes."""
    rule = init[path.rsplit("/", 1)[-1]]
    if "std" in rule:
        return float(rule["std"])
    return 1.0 / math.sqrt(math.prod(shape[:rule["fan_in_axes"]]))


def draw(key, path: str, layer, shape: tuple, std: float, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(path.encode())),
                           layer)
    x = jax.random.normal(k, shape, jnp.float32) * std
    return x.astype(dtype)


def _paths(tree) -> list[str]:
    flat = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))[0]
    return ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]


def program_params(abstract, logical, init: dict, seed: int):
    """The program's whole parameter tree, drawn in one jitted call.

    `abstract`/`logical` are the program's own parameter specs
    (`steps.abstract_params`); a leaf whose first logical axis is
    ``layers`` is drawn layer by layer and stacked."""
    leaves, treedef = jax.tree.flatten(abstract)
    paths = _paths(logical)
    logs = jax.tree.leaves(logical, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        out = []
        for path, sds, log in zip(paths, leaves, logs):
            if log and log[0] == "layers":
                shape = tuple(sds.shape[1:])
                std = leaf_std(init, path, shape)
                out.append(jax.vmap(
                    lambda l, p=path, s=shape, sd=std, dt=sds.dtype:
                    draw(key, p, l, s, sd, dt))(
                        jnp.arange(sds.shape[0], dtype=jnp.int32)))
            else:
                shape = tuple(sds.shape)
                out.append(draw(key, path, jnp.int32(0), shape,
                                leaf_std(init, path, shape), sds.dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(seed_key(seed))


def layer_drawer(leaf_shapes: dict, init: dict, dtype=jnp.bfloat16):
    """`fn(seed, layer) -> {path: array}`: the leaves of one layer (or the
    unstacked leaves, at layer 0), the same values `program_params` draws.
    One compiled program serves every layer."""

    @jax.jit
    def make(key, layer):
        return {p: draw(key, p, layer, s, leaf_std(init, p, s), dtype)
                for p, s in leaf_shapes.items()}

    return lambda seed, layer: make(seed_key(seed), jnp.int32(layer))
