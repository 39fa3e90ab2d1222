"""Model weights made on the device from the seed, in the program's layout.

The benchmark, not the program, makes the weights: one jitted call turns
the seed into the whole parameter tree in the dtype it is served in. The
plain reference calls the same compiled function again after the window,
so both sides see bit-identical weights without the reference taking
anything the program made. The tree is whatever the cell's reference
describes (`leaf_tables`): top-level leaves, `dense_{i}` layers and
`blocks_{j}` groups stacked on axis 0, each leaf a `(path, shape from the
sizes, init)` with init `normal` (the configuration's published
`initializer_range` as its standard deviation), `ones` or `zeros`.

Each leaf has its own key, folded from the seed. Top-level leaf t takes
fold t, skipping fold 1, which is the layers': leaf i of layer l takes
`fold_in(fold_in(fold_in(key, 1), l), i)`, where `dense_i` is layer i and
group g of `blocks_j` is layer `len(dense) + g * len(blocks) + j`, so no
two leaves share a key.

The embedding's scale matters to the check: drawn at unit scale, as the
program's own initializer draws it, the last token's embedding dominates
the residual stream and the tied head, every greedy token repeats its
input by a margin of tens of logits, and no precision, however low, could
change a served token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYERS_FOLD = 1


def seed_key(seed: int) -> jax.Array:
    """A key from any whole seed, including ones wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    while True:
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            return key


def _leaf(key, shape, init, s: dict):
    if init == "normal":
        return jax.random.normal(key, shape, jnp.float32) * s["init"]
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    raise ValueError(f"unknown init {init!r}")


def layer_weights(key, layer, table, s: dict) -> dict:
    """One layer's leaves in float32, as a flat {path: array} dict."""
    lk = jax.random.fold_in(jax.random.fold_in(key, LAYERS_FOLD), layer)
    return {path: _leaf(jax.random.fold_in(lk, i), shape(s), init, s)
            for i, (path, shape, init) in enumerate(table)}


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


def make_weights(key, *, tables: dict, s: dict, dtype) -> dict:
    """The whole tree that `tables` (a reference's `leaf_tables(s)`)
    describes, in `dtype`."""
    flat = {}
    for t, (path, shape, init) in enumerate(tables["top"]):
        fold = t if t < LAYERS_FOLD else t + 1
        flat[path] = _leaf(jax.random.fold_in(key, fold), shape(s), init, s)
    dense, blocks = tables["dense"], tables["blocks"]
    for i, table in enumerate(dense):
        flat[f"dense_{i}"] = _nest(layer_weights(key, i, table, s))
    if blocks and (s["L"] - len(dense)) % len(blocks):
        raise ValueError(f"{s['L'] - len(dense)} stacked layers do not "
                         f"divide into groups of {len(blocks)}")
    for j, table in enumerate(blocks):
        layers = jnp.arange(len(dense) + j, s["L"], len(blocks),
                            dtype=jnp.uint32)
        flat[f"blocks_{j}"] = _nest(jax.vmap(
            lambda l, table=table: layer_weights(key, l, table, s))(layers))
    return jax.tree.map(lambda x: x.astype(dtype), _nest(flat))


def weight_maker(ref, s: dict, dtype):
    """The jitted maker of the tree that the reference module `ref`
    describes: key -> tree. Calling the same maker twice gives
    bit-identical trees."""
    return jax.jit(functools.partial(make_weights, tables=ref.leaf_tables(s),
                                     s=s, dtype=jnp.dtype(dtype)))


def check_layout(tree, want) -> None:
    """The benchmark's tree must match the program's parameter layout
    (`want`: the program's abstract params) leaf for leaf."""
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), tree)
    exp = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), want)
    if got != exp:
        raise RuntimeError(f"weight layout differs from the program's:\n"
                           f"  bench   {got}\n  program {exp}")
