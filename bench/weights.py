"""Model weights made on the device from the seed, in the program's layout.

The benchmark, not the program, makes the weights: one jitted call turns
the seed into the whole parameter tree in the dtype it is served in. The
plain reference calls the same compiled function again after the window,
so both sides see bit-identical weights without the reference taking
anything the program made. Each leaf has its own key, folded from the seed,
the leaf's index and its layer. Every matrix, the (tied) embedding
included, is normal with the configuration's published
`initializer_range` as its standard deviation; norm scales are one.

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

# (name, shape from the sizes, is a matrix) of one layer's leaves, in the
# program's tree: blocks_0/{norm1,mixer,norm2,ffn}
_LAYER = (
    ("norm1/scale", lambda s: (s["d"],), False),
    ("mixer/wq", lambda s: (s["d"], s["hq"], s["hd"]), True),
    ("mixer/wk", lambda s: (s["d"], s["hkv"], s["hd"]), True),
    ("mixer/wv", lambda s: (s["d"], s["hkv"], s["hd"]), True),
    ("mixer/wo", lambda s: (s["hq"], s["hd"], s["d"]), True),
    ("mixer/q_norm_scale", lambda s: (s["hd"],), False),
    ("mixer/k_norm_scale", lambda s: (s["hd"],), False),
    ("norm2/scale", lambda s: (s["d"],), False),
    ("ffn/wi_gate", lambda s: (s["d"], s["ff"]), True),
    ("ffn/wi_up", lambda s: (s["d"], s["ff"]), True),
    ("ffn/wo", lambda s: (s["ff"], s["d"]), True),
)


def sizes(cfg: dict) -> dict:
    """The sizes the weights and the reference need, from a configuration
    file's published keys."""
    return {"V": cfg["vocab_size"], "d": cfg["hidden_size"],
            "L": cfg["num_hidden_layers"], "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "ff": cfg["intermediate_size"], "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "init": float(cfg["initializer_range"])}


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


def layer_weights(key, layer, s: dict) -> dict:
    """One layer's leaves in float32, as a flat {path: array} dict."""
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    out = {}
    for i, (name, shape, matrix) in enumerate(_LAYER):
        if matrix:
            out[name] = jax.random.normal(jax.random.fold_in(lk, i),
                                          shape(s), jnp.float32) * s["init"]
        else:
            out[name] = jnp.ones(shape(s), jnp.float32)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


def make_weights(key, *, s: dict, dtype) -> dict:
    """The whole tree: embed, blocks_0 (layers stacked on axis 0),
    final_norm. Tied embeddings: no separate output head."""
    embed = jax.random.normal(jax.random.fold_in(key, 0), (s["V"], s["d"]),
                              jnp.float32) * s["init"]
    layers = jax.vmap(lambda l: layer_weights(key, l, s))(
        jnp.arange(s["L"], dtype=jnp.uint32))
    tree = {"embed": embed, "blocks_0": _nest(layers),
            "final_norm": {"scale": jnp.ones((s["d"],), jnp.float32)}}
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def weight_maker(s: dict, dtype):
    """The jitted maker: key -> tree. Calling the same maker twice gives
    bit-identical trees."""
    return jax.jit(functools.partial(make_weights, s=s, dtype=jnp.dtype(dtype)))


def check_layout(tree, want) -> None:
    """The benchmark's tree must match the program's parameter layout
    (`want`: the program's abstract params) leaf for leaf."""
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), tree)
    exp = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), want)
    if got != exp:
        raise RuntimeError(f"weight layout differs from the program's:\n"
                           f"  bench   {got}\n  program {exp}")
