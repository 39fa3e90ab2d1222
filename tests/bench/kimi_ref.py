"""Sizes, leaf tables and FLOP count of the program's kimi-k2 layout, for
the layout tests: dense first layers, then sparse layers, each with a
router, stacked routed experts and shared experts; an untied head. No
forward: the tests compare the weights' tree with the program's and read
the count."""
from bench.counts import attn_ops_per_token


def sizes(cfg: dict) -> dict:
    return {"V": cfg["vocab_size"], "d": cfg["hidden_size"],
            "L": cfg["num_hidden_layers"], "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "ff": cfg["intermediate_size"], "E": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"],
            "ffe": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"],
            "dense": cfg["first_k_dense_replace"],
            "init": float(cfg["initializer_range"])}


_TOP = (
    ("embed", lambda s: (s["V"], s["d"]), "normal"),
    ("final_norm/scale", lambda s: (s["d"],), "ones"),
    ("unembed", lambda s: (s["d"], s["V"]), "normal"),
)
_ATTN = (
    ("norm1/scale", lambda s: (s["d"],), "ones"),
    ("mixer/wq", lambda s: (s["d"], s["hq"], s["hd"]), "normal"),
    ("mixer/wk", lambda s: (s["d"], s["hkv"], s["hd"]), "normal"),
    ("mixer/wv", lambda s: (s["d"], s["hkv"], s["hd"]), "normal"),
    ("mixer/wo", lambda s: (s["hq"], s["hd"], s["d"]), "normal"),
    ("norm2/scale", lambda s: (s["d"],), "ones"),
)
_DENSE = _ATTN + (
    ("ffn/wi_gate", lambda s: (s["d"], s["ff"]), "normal"),
    ("ffn/wi_up", lambda s: (s["d"], s["ff"]), "normal"),
    ("ffn/wo", lambda s: (s["ff"], s["d"]), "normal"),
)
_SPARSE = _ATTN + (
    ("ffn/router", lambda s: (s["d"], s["E"]), "normal"),
    ("ffn/wi_gate", lambda s: (s["E"], s["d"], s["ffe"]), "normal"),
    ("ffn/wi_up", lambda s: (s["E"], s["d"], s["ffe"]), "normal"),
    ("ffn/wo", lambda s: (s["E"], s["ffe"], s["d"]), "normal"),
    ("ffn/shared_wi_gate", lambda s: (s["d"], s["ffe"] * s["shared"]),
     "normal"),
    ("ffn/shared_wi_up", lambda s: (s["d"], s["ffe"] * s["shared"]),
     "normal"),
    ("ffn/shared_wo", lambda s: (s["ffe"] * s["shared"], s["d"]), "normal"),
)


def leaf_tables(s: dict) -> dict:
    return {"top": _TOP, "dense": (_DENSE,) * s["dense"],
            "blocks": (_SPARSE,)}


def layer_flops_per_token(s: dict) -> float:
    """Forward FLOPs of a layer for one token, the mean over the dense and
    the sparse layers: a sparse layer runs its router, its top-k routed
    experts and its shared experts, never all its experts."""
    d, hq, hkv, hd = s["d"], s["hq"], s["hkv"], s["hd"]
    attn = 2.0 * d * hd * (2 * hq + 2 * hkv) \
        + attn_ops_per_token(hkv, hd, hd) + attn_ops_per_token(hq, hd, hd)
    dense = 2.0 * 3 * d * s["ff"]
    sparse = 2.0 * d * s["E"] + 2.0 * 3 * d * s["ffe"] * (s["k"]
                                                         + s["shared"])
    n_dense = s["dense"]
    return attn + (n_dense * dense + (s["L"] - n_dense) * sparse) / s["L"]
