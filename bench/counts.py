"""Operations and bytes: the model's FLOPs per token, and each fastmax
kernel's operations and bytes per call, from the operand and result shapes
of the `tpu_custom_call`s in the lowered programs the window runs.

A layer's forward FLOPs per token are the cell's reference's
(`layer_flops_per_token` in `bench/refs/<reference>.py`): the work the
architecture really does per token, the routed top-k and shared experts
of a sparse layer and never all of them, and the mean over the layers
where they differ. This module adds the logits and the training passes.

Attention counts as fastmax's moment update plus query combine (the FAST
paper's factorized form, Eqs. 26-27): per token, per kv head for the update
and per query head for the combine, 2 (1 + D + D^2)(Dv + 1) operations at
order 2. The quadratic work inside a chunk and recomputation are not
counted, so a roofline share computed from these counts cannot pass 100%
through an inflated count. `kernel_cost` knows the three fastmax kernels;
a later kernel's operation and byte count goes in a new file under
`bench/`, which its roofline reader imports.
"""
from __future__ import annotations

import re

_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "i64": 8, "i32": 4,
          "ui32": 4, "i16": 2, "i8": 1, "ui8": 1, "i1": 1,
          "f8E4M3FN": 1, "f8E5M2": 1}
_TENSOR = re.compile(r"tensor<((?:\d+x)*)([a-zA-Z]\w*)>")
_NAME = re.compile(r'kernel_name = "([^"]+)"')
_SIG = re.compile(r"\}\s*:\s*\((.*)\)\s*->\s*(.*)$")


def _tensors(text: str) -> list:
    out = []
    for dims, dt in _TENSOR.findall(text):
        shape = tuple(int(x) for x in dims.split("x") if x)
        out.append((shape, dt))
    return out


def kernel_calls(program_text: str) -> list:
    """[(kernel name, operands, results)] of every Pallas TPU call in a
    lowered program, each operand/result a (shape, dtype) pair."""
    calls = []
    for line in program_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        name, sig = _NAME.search(line), _SIG.search(line)
        if name is None or sig is None:
            continue
        calls.append((name.group(1), _tensors(sig.group(1)),
                      _tensors(sig.group(2))))
    return calls


def nbytes(tensors) -> int:
    total = 0
    for shape, dt in tensors:
        n = 1
        for s in shape:
            n *= s
        total += n * _BYTES[dt]
    return total


def attn_ops_per_token(heads: int, d: int, dv: int, p: int = 2) -> float:
    """Update or combine operations for one token over `heads` heads."""
    feats = 1 + d + (d * d if p >= 2 else 0)
    return heads * 2.0 * feats * (dv + 1)


# kernel name -> passes over the factorized work (the backward's gradient
# of the combine and of the update is counted as twice the forward)
_PASSES = {"fastmax_causal_p2": 1, "fastmax_decode_p2": 1,
           "fastmax_causal_bwd_p2": 2}


def kernel_cost(name: str, operands, results):
    """(operations, bytes) of one call. The fastmax kernels take q first
    ([BH, G, N, D], or [BH, G, D] when decoding), then k [BH, N, D] and
    v [BH, N, Dv]. Bytes are every operand read once and every result
    written once."""
    if name not in _PASSES:
        raise KeyError(f"no operation count for kernel {name!r}")
    q, k, v = operands[0][0], operands[1][0], operands[2][0]
    bh, n, d = k
    g, dv = q[1], v[-1]
    ops = _PASSES[name] * n * (attn_ops_per_token(bh, d, dv)
                               + attn_ops_per_token(bh * g, d, dv))
    return ops, nbytes(operands) + nbytes(results)


def logits_flops(s: dict) -> float:
    return 2.0 * s["d"] * s["V"]


def forward_flops(ref, s: dict, tokens: int, logit_rows: int) -> float:
    """Forward FLOPs of `tokens` tokens through every layer, with logits
    for `logit_rows` of them; `ref` is the cell's reference module."""
    return tokens * s["L"] * ref.layer_flops_per_token(s) \
        + logit_rows * logits_flops(s)


def train_flops(ref, s: dict, tokens: int) -> float:
    """Forward and backward (twice the forward) of a training step with
    logits on every token; recomputation is not counted."""
    return 3.0 * forward_flops(ref, s, tokens, tokens)
