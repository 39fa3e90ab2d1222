"""FLOP and byte counts: the forward count against the matrix-product
FLOPs XLA compiles for a smoke-size forward, the kernels' counts from the
operand shapes of lowered TPU calls, and the peaks table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_cell  # noqa: E402


def _compiled_matmul_flops(cfg, n):
    import jax
    import jax.numpy as jnp

    from repro.launch.hlo_analysis import analyze_hlo
    from repro.models import init_model
    from repro.models.transformer import forward_lm

    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0),
                                               cfg)[0])
    tok = jax.ShapeDtypeStruct((1, n), jnp.int32)
    text = jax.jit(lambda p, t: forward_lm(p, t, cfg)[0]).lower(
        params, tok).compile().as_text()
    return analyze_hlo(text)["matmul_flops"]


def test_forward_count_matches_compiled_matmuls():
    """Widening the MLP and the vocabulary adds exactly the FLOPs the
    count says; attention's matrix products are the same on both sides."""
    from bench import counts, program

    ref = tiny_cell.load_ref()
    n = 32
    cfg_file = dict(tiny_cell.TINY)
    base = program.model_config(cfg_file)
    wide = dict(cfg_file, intermediate_size=256, vocab_size=1024)
    f0 = _compiled_matmul_flops(base, n)
    f1 = _compiled_matmul_flops(program.model_config(wide), n)
    s0, s1 = ref.sizes(cfg_file), ref.sizes(wide)
    assert f1 - f0 == pytest.approx(
        counts.forward_flops(ref, s1, n, n)
        - counts.forward_flops(ref, s0, n, n), rel=1e-9)
    # the projections and the MLP alone stay under what XLA compiles
    no_attn = n * s0["L"] * (ref.layer_flops_per_token(s0)
                             - counts.attn_ops_per_token(s0["hkv"], 16, 16)
                             - counts.attn_ops_per_token(s0["hq"], 16, 16))
    assert no_attn + n * counts.logits_flops(s0) <= f0


SAMPLE = (
    '    %15:7 = stablehlo.custom_call @tpu_custom_call(%1, %5) {backend_'
    'config = "{}", kernel_name = "fastmax_decode_p2", operand_layouts = '
    '[dense<[2, 1, 0]> : tensor<3xindex>]} : (tensor<16x2x128xbf16>, '
    'tensor<16x1x128xbf16>, tensor<16x1x128xbf16>, tensor<16x16384x128xf32>)'
    ' -> (tensor<16x2x128xbf16>, tensor<16x16384x128xf32>)\n'
    '    %3 = stablehlo.add %1, %2 : tensor<4xf32>\n')


def test_kernel_calls_parse_lowered_text():
    from bench import counts
    calls = counts.kernel_calls(SAMPLE)
    assert len(calls) == 1
    name, ops, res = calls[0]
    assert name == "fastmax_decode_p2"
    assert ops[0] == ((16, 2, 128), "bf16")
    assert ops[3] == ((16, 16384, 128), "f32")
    assert res[1] == ((16, 16384, 128), "f32")


def test_kernel_cost_from_shapes():
    from bench import counts
    (name, ops, res), = counts.kernel_calls(SAMPLE)
    flops, nbytes = counts.kernel_cost(name, ops, res)
    per = 2.0 * (1 + 128 + 128 * 128) * 129
    assert flops == pytest.approx(16 * (1 + 2) * per)
    assert nbytes == 2 * (16 * 16384 * 128 * 4) + 2 * (16 * 2 * 128 * 2) \
        + 2 * (16 * 128 * 2)
    bwd = counts.kernel_cost("fastmax_causal_bwd_p2",
                             [((8, 2, 64, 16), "bf16"), ((8, 64, 16), "bf16"),
                              ((8, 64, 16), "bf16")], [])[0]
    fwd = counts.kernel_cost("fastmax_causal_p2",
                             [((8, 2, 64, 16), "bf16"), ((8, 64, 16), "bf16"),
                              ((8, 64, 16), "bf16")], [])[0]
    assert bwd == 2 * fwd
    with pytest.raises(KeyError):
        counts.kernel_cost("mystery_kernel", ops, res)


def test_train_flops_are_three_forwards():
    from bench import counts
    ref = tiny_cell.load_ref()
    s = ref.sizes(tiny_cell.TINY)
    assert counts.train_flops(ref, s, 64) \
        == 3 * counts.forward_flops(ref, s, 64, 64)


def test_unknown_device_kind_raises():
    from bench import device
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(device.BenchError):
        device.peaks("TPU v99")
