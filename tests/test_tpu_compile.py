"""The Pallas kernels compile for a TPU v5e at real widths (no chip needed).

Each test lowers and compiles one kernel with `interpret=False` for one
chip of a described `v5e:2x2` topology, at D = Dv = 128, p = 2, bf16
inputs and qwen3-1.7b's GQA group (16 query / 8 kv heads): what Mosaic
refuses here (block tiling, unsupported primitives, scoped-VMEM overflow)
it would refuse on the chip. The topology is described inside a module
fixture, which skips where libtpu cannot describe it.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fastmax_causal import fastmax_causal_pallas
from repro.kernels.fastmax_causal_bwd import fastmax_causal_bwd_pallas
from repro.kernels.fastmax_decode import fastmax_decode_pallas
from repro.kernels.fastmax_noncausal import fastmax_noncausal_pallas
from repro.kernels.hybrid_causal import hybrid_causal_pallas

B, HQ, HKV, N, D = 1, 16, 8, 1024, 128
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # other test modules turn on x64 when imported; the chip runs 32-bit
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_x64", x64)


@pytest.fixture(scope="module")
def shapes(topo):
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    f32 = jnp.float32
    state = (s((B, HKV, D), f32), s((B, HKV, D, D), f32),
             s((B, HKV, D, D, D), f32), s((B, HKV), f32),
             s((B, HKV, D), f32), s((B, HKV, D, D), f32))
    return {
        "q": s((B, HQ, N, D)), "k": s((B, HKV, N, D)), "v": s((B, HKV, N, D)),
        "mask": s((B, 1, N), f32), "state": state,
        "q1": s((4, HQ, 1, D)), "k1": s((4, HKV, 1, D)),
        "v1": s((4, HKV, 1, D)),
        "state4": tuple(s((4,) + x.shape[1:], f32) for x in state),
        "live4": s((4,), jnp.bool_),
    }


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _causal(return_state, seeded):
    def fn(sh):
        if seeded:
            return _compile(
                lambda q, k, v, m, st: fastmax_causal_pallas(
                    q, k, v, m, p=2, return_state=True, init_state=st),
                sh["q"], sh["k"], sh["v"], sh["mask"], sh["state"])
        return _compile(
            lambda q, k, v, m: fastmax_causal_pallas(
                q, k, v, m, p=2, return_state=return_state),
            sh["q"], sh["k"], sh["v"], sh["mask"])
    return fn


def _bwd(return_dstate):
    def fn(sh):
        return _compile(
            lambda q, k, v, st, do: fastmax_causal_bwd_pallas(
                q, k, v, st, do, p=2, return_dstate=return_dstate),
            sh["q"], sh["k"], sh["v"], sh["state"], sh["q"])
    return fn


def _decode(sh):
    return _compile(
        lambda q, k, v, st: fastmax_decode_pallas(q, k, v, st, p=2),
        sh["q1"], sh["k1"], sh["v1"], sh["state4"])


def _decode_live(sh):
    return _compile(
        lambda q, k, v, st, live: fastmax_decode_pallas(q, k, v, st, live,
                                                        p=2),
        sh["q1"], sh["k1"], sh["v1"], sh["state4"], sh["live4"])


def _noncausal(sh):
    return _compile(lambda q, k, v: fastmax_noncausal_pallas(q, k, v, p=2),
                    sh["q"], sh["k"], sh["v"])


def _hybrid(sh):
    return _compile(
        lambda q, k, v, m: hybrid_causal_pallas(
            q, k, v, m, p=2, window=64, return_state=True),
        sh["q"], sh["k"], sh["v"], sh["mask"])


@pytest.mark.parametrize("case", [
    pytest.param(_causal(False, False), id="causal"),
    pytest.param(_causal(True, False), id="causal-return_state"),
    pytest.param(_causal(True, True), id="causal-init_state"),
    pytest.param(_bwd(False), id="causal_bwd"),
    pytest.param(_bwd(True), id="causal_bwd-return_dstate"),
    pytest.param(_decode, id="decode"),
    pytest.param(_decode_live, id="decode-live"),
    pytest.param(_noncausal, id="noncausal"),
    pytest.param(_hybrid, id="hybrid"),
])
def test_kernel_compiles_for_v5e(shapes, case):
    case(shapes)


def test_decode_kernel_counts_unchanged_by_live_flag(topo):
    """The live flag adds one int32 per (slot, kv head) to the decode
    kernel's operands: at qwen3-1.7b's serving widths (3 slots, 8 kv
    heads, D = Dv = 128) the benchmark's operation and byte counts of a
    call read as they do for the kernel without it (409 MB a call)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench.counts import kernel_calls, kernel_cost

    one = SingleDeviceSharding(topo.devices[0])
    b, bh, g = 3, 3 * HKV, HQ // HKV
    f32, bf16 = jnp.float32, jnp.bfloat16

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    state = [(b, HKV, D), (b, HKV, D, D), (b, HKV, D, D, D), (b, HKV),
             (b, HKV, D), (b, HKV, D, D)]
    text = jax.jit(
        lambda q, k, v, st, live: fastmax_decode_pallas(q, k, v, st, live,
                                                        p=2)
    ).lower(s((b, HQ, 1, D), bf16), s((b, HKV, 1, D), bf16),
            s((b, HKV, 1, D), bf16), tuple(s(x, f32) for x in state),
            s((b,), jnp.bool_)).as_text()
    (call,) = kernel_calls(text)
    assert call[0] == "fastmax_decode_p2"
    ops, nbytes = kernel_cost(*call)
    # the kernel's operands and results as they were without the flag:
    # q, k, v, the six moments, q-hat transposed and the k-hat column;
    # the output rows and the six moments
    moments = [((bh, 1, D), "f32"), ((bh, D, D), "f32"),
               ((bh, D * D, D), "f32"), ((bh, 1, 1), "f32"),
               ((bh, 1, D), "f32"), ((bh, D, D), "f32")]
    before = ([((bh, g, D), "bf16"), ((bh, 1, D), "bf16"),
               ((bh, 1, D), "bf16")] + moments
              + [((bh * D, g), "f32"), ((bh * D, 1), "f32")],
              [((bh, g, D), "bf16")] + moments)
    ops0, nbytes0 = kernel_cost("fastmax_decode_p2", *before)
    assert ops == ops0
    assert abs(nbytes - nbytes0) <= 1024
    assert 408e6 < nbytes0 < 410e6
