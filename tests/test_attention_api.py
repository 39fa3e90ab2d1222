"""repro.attention: spec parsing, registry capability routing, dispatcher
equivalence of every registered backend against the oracle, the unified
decode-state protocol, and the deprecation shims."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import (
    AttentionSpec,
    UnsupportedCapabilityError,
    attention,
    get_backend,
    init_state,
    list_backends,
    prefill,
    resolve,
    step,
)
from repro.core.ref import softmax_attention_ref

jax.config.update("jax_enable_x64", True)


def mk(rng, b, hq, hkv, n, d, dv, dtype=jnp.float64):
    q = jnp.asarray(rng.normal(size=(b, hq, n, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, n, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, n, dv)), dtype)
    return q, k, v


# ---------------------------------------------------------------------------
# spec
# ---------------------------------------------------------------------------


def test_parse_names():
    assert AttentionSpec.parse("softmax").family == "softmax"
    assert AttentionSpec.parse("fastmax").p == 2
    assert AttentionSpec.parse("fastmax1").p == 1
    assert AttentionSpec.parse("fastmax2").p == 2
    s = AttentionSpec.parse("fastmax1-kernel")
    assert (s.family, s.p, s.impl) == ("fastmax", 1, "kernel")
    assert AttentionSpec.parse(None) == AttentionSpec()
    with pytest.raises(ValueError):
        AttentionSpec.parse("flashmax")


def test_spec_validates():
    with pytest.raises(ValueError):
        AttentionSpec(family="nope")
    with pytest.raises(ValueError):
        AttentionSpec(impl="nope")
    with pytest.raises(ValueError):
        AttentionSpec(p=3)


def test_backend_names_cover_registry():
    """Every spec-reachable backend is registered, and vice versa."""
    reachable = {"softmax"} | {f"fastmax-{i}"
                               for i in ("oracle", "rowwise", "chunked",
                                         "kernel")} \
        | {"hybrid-chunked", "hybrid-kernel"}
    assert set(list_backends()) == reachable


def test_p_derivation_single_source():
    """The old `p = 1 if backend == "fastmax1" else 2` 4x duplication is now
    one field with one legacy mapping."""
    assert AttentionSpec.parse("fastmax1").legacy_name == "fastmax1"
    assert AttentionSpec.parse("fastmax2").legacy_name == "fastmax2"
    assert AttentionSpec(family="softmax").legacy_name == "softmax"


# ---------------------------------------------------------------------------
# dispatcher equivalence: every backend vs the oracle
# ---------------------------------------------------------------------------

ORACLE = AttentionSpec(impl="oracle")
# (B, Hq, Hkv, N, D, Dv): MHA and GQA (g=2, g=4)
EQ_SHAPES = [(1, 2, 2, 33, 8, 8), (2, 4, 2, 29, 8, 8), (1, 8, 2, 24, 4, 4)]


@pytest.mark.parametrize("shape", EQ_SHAPES)
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("p", [1, 2])
@pytest.mark.parametrize("impl", ["rowwise", "chunked", "kernel"])
def test_fastmax_backends_match_oracle(impl, p, causal, shape):
    rng = np.random.default_rng(hash((impl, p, causal, shape)) % 2**31)
    q, k, v = mk(rng, *shape)
    ref = attention(q, k, v, dataclasses.replace(ORACLE, p=p), causal=causal)
    out = attention(q, k, v,
                    AttentionSpec(family="fastmax", p=p, impl=impl,
                                  chunk_size=16),
                    causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-8, atol=1e-8)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", EQ_SHAPES)
def test_softmax_backend_matches_reference(causal, shape):
    rng = np.random.default_rng(hash((causal, shape)) % 2**31)
    q, k, v = mk(rng, *shape)
    out = attention(q, k, v, AttentionSpec(family="softmax"), causal=causal)
    # reference handles GQA by explicit broadcast
    g = q.shape[1] // k.shape[1]
    kb = jnp.repeat(k, g, axis=1)
    vb = jnp.repeat(v, g, axis=1)
    ref = softmax_attention_ref(q, kb, vb, causal=causal)
    # production softmax accumulates in f32 regardless of input dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# capability routing
# ---------------------------------------------------------------------------


def test_dropout_on_chunked_routes_to_rowwise():
    spec = AttentionSpec(impl="chunked", dropout_rate=0.25)
    assert resolve(spec, causal=True, dropout=True).name == "fastmax-rowwise"
    # and the dispatched result equals calling rowwise directly
    rng = np.random.default_rng(0)
    q, k, v = mk(rng, 1, 2, 2, 16, 4, 4, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    out = attention(q, k, v, spec, causal=True, rng=key)
    direct = attention(q, k, v, dataclasses.replace(spec, impl="rowwise"),
                       causal=True, rng=key)
    np.testing.assert_allclose(np.asarray(out), np.asarray(direct),
                               rtol=1e-6, atol=1e-6)


def test_dropout_strict_raises():
    spec = AttentionSpec(impl="chunked", dropout_rate=0.25)
    rng = np.random.default_rng(0)
    q, k, v = mk(rng, 1, 2, 2, 16, 4, 4, dtype=jnp.float32)
    with pytest.raises(UnsupportedCapabilityError):
        attention(q, k, v, spec, causal=True, rng=jax.random.PRNGKey(0),
                  strict=True)


def test_kernel_dropout_routes_through_chain_to_rowwise():
    spec = AttentionSpec(impl="kernel", dropout_rate=0.25)
    assert resolve(spec, causal=True, dropout=True).name == "fastmax-rowwise"


def test_kv_mask_on_kernel_routes_to_chunked():
    spec = AttentionSpec(impl="kernel")
    assert resolve(spec, causal=False, kv_mask=True).name == "fastmax-chunked"


def test_no_capable_backend_raises():
    # dropout has no softmax-family implementation
    spec = AttentionSpec(family="softmax", dropout_rate=0.25)
    with pytest.raises(UnsupportedCapabilityError):
        resolve(spec, causal=True, dropout=True)


def test_kernel_off_platform_still_serves():
    """Off-TPU the kernel backend interprets instead of rerouting."""
    b = resolve(AttentionSpec(impl="kernel"), causal=True)
    assert b.name == "fastmax-kernel"


def test_resolution_is_logged(caplog):
    import repro.attention.registry as R
    R._LOGGED.clear()
    with caplog.at_level("INFO", logger="repro.attention"):
        resolve(AttentionSpec(impl="chunked", dropout_rate=0.5),
                causal=True, dropout=True)
    assert any("routing to fastmax-rowwise" in r.message
               for r in caplog.records)


# ---------------------------------------------------------------------------
# unified decode-state protocol
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [
    AttentionSpec(family="fastmax", p=2, chunk_size=8),
    AttentionSpec(family="fastmax", p=1, chunk_size=8),
    AttentionSpec(family="softmax"),
], ids=["fastmax2", "fastmax1", "softmax"])
def test_prefill_then_step_equals_full_causal(spec):
    """prefill(prompt) + step(token)* must reproduce full causal attention
    for BOTH state families (moments and KV cache)."""
    rng = np.random.default_rng(7)
    b, hq, hkv, n, d = 2, 4, 2, 21, 8
    q, k, v = mk(rng, b, hq, hkv, n, d, d)
    full = attention(
        q, k, v,
        spec if spec.family == "softmax"
        else dataclasses.replace(spec, impl="oracle"),
        causal=True)
    st = init_state(spec, batch=b, n_kv_heads=hkv, q_head_dim=d,
                    v_head_dim=d, max_len=n, dtype=jnp.float64)
    pre = 13
    o_pre, st = prefill(q[:, :, :pre], k[:, :, :pre], v[:, :, :pre], spec,
                        state=st)
    np.testing.assert_allclose(np.asarray(o_pre), np.asarray(full[:, :, :pre]),
                               rtol=1e-6, atol=1e-7)
    for t in range(pre, n):
        o_t, st = step(st, q[:, :, t:t + 1], k[:, :, t:t + 1],
                       v[:, :, t:t + 1], spec)
        np.testing.assert_allclose(np.asarray(o_t[:, :, 0]),
                                   np.asarray(full[:, :, t]),
                                   rtol=1e-6, atol=1e-7)


def test_softmax_prefill_kv_mask_persists_through_steps():
    """Padding keys masked at prefill must stay invisible in later decode
    steps (the mask is carried in the KV cache, not rebuilt from length)."""
    rng = np.random.default_rng(11)
    spec = AttentionSpec(family="softmax")
    b, h, n, d = 1, 2, 8, 4
    q, k, v = mk(rng, b, h, h, n, d, d)
    pad = 3  # prompt = 5 real tokens + 3 padding
    mask = jnp.concatenate([jnp.ones((b, h, n - pad)),
                            jnp.zeros((b, h, pad))], axis=-1)
    st = init_state(spec, batch=b, n_kv_heads=h, q_head_dim=d, v_head_dim=d,
                    max_len=n + 2, dtype=jnp.float64)
    _, st = prefill(q, k, v, spec, state=st, kv_mask=mask)
    # reference: same cache contents but padding rows dropped entirely
    st2 = init_state(spec, batch=b, n_kv_heads=h, q_head_dim=d, v_head_dim=d,
                     max_len=n + 2, dtype=jnp.float64)
    _, st2 = prefill(q[:, :, :n - pad], k[:, :, :n - pad], v[:, :, :n - pad],
                     spec, state=st2)
    q1, k1, v1 = mk(rng, b, h, h, 1, d, d)
    o_masked, _ = step(st, q1, k1, v1, spec)
    # the truncated reference appends at a different slot; align lengths:
    # masked cache has length n with 3 dead slots -> same attention set
    o_trunc, _ = step(st2, q1, k1, v1, spec)
    np.testing.assert_allclose(np.asarray(o_masked), np.asarray(o_trunc),
                               rtol=1e-6, atol=1e-7)


def test_fastmax_resumable_prefill_3d_kv_mask_bitwise():
    """Chunked (offset=...) prefill with a per-head [B, Hkv, N] kv_mask
    must be BITWISE equal to the whole-prompt offset prefill: the carried
    moments seed the scan exactly, and per-head masking survives the
    split."""
    rng = np.random.default_rng(21)
    spec = AttentionSpec(family="fastmax", p=2, chunk_size=16)
    b, hq, hkv, n, d = 2, 4, 2, 32, 8
    q, k, v = mk(rng, b, hq, hkv, n, d, d)
    mask = (rng.random((b, hkv, n)) < 0.7).astype(np.float64)
    mask[..., 0] = 1.0                 # keep row 0 denominators non-degenerate
    mask = jnp.asarray(mask)

    def fresh():
        return init_state(spec, batch=b, n_kv_heads=hkv, q_head_dim=d,
                          v_head_dim=d, max_len=n, dtype=jnp.float64)

    zero = jnp.asarray(0, jnp.int32)
    o_full, st_full = prefill(q, k, v, spec, state=fresh(), kv_mask=mask,
                              offset=zero)
    c = 16                             # split exactly at a chunk boundary
    st = fresh()
    o1, st = prefill(q[:, :, :c], k[:, :, :c], v[:, :, :c], spec, state=st,
                     kv_mask=mask[:, :, :c], offset=zero)
    o2, st = prefill(q[:, :, c:], k[:, :, c:], v[:, :, c:], spec, state=st,
                     kv_mask=mask[:, :, c:],
                     offset=jnp.asarray(c, jnp.int32))
    got = jnp.concatenate([o1, o2], axis=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(o_full))
    for name, a, ref in zip(st.moments._fields, st.moments,
                            st_full.moments):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(ref),
                                      err_msg=name)


def test_fastmax_kernel_resumable_prefill_seeds_kernel(monkeypatch):
    """Under the kernel route a resumable (offset) prefill chunk runs the
    forward kernel seeded with the carried moments: split at a chunk
    boundary it matches the whole-prompt kernel prefill (to f64 rounding:
    the seeded kernel adds the carry in a different order) and the jnp
    scan route."""
    from repro.attention import registry as _reg

    monkeypatch.setenv("REPRO_DECODE_KERNEL", "1")
    rng = np.random.default_rng(23)
    spec = AttentionSpec(family="fastmax", p=2, impl="kernel", chunk_size=8)
    b, hq, hkv, n, d = 1, 4, 2, 24, 8
    q, k, v = mk(rng, b, hq, hkv, n, d, d)
    mask = (rng.random((b, hkv, n)) < 0.7).astype(np.float64)
    mask[..., 0] = 1.0
    mask = jnp.asarray(mask)

    def fresh():
        return init_state(spec, batch=b, n_kv_heads=hkv, q_head_dim=d,
                          v_head_dim=d, max_len=n, dtype=jnp.float64)

    o_full, st_full = prefill(q, k, v, spec, state=fresh(), kv_mask=mask)
    c = 16
    o1, st = prefill(q[:, :, :c], k[:, :, :c], v[:, :, :c], spec,
                     state=fresh(), kv_mask=mask[:, :, :c],
                     offset=jnp.asarray(0, jnp.int32))
    o2, st = prefill(q[:, :, c:], k[:, :, c:], v[:, :, c:], spec, state=st,
                     kv_mask=mask[:, :, c:],
                     offset=jnp.asarray(c, jnp.int32))
    assert any("kernel seeded with the carried moments" in m
               for m in _reg._LOGGED)
    got = jnp.concatenate([o1, o2], axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(o_full),
                               rtol=1e-12, atol=1e-14)
    for name, a, ref in zip(st.moments._fields, st.moments,
                            st_full.moments):
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref),
                                   rtol=1e-12, atol=1e-14, err_msg=name)
    o_jnp, _ = prefill(q, k, v, dataclasses.replace(spec, impl="chunked"),
                       state=fresh(), kv_mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(o_jnp),
                               rtol=1e-10, atol=1e-12)


def test_softmax_resumable_prefill_3d_kv_mask_matches_whole():
    """Same split through the KV-cache resume path: outputs match the
    whole-prompt call and a later decode step sees identical caches (the
    per-head mask rides the cache's mask lane across the resume)."""
    rng = np.random.default_rng(22)
    spec = AttentionSpec(family="softmax")
    b, hq, hkv, n, d = 1, 4, 2, 32, 8
    q, k, v = mk(rng, b, hq, hkv, n, d, d)
    mask = (rng.random((b, hkv, n)) < 0.7).astype(np.float64)
    mask[..., 0] = 1.0
    mask = jnp.asarray(mask)

    def fresh():
        return init_state(spec, batch=b, n_kv_heads=hkv, q_head_dim=d,
                          v_head_dim=d, max_len=n + 2, dtype=jnp.float64)

    o_full, st_full = prefill(q, k, v, spec, state=fresh(), kv_mask=mask)
    c = 16
    st = fresh()
    o1, st = prefill(q[:, :, :c], k[:, :, :c], v[:, :, :c], spec, state=st,
                     kv_mask=mask[:, :, :c],
                     offset=jnp.asarray(0, jnp.int32))
    o2, st = prefill(q[:, :, c:], k[:, :, c:], v[:, :, c:], spec, state=st,
                     kv_mask=mask[:, :, c:],
                     offset=jnp.asarray(c, jnp.int32))
    got = jnp.concatenate([o1, o2], axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(o_full),
                               rtol=2e-5, atol=2e-5)
    q1, k1, v1 = mk(rng, b, hq, hkv, 1, d, d)
    o_a, _ = step(st, q1, k1, v1, spec)
    o_b, _ = step(st_full, q1, k1, v1, spec)
    np.testing.assert_allclose(np.asarray(o_a), np.asarray(o_b),
                               rtol=2e-5, atol=2e-5)


def test_parse_rejects_softmax_impl_suffix():
    with pytest.raises(ValueError):
        AttentionSpec.parse("softmax-kernel")


def test_step_with_wrong_family_state_raises_clearly():
    st = init_state(AttentionSpec(family="softmax"), batch=1, n_kv_heads=1,
                    q_head_dim=4, v_head_dim=4, max_len=4)
    rng = np.random.default_rng(0)
    q, k, v = mk(rng, 1, 1, 1, 1, 4, 4, dtype=jnp.float32)
    with pytest.raises(ValueError, match="different attention family"):
        step(st, q, k, v, AttentionSpec())


def test_init_state_shapes():
    soft = init_state(AttentionSpec(family="softmax"), batch=2, n_kv_heads=3,
                      q_head_dim=8, v_head_dim=4, max_len=10)
    assert soft.moments is None
    assert soft.kv.k.shape == (2, 3, 10, 8)
    assert soft.kv.v.shape == (2, 3, 10, 4)
    fast = init_state(AttentionSpec(), batch=2, n_kv_heads=3, q_head_dim=8,
                      v_head_dim=4, max_len=10)
    assert fast.kv is None
    assert fast.moments.m2.shape == (2, 3, 8, 8, 4)


def test_init_state_requires_decode_capability():
    with pytest.raises(ValueError):
        init_state(AttentionSpec(impl="oracle"), batch=1, n_kv_heads=1,
                   q_head_dim=4, v_head_dim=4, max_len=4)


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------


def test_modelconfig_legacy_string_pair_shim():
    from repro.models.transformer import ModelConfig

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg = ModelConfig(attn_backend="fastmax1", attn_impl="kernel")
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert (cfg.attn.family, cfg.attn.p, cfg.attn.impl) == \
        ("fastmax", 1, "kernel")
    # dataclasses.replace with the legacy kwarg still works
    cfg2 = dataclasses.replace(cfg, attn_backend="softmax")
    assert cfg2.attn.family == "softmax"
    # plain replace of unrelated fields must NOT disturb the spec
    cfg3 = dataclasses.replace(cfg, d_model=128)
    assert cfg3.attn == cfg.attn


def test_core_fastmax_attention_shim_matches_dispatcher():
    from repro.core import fastmax_attention

    rng = np.random.default_rng(9)
    q, k, v = mk(rng, 1, 4, 2, 18, 4, 4)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        old = fastmax_attention(q, k, v, p=2, causal=True, impl="chunked",
                                chunk_size=8)
    new = attention(q, k, v, AttentionSpec(p=2, impl="chunked", chunk_size=8),
                    causal=True)
    np.testing.assert_allclose(np.asarray(old), np.asarray(new))


def test_core_fastmaxconfig_alias():
    import repro.core as core

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cls = core.FastmaxConfig
    assert cls is AttentionSpec
    assert any(issubclass(x.category, DeprecationWarning) for x in w)


def test_chunk_size_inheritance_from_model_config():
    from repro.models.transformer import ModelConfig

    cfg = ModelConfig(chunk_size=64)
    assert cfg.attn.chunk_size is None
    assert cfg.attn_spec.chunk_size == 64
    cfg2 = dataclasses.replace(cfg, chunk_size=16)
    assert cfg2.attn_spec.chunk_size == 16  # replace() must not freeze it
    pinned = ModelConfig(attn=AttentionSpec(chunk_size=32), chunk_size=64)
    assert pinned.attn_spec.chunk_size == 32


def test_registry_backend_lookup_error():
    with pytest.raises(KeyError):
        get_backend("does-not-exist")


# ---------------------------------------------------------------------------
# decode_kernel capability + native-state kernel routing
# ---------------------------------------------------------------------------


def test_decode_kernel_capability_declared():
    assert get_backend("fastmax-kernel").caps.decode_kernel
    assert not get_backend("fastmax-chunked").caps.decode_kernel
    assert not get_backend("softmax").caps.decode_kernel


def test_use_decode_kernel_env_routing(monkeypatch, caplog):
    import logging

    from repro.attention.state import use_decode_kernel

    spec = AttentionSpec(family="fastmax", impl="kernel")
    caplog.set_level(logging.INFO, logger="repro.attention")
    # off-TPU default: logged fallback to the jnp moment step
    monkeypatch.delenv("REPRO_DECODE_KERNEL", raising=False)
    if jax.default_backend() != "tpu":
        assert not use_decode_kernel(spec)
    # forced: kernel path even off-TPU (interpret)
    monkeypatch.setenv("REPRO_DECODE_KERNEL", "1")
    assert use_decode_kernel(spec)
    # disabled: never the kernel
    monkeypatch.setenv("REPRO_DECODE_KERNEL", "0")
    assert not use_decode_kernel(spec)
    # only backends with the capability route to the kernel
    monkeypatch.setenv("REPRO_DECODE_KERNEL", "1")
    assert not use_decode_kernel(AttentionSpec(family="fastmax",
                                               impl="chunked"))
    assert not use_decode_kernel(AttentionSpec(family="softmax"))
    from repro.attention import registry as _reg
    assert any("native-state kernel" in m for m in _reg._LOGGED)


def test_prefill_step_kernel_path_matches_oracle(monkeypatch):
    """The forced kernel decode path (prefill carry emitted by the forward
    kernel + fused decode steps) reproduces full causal attention."""
    monkeypatch.setenv("REPRO_DECODE_KERNEL", "1")
    spec = AttentionSpec(family="fastmax", p=2, impl="kernel", chunk_size=8)
    rng = np.random.default_rng(9)
    b, hq, hkv, n, d = 1, 4, 2, 21, 8
    q, k, v = mk(rng, b, hq, hkv, n, d, d)
    full = attention(q, k, v, dataclasses.replace(spec, impl="oracle"),
                     causal=True)
    st = init_state(spec, batch=b, n_kv_heads=hkv, q_head_dim=d,
                    v_head_dim=d, max_len=n, dtype=jnp.float64)
    pre = 13
    o_pre, st = prefill(q[:, :, :pre], k[:, :, :pre], v[:, :, :pre], spec,
                        state=st)
    np.testing.assert_allclose(np.asarray(o_pre),
                               np.asarray(full[:, :, :pre]),
                               rtol=1e-6, atol=1e-7)
    for t in range(pre, n):
        o_t, st = step(st, q[:, :, t:t + 1], k[:, :, t:t + 1],
                       v[:, :, t:t + 1], spec)
        np.testing.assert_allclose(np.asarray(o_t[:, :, 0]),
                                   np.asarray(full[:, :, t]),
                                   rtol=1e-6, atol=1e-7)
