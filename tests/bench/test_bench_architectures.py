"""What is particular to an architecture lives in its configuration file
and its reference module: qwen3's seeded weights are the same bit for bit
as before its leaf tables left the shared harness; the program's config is
mapped from published keys by a table; a sparse-expert layout's leaf
tables give the program's tree, and its FLOP count charges only the
experts a token runs."""
import dataclasses
import hashlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_cell  # noqa: E402

SEED = 2**33 + 5
# per leaf, the first 16 hex digits of the sha256 of its bytes, as the
# maker with qwen3's layer table inside bench/weights.py made them
GOLDEN = {
    "TINY": {
        "['blocks_0']['ffn']['wi_gate']": "ae40c5b90b787bb3",
        "['blocks_0']['ffn']['wi_up']": "72e575ec983bfb5b",
        "['blocks_0']['ffn']['wo']": "e58fe643d8f00dbb",
        "['blocks_0']['mixer']['k_norm_scale']": "b638277a8690e175",
        "['blocks_0']['mixer']['q_norm_scale']": "b638277a8690e175",
        "['blocks_0']['mixer']['wk']": "97e2b448958063e1",
        "['blocks_0']['mixer']['wo']": "2eb9ff325e880d20",
        "['blocks_0']['mixer']['wq']": "66a4c23d16b4e3b2",
        "['blocks_0']['mixer']['wv']": "33df74e7758514e4",
        "['blocks_0']['norm1']['scale']": "02722f124d0f1736",
        "['blocks_0']['norm2']['scale']": "02722f124d0f1736",
        "['embed']": "0405e959ded6f9e3",
        "['final_norm']['scale']": "2f20cd03c9cd392a"},
    "WIDE": {
        "['blocks_0']['ffn']['wi_gate']": "282cbce1f892d681",
        "['blocks_0']['ffn']['wi_up']": "76dab02df5c3a77f",
        "['blocks_0']['ffn']['wo']": "0f0457a20e1adbed",
        "['blocks_0']['mixer']['k_norm_scale']": "1ede9ebfa1ad011b",
        "['blocks_0']['mixer']['q_norm_scale']": "1ede9ebfa1ad011b",
        "['blocks_0']['mixer']['wk']": "35001d59f2a6245d",
        "['blocks_0']['mixer']['wo']": "b29ecf1ecbcb969a",
        "['blocks_0']['mixer']['wq']": "728a041f47a7f454",
        "['blocks_0']['mixer']['wv']": "3ec9b50f683ac070",
        "['blocks_0']['norm1']['scale']": "ad755ff209a358c0",
        "['blocks_0']['norm2']['scale']": "ad755ff209a358c0",
        "['embed']": "d537dd9968084e36",
        "['final_norm']['scale']": "8e6b548203bfc086"},
}

# the program's kimi-k2 smoke preset under published (DeepSeek-V3) keys
KIMI = {
    "reference": "kimi", "attention_bias": False, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "initializer_range": 0.02,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 3, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 50000, "tie_word_embeddings": False,
    "torch_dtype": "float32", "vocab_size": 512, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "first_k_dense_replace": 1,
    "run": {"arch": "kimi-k2-1t-a32b", "attention": "fastmax2",
            "chunk_size": 16}}


def _kimi_ref():
    from bench import run
    return run.load_module(os.path.join(tiny_cell.HERE, "kimi_ref.py"),
                           "bench_ref_kimi")


def _digests(tree):
    import jax
    return {jax.tree_util.keystr(p):
            hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_qwen3_weights_are_bit_identical(name):
    from bench.weights import seed_key, weight_maker
    cfg = getattr(tiny_cell, name)
    ref = tiny_cell.load_ref()
    tree = weight_maker(ref, ref.sizes(cfg), cfg["torch_dtype"])(
        seed_key(SEED))
    assert _digests(tree) == GOLDEN[name]


def test_sparse_layout_matches_program():
    from bench import program
    from bench.weights import check_layout, seed_key, weight_maker
    ref = _kimi_ref()
    s = ref.sizes(KIMI)
    w = weight_maker(ref, s, "float32")(seed_key(SEED))
    check_layout(w, program.abstract_params(program.model_config(KIMI)))
    ffn = w["blocks_0"]["ffn"]
    assert ffn["router"].shape == (2, 64, 8)
    assert ffn["wi_gate"].shape == (2, 8, 64, 32)
    assert ffn["shared_wo"].shape == (2, 32, 64)
    assert w["dense_0"]["ffn"]["wi_gate"].shape == (64, 128)
    # dense_0 is layer 0, the two groups layers 1 and 2; unembed has a
    # fold of its own: no two leaves share a key
    wq = [np.asarray(w["dense_0"]["mixer"]["wq"])] \
        + list(np.asarray(w["blocks_0"]["mixer"]["wq"]))
    assert not any(np.array_equal(a, b) for i, a in enumerate(wq)
                   for b in wq[i + 1:])
    assert not np.array_equal(np.asarray(w["embed"]),
                              np.asarray(w["unembed"]).T)


def test_sparse_count_charges_routed_top_k_and_shared_experts():
    ref = _kimi_ref()
    s = ref.sizes(KIMI)
    f = ref.layer_flops_per_token
    d, ffe, sparse_share = s["d"], s["ffe"], (s["L"] - s["dense"]) / s["L"]
    # eight times the experts: only the router grows
    assert f(dict(s, E=64)) - f(s) == pytest.approx(
        2.0 * d * 56 * sparse_share)
    # one more routed expert a token, or one more shared one: one
    # expert's SwiGLU on each sparse layer
    expert = 2.0 * 3 * d * ffe * sparse_share
    assert f(dict(s, k=3)) - f(s) == pytest.approx(expert)
    assert f(dict(s, shared=2)) - f(s) == pytest.approx(expert)
    all_experts = f(dict(s, k=s["E"]))
    assert f(s) < all_experts - (s["E"] - s["k"] - 1) * expert


def test_program_config_from_published_keys():
    from bench import program
    from repro.attention import AttentionSpec
    from repro.configs import get_smoke_config
    want = dataclasses.replace(get_smoke_config("kimi-k2-1t-a32b"),
                               norm_eps=1e-6,
                               attn=AttentionSpec.parse("fastmax2"))
    assert program.model_config(KIMI) == want
    extra = dict(KIMI, run=dict(KIMI["run"], program={
        "pattern": ["attn:moe", "attn:moe"], "capacity_factor": 2.0,
        "first_k_dense": 0}))
    got = program.model_config(extra)
    assert got.pattern == ("attn:moe", "attn:moe")
    assert (got.capacity_factor, got.first_k_dense) == (2.0, 0)


@pytest.mark.parametrize("change", [
    {"run": dict(KIMI["run"], program={"n_expert": 4})},
    {"hidden_act": "gelu"},
    {"attention_bias": True},
])
def test_program_config_refuses_what_the_preset_cannot_do(change):
    from bench import program
    with pytest.raises(ValueError):
        program.model_config(dict(KIMI, **change))
