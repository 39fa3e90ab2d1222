"""The plain float32 reference against the program's model at the smoke
size on the CPU, on the benchmark's seeded weights: logits, loss and
gradient agree to float32 rounding."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_cell  # noqa: E402


@pytest.fixture(scope="module")
def setup():
    import jax
    import jax.numpy as jnp

    from bench import program
    from bench.weights import check_layout, seed_key, sizes, weight_maker

    s = sizes(tiny_cell.TINY)
    cfg = program.model_config(tiny_cell.TINY)
    w = weight_maker(s, "float32")(seed_key(2**31 + 12345))
    check_layout(w, program.abstract_params(cfg))
    ref = tiny_cell.load_ref()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, s["V"], (2, 40)).astype(np.int32)
    targets = rng.integers(0, s["V"], (2, 40)).astype(np.int32)
    return jax, jnp, s, cfg, w, ref, tokens, targets


def test_logits_match_program(setup):
    jax, jnp, s, cfg, w, ref, tokens, targets = setup
    from repro.models.transformer import forward_lm
    n = tokens.shape[1]
    want = np.asarray(forward_lm(w, jnp.asarray(tokens[:1]), cfg)[0][0])
    pad = np.zeros(ref.bucket(n), np.int32)
    pad[:n] = tokens[0]
    tgt = np.zeros_like(pad)
    tgt[:n] = targets[0]
    best, at, arg = (np.asarray(x)[:n] for x in ref.row_stats(
        w, pad, tgt, s=tuple(sorted(s.items())), mode="f32"))
    np.testing.assert_allclose(best, want.max(-1), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(at, want[np.arange(n), targets[0]],
                               rtol=2e-4, atol=2e-5)
    assert np.mean(arg == want.argmax(-1)) > 0.9


def test_served_gaps_zero_for_the_reference_own_tokens(setup):
    jax, jnp, s, cfg, w, ref, tokens, _ = setup
    from repro.models.transformer import forward_lm
    prompt = tokens[0, :30]
    seq = tokens[0].copy()
    for i in range(30, 40):       # greedy continuation by the program
        lg = np.asarray(forward_lm(w, jnp.asarray(seq[None, :i]), cfg)[0])
        seq[i] = int(lg[0, -1].argmax())
    gaps, = ref.served_gaps(w, s, [(prompt, seq[30:40])])
    assert gaps.shape == (10,)
    assert float(gaps.max()) < 1e-4


def test_loss_and_gradient_match_program(setup):
    jax, jnp, s, cfg, w, ref, tokens, targets = setup
    from repro.models.transformer import lm_loss
    batch = {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(targets)}
    lp, gp = jax.value_and_grad(lambda p: lm_loss(p, batch, cfg)[0])(w)
    lr, gr = jax.value_and_grad(ref.loss)(w, jnp.asarray(tokens),
                                          jnp.asarray(targets), s)
    assert float(lr) == pytest.approx(float(lp), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)


def test_fp8_control_departs(setup):
    jax, jnp, s, cfg, w, ref, tokens, targets = setup
    args = (jnp.asarray(tokens), jnp.asarray(targets), s)
    g32 = jax.grad(ref.loss)(w, *args)
    g8 = jax.grad(ref.loss)(w, *args, "fp8")
    rel = [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
           for a, b in zip(jax.tree.leaves(g32), jax.tree.leaves(g8))]
    assert max(rel) > 1e-2


def test_seed_key_takes_wide_seeds():
    from bench.weights import seed_key
    a, b = seed_key(5), seed_key(5 + 2**32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(seed_key(2**33 + 1)),
                          np.asarray(seed_key(2**33 + 1)))
