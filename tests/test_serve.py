"""Continuous-batching engine tier (`serve` marker; `make test-serve`).

The load-bearing contract: the engine — staggered admissions, chunked
prefill mixed with batched decode, slot reuse — produces EXACTLY the
tokens `launch.serve.generate` produces per request, for every registered
decode-capable backend (softmax KV, fastmax p in {1,2} chunked, fastmax
kernel routing), on a GQA config, plus the SSM-mixer architectures.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import AttentionSpec
from repro.configs import get_smoke_config
from repro.launch.serve import generate
from repro.models import init_decode_state, init_model
from repro.serve import PrefixCache, Request, Scheduler, ServeEngine
from repro.serve.slots import SlotManager

pytestmark = pytest.mark.serve

DECODE_SPECS = ["softmax", "fastmax1-chunked", "fastmax2-chunked",
                "fastmax2-kernel"]


def _setup(spec_name=None, arch="qwen3-1.7b", seed=0):
    cfg = get_smoke_config(arch)
    if spec_name is not None:
        cfg = dataclasses.replace(cfg, attn=AttentionSpec.parse(spec_name))
    params, _ = init_model(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def _ref(params, cfg, prompt, gen, max_len, eos_id=None):
    return np.asarray(generate(params, cfg, jnp.asarray(prompt[None]), gen,
                               max_len=max_len, eos_id=eos_id))[0]


# ---------------------------------------------------------------------------
# slot pool unit behavior
# ---------------------------------------------------------------------------


def test_slot_write_read_roundtrip():
    cfg, _ = _setup("fastmax2-chunked")
    sm = SlotManager(cfg, max_slots=3, max_len=32)
    # perturb slot 1 with a recognisable unit state, read it back
    unit = jax.tree.map(lambda l: jnp.full_like(l, 7), sm.fresh_unit)
    sm.admit(1, unit_state=unit)
    got = sm.snapshot(1)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(unit)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # neighbours untouched (still the fresh init, not 7s)
    other = sm.snapshot(0)
    for a, b in zip(jax.tree.leaves(other), jax.tree.leaves(sm.fresh_unit)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_slot_axes_cover_every_leaf():
    # every decode-state leaf must expose a batch/slot axis — softmax KV,
    # moments, and both SSM families
    for arch in ["qwen3-1.7b", "xlstm-1.3b", "jamba-v0.1-52b"]:
        cfg, _ = _setup(arch=arch)
        sm = SlotManager(cfg, max_slots=2, max_len=32)
        n_state = len(jax.tree.leaves(sm.state))
        assert n_state == len(jax.tree.leaves(sm.axes))


def test_slot_memory_constant_for_fastmax():
    from repro.core.decode_state import decode_state_bytes
    cfg_f, _ = _setup("fastmax2-chunked")
    cfg_s, _ = _setup("softmax")
    f_small = decode_state_bytes(cfg_f, 1, 128)
    f_big = decode_state_bytes(cfg_f, 1, 8192)
    s_small = decode_state_bytes(cfg_s, 1, 128)
    s_big = decode_state_bytes(cfg_s, 1, 8192)
    assert f_small == f_big          # O(1) in context
    assert s_big > s_small * 32      # KV cache is linear in context


# ---------------------------------------------------------------------------
# the decode tick's live mask
# ---------------------------------------------------------------------------

# (spec, arch): the decode kernel (interpret), the jnp moment step, the
# softmax KV cache and a recurrent mixer
LIVE_CASES = [("fastmax2-kernel", "qwen3-1.7b"),
              ("fastmax2-chunked", "qwen3-1.7b"),
              ("softmax", "qwen3-1.7b"),
              (None, "xlstm-1.3b")]


def _live_setup(monkeypatch, spec, arch):
    if spec == "fastmax2-kernel":
        monkeypatch.setenv("REPRO_DECODE_KERNEL", "1")
    return _setup(spec, arch=arch)


def _select_per_slot(live, new, old, axes):
    """Reference: keep slot i's new state where live[i], else its old
    state, leaf by leaf along each leaf's slot axis."""
    def sel(n, o, ax):
        shape = [1] * n.ndim
        shape[ax] = live.shape[0]
        return jnp.where(live.reshape(shape), n, o)

    return jax.tree.map(sel, new, old, axes)


@pytest.mark.parametrize("spec,arch", LIVE_CASES)
def test_decode_step_live_mask_matches_select(monkeypatch, spec, arch):
    """lm_decode_step(live=mask) equals the unmasked step followed by a
    per-slot select over the whole state, and its live slots' logits
    equal the unmasked step's. Slots that are not live keep their state
    bit for bit. The attention paths match bit for bit on live slots too;
    for the recurrent mixer XLA fuses the sLSTM cell differently once a
    select consumes it, which moves its cell state by a few float32 ulps
    (as an all-live mask does), so live slots there match to float32
    rounding."""
    from repro.models.transformer import lm_decode_step
    cfg, params = _live_setup(monkeypatch, spec, arch)
    sm = SlotManager(cfg, max_slots=3, max_len=32)

    @jax.jit
    def step(state, tok, pos, live):
        return lm_decode_step(params, state, tok, cfg, position=pos,
                              live=live)

    @jax.jit
    def step_all(state, tok, pos):
        return lm_decode_step(params, state, tok, cfg, position=pos)

    pos = jnp.asarray([0, 0, 0], jnp.int32)
    _, state = step_all(sm.state, jnp.asarray([3, 5, 7], jnp.int32), pos)
    tok = jnp.asarray([11, 13, 17], jnp.int32)
    live = jnp.asarray([True, False, True])
    logits, got = step(state, tok, pos + 1, live)
    logits_all, new = step_all(state, tok, pos + 1)
    want = _select_per_slot(live, new, state, sm.axes)
    exact = spec is not None      # the attention cases; xlstm has none
    for a, b, ax in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        jax.tree.leaves(sm.axes)):
        a, b = np.moveaxis(np.asarray(a), ax, 0), np.moveaxis(
            np.asarray(b), ax, 0)
        np.testing.assert_array_equal(a[1], b[1])
        if exact:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    live_rows = np.asarray(logits)[[0, 2]], np.asarray(logits_all)[[0, 2]]
    if exact:
        np.testing.assert_array_equal(*live_rows)
    else:
        np.testing.assert_allclose(*live_rows, rtol=1e-5, atol=1e-6)


def _tensor_type(shape, dtype) -> str:
    dt = {"float32": "f32", "int32": "i32", "bool": "i1"}[str(dtype)]
    return "tensor<" + "".join(f"{n}x" for n in shape) + dt + ">"


@pytest.mark.parametrize("spec,arch", LIVE_CASES)
def test_decode_tick_updates_pool_in_place(monkeypatch, spec, arch):
    """The lowered decode tick has no select over a whole-pool-shaped
    tensor, and its layer scan carries each pool leaf exactly once (a
    scan with the pool as xs and ys would carry it twice)."""
    import collections
    import re
    cfg, params = _live_setup(monkeypatch, spec, arch)
    eng = ServeEngine(params, cfg, max_slots=3, max_len=32)
    b = eng.slots.max_slots
    text = eng._tick_fn.lower(
        params, eng.slots.state, None, None, None, None, None,
        jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32),
        jnp.ones(b, bool), do_prefill=False, do_decode=True).as_text()
    stacked = [leaf for key, sub in eng.slots.state.items()
               if key.startswith("blocks_") for leaf in jax.tree.leaves(sub)]
    pool = collections.Counter(_tensor_type(x.shape, x.dtype)
                               for x in stacked)
    assert pool
    for line in text.splitlines():
        if "stablehlo.select" in line:
            assert line.rsplit(": ", 1)[-1].split(", ")[-1] not in pool, line
    carries = [collections.Counter(
        t for t in re.findall(r"tensor<[^>]*>", line.rsplit(") : ", 1)[-1])
        if t in pool) for line in text.splitlines()
        if "stablehlo.while" in line]
    assert pool in carries, carries


def test_decode_masked_slots_counts_a_mid_prefill_slot():
    """A slot still prefilling rides through the decode part unchanged,
    and each such slot-tick is counted."""
    cfg, params = _setup("fastmax2-chunked")
    rng = np.random.default_rng(8)
    eng = ServeEngine(params, cfg, max_slots=2, max_len=64, chunk=4)
    eng.submit(rng.integers(0, cfg.vocab_size, 4).astype(np.int32), 8)
    eng.submit(rng.integers(0, cfg.vocab_size, 12).astype(np.int32), 2)
    eng.step()          # slot 0's whole prompt: no decode part yet
    assert eng.stats()["decode_masked_slots"] == 0
    eng.step()          # slot 0 decodes, slot 1 prefills chunk 1 of 3
    eng.step()          # chunk 2 of 3
    st = eng.stats()
    assert (st["decode_ticks"], st["decode_masked_slots"]) == (2, 2)
    eng.run()
    st = eng.stats()
    # slot 1 is held while it prefills (three ticks) and again once it is
    # free (the last three of slot 0's seven decode ticks)
    assert st["decode_ticks"] == 7
    assert st["decode_masked_slots"] == 3 + 3
    assert st["decode_tokens"] == 7 + 1


# ---------------------------------------------------------------------------
# engine vs generate(): token parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", DECODE_SPECS)
def test_engine_parity_staggered(spec):
    """Staggered admissions + ragged prompts produce the same tokens as
    per-request generate() for every decode-capable backend (GQA config)."""
    cfg, params = _setup(spec)
    assert cfg.n_kv_heads < cfg.n_heads  # GQA is actually exercised
    rng = np.random.default_rng(1)
    p0 = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)  # ragged tail
    p1 = rng.integers(0, cfg.vocab_size, 23).astype(np.int32)
    G = 6
    ref0 = _ref(params, cfg, p0, G, 64)
    ref1 = _ref(params, cfg, p1, G, 64)
    eng = ServeEngine(params, cfg, max_slots=2, max_len=64)
    r0 = eng.submit(p0, G)
    outs = {}
    for _ in range(3):                      # p1 arrives mid-flight
        for f in eng.step():
            outs[f.rid] = f.tokens
    r1 = eng.submit(p1, G)
    outs.update(eng.run())
    np.testing.assert_array_equal(outs[r0], ref0)
    np.testing.assert_array_equal(outs[r1], ref1)


@pytest.mark.slow  # ~2 min combined: whole-model SSM prefill compiles
@pytest.mark.parametrize("arch", ["xlstm-1.3b", "jamba-v0.1-52b"])
def test_engine_parity_ssm_mixers(arch):
    """SSM-mixer archs resume via recurrent state (exact-length ragged
    chunks, no kv_mask) and must still match generate()."""
    cfg, params = _setup(arch=arch)
    rng = np.random.default_rng(2)
    p0 = rng.integers(0, cfg.vocab_size, 33).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab_size, 17).astype(np.int32)
    G = 5
    ref0 = _ref(params, cfg, p0, G, 64)
    ref1 = _ref(params, cfg, p1, G, 64)
    eng = ServeEngine(params, cfg, max_slots=2, max_len=64)
    r0 = eng.submit(p0, G)
    outs = {}
    for _ in range(2):
        for f in eng.step():
            outs[f.rid] = f.tokens
    r1 = eng.submit(p1, G)
    outs.update(eng.run())
    np.testing.assert_array_equal(outs[r0], ref0)
    np.testing.assert_array_equal(outs[r1], ref1)


def test_engine_slot_reuse_single_slot():
    """max_slots=1 serving 3 queued requests: each admit fully overwrites
    the evicted slot — no state leaks between tenants."""
    cfg, params = _setup("fastmax2-chunked")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (19, 40, 8)]
    G = 4
    refs = [_ref(params, cfg, p, G, 64) for p in prompts]
    eng = ServeEngine(params, cfg, max_slots=1, max_len=64)
    rids = [eng.submit(p, G) for p in prompts]
    outs = eng.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(outs[rid], ref)


def test_prefill_round_robin_interleaves():
    """Two equal prompts admitted together must make chunk-for-chunk
    progress (round-robin), not slot-0-to-completion-first (head-of-line
    bias that inflates slot 1's TTFT) — and parity must survive the
    interleaving."""
    cfg, params = _setup("fastmax2-chunked")
    rng = np.random.default_rng(7)
    p0 = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    G = 4
    ref0 = _ref(params, cfg, p0, G, 64)
    ref1 = _ref(params, cfg, p1, G, 64)
    eng = ServeEngine(params, cfg, max_slots=2, max_len=64, chunk=8)
    r0 = eng.submit(p0, G)
    r1 = eng.submit(p1, G)
    eng.step()
    eng.step()
    # after two single-chunk ticks BOTH prompts have advanced; the biased
    # lowest-slot-first scan would leave slot 1 still at position 0
    pos = np.asarray(eng.slots.position)
    assert pos[0] > 0 and pos[1] > 0, pos
    outs = eng.run()
    np.testing.assert_array_equal(outs[r0], ref0)
    np.testing.assert_array_equal(outs[r1], ref1)


def test_submit_rejects_empty_prompt_and_zero_gen():
    cfg, params = _setup("fastmax2-chunked")
    eng = ServeEngine(params, cfg, max_slots=1, max_len=64)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.zeros(0, np.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens must be >= 1"):
        eng.submit(np.arange(4, dtype=np.int32), 0)
    assert eng.pending == 0            # nothing was enqueued


# ---------------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------------


def test_prefix_cache_hit_matches_cold_path():
    """A request resumed from a cached prefix snapshot must decode the
    exact cold-path tokens, stepped out to 64 tokens."""
    cfg, params = _setup("fastmax2-chunked")
    C = cfg.chunk_size
    rng = np.random.default_rng(4)
    shared = rng.integers(0, cfg.vocab_size, 2 * C).astype(np.int32)
    a = np.concatenate([shared,
                        rng.integers(0, cfg.vocab_size, 5).astype(np.int32)])
    b = np.concatenate([shared,
                        rng.integers(0, cfg.vocab_size, 9).astype(np.int32)])
    G = 64
    max_len = len(b) + G
    ref_b = _ref(params, cfg, b, G, max_len)
    eng = ServeEngine(params, cfg, max_slots=1, max_len=max_len,
                      prefix_cache_bytes=1 << 30)
    eng.submit(a, G)
    eng.run()                                  # populates the cache
    rb = eng.submit(b, G)
    outs = eng.run()
    assert eng.prefix_cache.hits >= 1          # b resumed from a's prefix
    np.testing.assert_array_equal(outs[rb], ref_b)


def test_prefix_cache_lru_byte_budget():
    cache = PrefixCache(byte_budget=100, chunk=4)
    state1 = {"x": np.zeros(10, np.float32)}   # 40 bytes
    p1 = np.arange(8, dtype=np.int32)
    p2 = np.arange(100, 108, dtype=np.int32)
    p3 = np.arange(200, 208, dtype=np.int32)
    cache.insert(p1, 4, state1)
    cache.insert(p2, 4, state1)
    assert cache.bytes == 80 and len(cache) == 2
    cache.insert(p3, 4, state1)                # 120 > 100: evicts oldest
    assert cache.bytes == 80 and len(cache) == 2
    assert cache.lookup(p1)[1] is None         # p1 was LRU-evicted
    assert cache.lookup(p3)[1] is not None
    # oversized entries are refused outright
    cache.insert(np.arange(300, 308, dtype=np.int32), 4,
                 {"x": np.zeros(100, np.float32)})
    assert cache.bytes == 80


def test_prefix_cache_stats_transitions():
    """hits/misses/insertions/evictions move exactly when they should; in
    particular a prompt too short to HAVE a cacheable prefix (< one chunk
    past the boundary) is not a miss."""
    cache = PrefixCache(byte_budget=100, chunk=4)
    state = {"x": np.zeros(10, np.float32)}    # 40 bytes

    # sub-chunk prompt: no key of length k*chunk < plen exists -> no miss
    assert cache.lookup(np.arange(3, dtype=np.int32)) == (0, None)
    assert cache.lookup(np.arange(4, dtype=np.int32)) == (0, None)
    assert cache.stats()["misses"] == 0

    # long enough to have a prefix, but cache is cold -> a real miss
    p = np.arange(8, dtype=np.int32)
    assert cache.lookup(p) == (0, None)
    assert cache.stats()["misses"] == 1

    cache.insert(p, 4, state)
    assert cache.stats()["insertions"] == 1
    m, snap = cache.lookup(p)                  # now a hit at m=4
    assert m == 4 and snap is state
    assert cache.stats() == {"entries": 1, "bytes": 40, "hits": 1,
                             "misses": 1, "insertions": 1, "evictions": 0}

    # two more 40-byte entries blow the 100-byte budget -> one eviction
    cache.insert(np.arange(100, 108, dtype=np.int32), 4, state)
    cache.insert(np.arange(200, 208, dtype=np.int32), 4, state)
    st = cache.stats()
    assert st["insertions"] == 3 and st["evictions"] == 1
    assert st["entries"] == 2 and st["bytes"] == 80


def test_prefix_cache_resume_is_strictly_shorter():
    cache = PrefixCache(byte_budget=1 << 20, chunk=4)
    p = np.arange(8, dtype=np.int32)
    cache.insert(p, 8, {"x": np.zeros(2, np.float32)})
    # a full-prompt snapshot must NOT be returned for the same prompt —
    # at least one token has to run prefill to produce the first logits
    m, state = cache.lookup(p)
    assert (m, state) == (0, None)


# ---------------------------------------------------------------------------
# scheduler policies
# ---------------------------------------------------------------------------


def _req(rid, plen, tick=0):
    return Request(rid=rid, prompt=np.zeros(plen, np.int32),
                   max_new_tokens=1, submit_tick=tick)


def test_scheduler_fcfs_order():
    s = Scheduler("fcfs")
    for r in [_req(0, 5), _req(1, 50), _req(2, 10)]:
        s.push(r)
    assert [s.pop(0).rid for _ in range(3)] == [0, 1, 2]


def test_scheduler_lpf_prefers_long_prompts():
    s = Scheduler("lpf", max_wait=100)
    for r in [_req(0, 5), _req(1, 50), _req(2, 10)]:
        s.push(r)
    assert [s.pop(0).rid for _ in range(3)] == [1, 2, 0]


def test_scheduler_lpf_starvation_guard():
    s = Scheduler("lpf", max_wait=10)
    s.push(_req(0, 5, tick=0))        # short, would lose every lpf round
    s.push(_req(1, 50, tick=9))
    s.push(_req(2, 60, tick=9))
    assert s.pop(9).rid == 2          # lpf still winning
    assert s.pop(10).rid == 0         # rid 0 has starved past max_wait
    assert s.pop(11).rid == 1


def test_scheduler_rejects_unknown_policy():
    with pytest.raises(ValueError):
        Scheduler("priority")


# ---------------------------------------------------------------------------
# eos early stop
# ---------------------------------------------------------------------------


def _emitted_token(params, cfg, prompt):
    """A token the model actually emits (so eos fires mid-generation)."""
    toks = _ref(params, cfg, prompt, 4, 64)
    return int(toks[1])


def test_generate_eos_early_stop():
    cfg, params = _setup("fastmax2-chunked")
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    eos = _emitted_token(params, cfg, prompt)
    G = 8
    free = _ref(params, cfg, prompt, G, 64)
    stopped = _ref(params, cfg, prompt, G, 64, eos_id=eos)
    k = int(np.argmax(free == eos))            # first eos position
    np.testing.assert_array_equal(stopped[:k + 1], free[:k + 1])
    assert (stopped[k:] == eos).all()          # frozen after eos


def test_engine_eos_early_stop():
    cfg, params = _setup("fastmax2-chunked")
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    eos = _emitted_token(params, cfg, prompt)
    G = 8
    free = _ref(params, cfg, prompt, G, 64)
    k = int(np.argmax(free == eos))
    eng = ServeEngine(params, cfg, max_slots=1, max_len=64, eos_id=eos)
    rid = eng.submit(prompt, G)
    outs = eng.run()
    np.testing.assert_array_equal(outs[rid], free[:k + 1])  # ends at eos


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------


def test_stream_yields_tokens_in_order():
    cfg, params = _setup("fastmax2-chunked")
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, 21).astype(np.int32)
    G = 5
    ref = _ref(params, cfg, prompt, G, 64)
    eng = ServeEngine(params, cfg, max_slots=2, max_len=64)
    got = list(eng.stream(prompt, G))
    np.testing.assert_array_equal(np.asarray(got, np.int32), ref)
