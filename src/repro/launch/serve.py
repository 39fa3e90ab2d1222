"""Serving driver: batched prefill + decode with O(1)-in-context state.

With fastmax backends the per-sequence state is the moment tuple — constant
in context length — so a 32k or 500k context costs the same per decoded
token (the paper's asymptotic claim, made concrete; see
examples/long_context.py). Softmax baseline uses a (sequence-sharded at
scale) KV cache.

Two paths:

  default          `generate()` — one static batch, whole-prompt prefill,
                   lockstep greedy decode (optionally eos-early-stopped).
  --serve-engine   `repro.serve.ServeEngine` — continuous batching over a
                   slot pool: staggered admissions, chunked prefill mixed
                   with decode, per-request streaming, plus the fault
                   envelope (--max-queue backpressure, --ttft-deadline /
                   --deadline timeouts; the driver prints the lifecycle
                   counters from engine.stats()). See docs/serving.md.

Usage (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --batch 4 --prompt-len 64 --gen 32 [--serve-engine --slots 4]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import init_decode_state, init_model

# jitted prefill/step per config — reused across generate() calls so a
# warmup call actually warms the timed call (cfg is frozen/hashable). The
# step donates the decode state: its layer scan carries the state and
# updates it in place, which a state it may not overwrite would first copy
_JIT_CACHE: dict = {}


def _jitted_steps(cfg):
    fns = _JIT_CACHE.get(cfg)
    if fns is None:
        fns = (jax.jit(make_prefill_step(cfg)),
               jax.jit(make_serve_step(cfg), donate_argnums=(1,)))
        _JIT_CACHE[cfg] = fns
    return fns


def generate(params, cfg, prompts: jnp.ndarray, n_gen: int,
             max_len: int | None = None, enc_out=None,
             eos_id: int | None = None):
    """prompts: [B, P] int32. Greedy decode of n_gen tokens.

    With `eos_id`, a sequence that emits it is frozen: its remaining
    positions are filled with `eos_id`, and the loop exits early once
    every sequence is done (per-sequence done mask).
    """
    b, plen = prompts.shape
    state = init_decode_state(cfg, b, (max_len or (plen + n_gen)))
    prefill, step = _jitted_steps(cfg)
    tok, state = prefill(params, state, prompts, *(
        [enc_out] if enc_out is not None else []))
    done = (tok == eos_id) if eos_id is not None else None
    out = [tok]
    for i in range(n_gen - 1):
        if done is not None and bool(done.all()):
            out.extend([jnp.full_like(tok, eos_id)] * (n_gen - 1 - i))
            break
        pos = jnp.asarray(plen + i, jnp.int32)  # traced: no retrace per step
        tok, state = step(params, state, tok, pos, *(
            [enc_out] if enc_out is not None else []))
        if done is not None:
            tok = jnp.where(done, eos_id, tok)
            done = done | (tok == eos_id)
        out.append(tok)
    return jnp.stack(out, axis=1)


def _submit_all(eng, prompts, n_gen, args):
    """Submit the batch, absorbing backpressure: a bounded queue
    (--max-queue) rejects at submit time with EngineOverloaded, and we
    drain a tick and retry rather than crash the driver."""
    from repro.serve import EngineOverloaded

    rids = []
    for p in np.asarray(prompts):
        while True:
            try:
                rids.append(eng.submit(
                    p, n_gen, ttft_deadline=args.ttft_deadline,
                    deadline=args.deadline))
                break
            except EngineOverloaded:
                eng.step()   # make room, then retry this prompt
    return rids


def _run_engine(params, cfg, prompts, n_gen, args):
    """Continuous-batching path: submit the batch as staggered requests."""
    from repro.serve import ServeEngine

    max_len = prompts.shape[1] + n_gen
    eng = ServeEngine(
        params, cfg, max_slots=args.slots, max_len=max_len,
        eos_id=args.eos_id, policy=args.policy,
        prefix_cache_bytes=args.prefix_cache_mb << 20,
        max_queue=args.max_queue)
    rids = _submit_all(eng, prompts, n_gen, args)
    outs = eng.run()
    return eng, [outs.get(r, []) for r in rids]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--attn", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--serve-engine", action="store_true",
                    help="continuous batching via repro.serve.ServeEngine")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--policy", default="fcfs", choices=("fcfs", "lpf"))
    ap.add_argument("--prefix-cache-mb", type=int, default=0)
    ap.add_argument("--max-queue", type=int, default=256,
                    help="bounded admission queue depth; submits beyond it "
                         "raise EngineOverloaded (0 = unbounded)")
    ap.add_argument("--ttft-deadline", type=float, default=None,
                    help="seconds from submit to first token before the "
                         "request is timed out")
    ap.add_argument("--deadline", type=float, default=None,
                    help="seconds from submit to completion before the "
                         "request is timed out")
    args = ap.parse_args(argv)
    setup_compile_cache()

    import dataclasses

    from repro.attention import AttentionSpec
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.attn:
        cfg = dataclasses.replace(cfg, attn=AttentionSpec.parse(args.attn))
    params, _ = init_model(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    enc_out = None
    if cfg.encoder_layers > 0:
        from repro.models.encdec import encode
        frames = jnp.asarray(
            rng.normal(size=(args.batch, cfg.encoder_seq, cfg.d_model)),
            cfg.adtype())
        enc_out = encode(params, frames, cfg)

    if args.serve_engine:
        # warmup batch traces the engine's tick variants; the timed batch
        # reuses the same engine (and therefore its jit caches)
        eng, _ = _run_engine(params, cfg, prompts, args.gen, args)
        t0 = time.monotonic()
        rids = _submit_all(eng, prompts, args.gen, args)
        outs = eng.run()
        dt = time.monotonic() - t0
        n_tok = sum(len(outs.get(r, [])) for r in rids)
        ttfts = sorted(f.ttft for f in eng.history[-len(rids):]
                       if f.ttft is not None)
        ttft_ms = (f"{ttfts[len(ttfts) // 2] * 1e3:.1f}ms"
                   if ttfts else "n/a")
        st = eng.stats()
        print(f"[engine] generated {n_tok} tokens in {dt:.2f}s "
              f"({n_tok / dt:.1f} tok/s)  ttft p50 {ttft_ms}  "
              f"slot bytes {eng.slots.state_bytes_per_slot()}  sample: "
              f"{outs[rids[0]][:16]}")
        print(f"[engine] lifecycle: finished {st['finished']}  "
              f"failed {st['failed']}  cancelled {st['cancelled']}  "
              f"timed_out {st['timed_out']}  rejected {st['rejected']}  "
              f"shed {st['shed']}  quarantined {st['quarantined']}  "
              f"ticks {st['ticks']}")
        return

    # warmup: trace + compile out of the timed region (jits are cached
    # per-config, so the timed call reuses them)
    toks = jax.block_until_ready(
        generate(params, cfg, prompts, args.gen, enc_out=enc_out,
                 eos_id=args.eos_id))
    t0 = time.monotonic()
    toks = jax.block_until_ready(
        generate(params, cfg, prompts, args.gen, enc_out=enc_out,
                 eos_id=args.eos_id))
    dt = time.monotonic() - t0
    print(f"generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)  sample: "
          f"{np.asarray(toks[0][:16])}")


if __name__ == "__main__":
    main()
