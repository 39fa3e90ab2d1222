"""Smoke run of the main path on a TPU: serve, check, train.

    python chip_smoke.py              # one chip: qwen3-1.7b at full width
    python chip_smoke.py --chips 4    # context-parallel training on 4 chips

One chip (the default) runs qwen3-1.7b at its full `config()` (28 layers,
d_model 2048, GQA 16/8, head_dim 128, vocab 151936, bf16) with random
weights from `--seed`, through the entry points a user calls:

  serve   `repro.serve.ServeEngine` with attn=fastmax2-kernel: 4 requests
          of different prompt lengths over 2 slots, all must FINISH; then
          one `launch.serve.generate` batch (the whole-prompt prefill
          kernel).
  check   prefill logits and the first decode-step logits of one prompt
          under fastmax2-kernel against fastmax2 (the chunked jnp scan),
          same weights: relative L2 error at most `LOGIT_RTOL`, for the
          whole-prompt prefill kernel and for a prefill resumed at
          `CHECK_SPLIT` (the kernel seeded with the carried moments, as
          the serving engine's chunked prefill runs it).
  train   3 `launch.train` steps with fastmax2-kernel at full width and
          `TRAIN_LAYERS` layers (AdamW state of the full depth does not fit
          16 GB); the loss must be finite.

`--chips 4` runs only `launch.train --cp 4` on a (data=1, seq=4) mesh and
the same steps on one chip, and compares the losses (`CP_LOSS_RTOL`).

Every phase also lowers its jitted entry point and requires the compiled
Pallas kernels (`tpu_custom_call` with the kernel's name) on the route, and
the attention routing log must show no interpret mode and no jnp fallback.
The script exits non-zero, without printing a result, when JAX finds no
TPU or any phase fails. Its last line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Times printed here are one smoke run, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import logging
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ARCH = "qwen3-1.7b"
SLOTS = 2
MAX_LEN = 384
PROMPT_LENS = (37, 130, 260, 301)
GEN = 16
CHECK_PROMPT = 200
CHECK_STEPS = 4
CHECK_SPLIT = 128
TRAIN_LAYERS = 4
TRAIN_STEPS = 3
TRAIN_SEQ = 1024
# relative L2 error of the kernel route's logits against the jnp route's:
# both keep bf16 activations (8 significant bits, 2^-8 ~ 3.9e-3 per
# rounding) and accumulate moments in f32 with different matmul passes,
# and the difference compounds over 28 residual layers
LOGIT_RTOL = 5e-2
# per-step loss of the 4-chip context-parallel run against one chip: the
# shards carry the same moments in a different summation order
CP_LOSS_RTOL = 1e-2
# environment switches that would reroute the kernels to the jnp path
REROUTE_VARS = ("REPRO_DECODE_KERNEL", "REPRO_FASTMAX_BWD", "REPRO_AUTOTUNE")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def device_label(chips: int = 1) -> dict:
    """The device this run measures, as JAX reports it. Exits non-zero
    unless JAX's default backend is a TPU with at least `chips` chips."""
    import jax

    devs = jax.devices()
    label = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if label["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX sees {label}", file=sys.stderr)
        raise SystemExit(2)
    if label["count"] < chips:
        print(f"chip_smoke: needs {chips} chips; JAX sees {label}",
              file=sys.stderr)
        raise SystemExit(2)
    return label


class RouteLog(logging.Handler):
    """Collects the attention routing lines (`repro.attention` logger)."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())

    def check(self, expect=()):
        bad = [m for m in self.lines
               if "interpret" in m or "fallback" in m or "disabled" in m]
        if bad:
            raise SmokeFailure(f"kernels not on the compiled route: {bad}")
        for want in expect:
            if not any(want in m for m in self.lines):
                raise SmokeFailure(f"routing line {want!r} missing from "
                                   f"{self.lines}")


class CompileClock:
    """Seconds JAX spent compiling, and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def require_kernels(lowered, names):
    """The lowered program calls the compiled Pallas kernels `names`."""
    text = lowered.as_text()
    missing = [n for n in names if n not in text]
    if "tpu_custom_call" not in text or missing:
        raise SmokeFailure(f"compiled kernels {missing or names} not in the "
                           f"lowered program")


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def with_attn(cfg, name: str):
    from repro.attention import AttentionSpec
    return dataclasses.replace(cfg, attn=AttentionSpec.parse(name))


def phase_serve(params, cfg, *, seed: int):
    """ServeEngine over the slot pool, then one generate() batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import generate
    from repro.models.transformer import lm_decode_step, lm_prefill
    from repro.serve import RequestStatus, ServeEngine

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPT_LENS]
    eng = ServeEngine(params, cfg, max_slots=SLOTS, max_len=MAX_LEN)
    rids = [eng.submit(p, GEN) for p in prompts]
    eng.run()
    fins = {f.rid: f for f in eng.history}
    for rid in rids:
        f = fins.get(rid)
        if f is None or f.status is not RequestStatus.FINISHED \
                or len(f.tokens) != GEN:
            raise SmokeFailure(f"request {rid} did not finish: {f}")
        if not ((f.tokens >= 0) & (f.tokens < cfg.vocab_size)).all():
            raise SmokeFailure(f"request {rid} emitted invalid tokens")
        tpot = (f.latency - f.ttft) / max(len(f.tokens) - 1, 1)
        print(f"  request {rid}: prompt {f.prompt_len} FINISHED "
              f"ttft {f.ttft:.3f}s tpot {tpot * 1e3:.1f}ms "
              f"(engine-reported, one smoke run, not a benchmark)")
    st = eng.stats()
    print(f"  engine: {st['finished']} finished over {st['ticks']} ticks, "
          f"{SLOTS} slots, max_len {MAX_LEN}, "
          f"{eng.slots.state_bytes_per_slot() / 2**30:.2f} GiB per slot")
    pool = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        eng.slots.state)
    tok = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    require_kernels(jax.jit(
        lambda p, s, t: lm_decode_step(p, s, t, cfg, position=t)
    ).lower(params, pool, tok), ["fastmax_decode_p2"])
    del eng
    gc.collect()

    plen = min(len(p) for p in prompts[2:])
    batch = jnp.asarray(np.stack([p[:plen] for p in prompts[2:]]))
    toks = jax.block_until_ready(generate(params, cfg, batch, GEN))
    if toks.shape != (2, GEN) or not bool(
            ((toks >= 0) & (toks < cfg.vocab_size)).all()):
        raise SmokeFailure(f"generate() gave {toks.shape} / bad tokens")
    print(f"  generate(): batch {tuple(batch.shape)} -> {tuple(toks.shape)}")
    from repro.models import init_decode_state
    state = jax.eval_shape(lambda: init_decode_state(cfg, 2, plen + GEN))
    require_kernels(jax.jit(
        lambda p, t, s: lm_prefill(p, t, cfg, s)[0]
    ).lower(params, batch, state), ["fastmax_causal_p2"])


def route_logits(params, cfg, prompt, steps: int, split=None):
    """Prefill logits of `prompt` then `steps` teacher-forced decode-step
    logits (the tokens fed are the prompt's own continuation), f32 numpy.
    With `split` the prefill runs as two resumable chunks, [0, split) and
    [split, end)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_decode_state
    from repro.models.transformer import lm_decode_step, lm_prefill

    n = len(prompt) - steps
    state = init_decode_state(cfg, 1, len(prompt))
    pre = jax.jit(lambda p, t, s, off: lm_prefill(p, t, cfg, s, offset=off))
    dec = jax.jit(lambda p, s, t, pos: lm_decode_step(p, s, t, cfg,
                                                       position=pos))
    out = []
    if split is None:
        logits, state = jax.jit(lambda p, t, s: lm_prefill(p, t, cfg, s))(
            params, jnp.asarray(prompt[None, :n]), state)
        out.append(np.asarray(logits[0], np.float32))
    else:
        for lo, hi in ((0, split), (split, n)):
            logits, state = pre(params, jnp.asarray(prompt[None, lo:hi]),
                                state, jnp.asarray(lo, jnp.int32))
            out.append(np.asarray(logits[0], np.float32))
    for i in range(steps):
        lg, state = dec(params, state, jnp.asarray(prompt[None, n + i]),
                        jnp.asarray(n + i, jnp.int32))
        out.append(np.asarray(lg, np.float32).reshape(1, -1))
    del state
    return np.concatenate(out, axis=0)


def phase_check(params, cfg_kernel, cfg_ref, *, seed: int):
    """Kernel route vs the chunked jnp route on the same prompt."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(0, cfg_kernel.vocab_size,
                          CHECK_PROMPT + CHECK_STEPS).astype(np.int32)
    want = route_logits(params, cfg_ref, prompt, CHECK_STEPS)
    if not np.isfinite(want).all():
        raise SmokeFailure("non-finite jnp-route logits")
    for route, split in (("whole-prompt", None), ("resumed", CHECK_SPLIT)):
        gc.collect()
        got = route_logits(params, cfg_kernel, prompt, CHECK_STEPS, split)
        if not np.isfinite(got).all():
            raise SmokeFailure(f"non-finite {route} kernel logits")
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        step_rel = [float(np.linalg.norm(g - w) / np.linalg.norm(w))
                    for g, w in zip(got[-CHECK_STEPS:], want[-CHECK_STEPS:])]
        agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
        print(f"  logits {route} kernel vs jnp: rel L2 {rel:.3e} "
              f"(tolerance {LOGIT_RTOL:.0e}), decode steps "
              f"{[f'{r:.2e}' for r in step_rel]}, argmax agreement "
              f"{agree:.3f} over {got.shape[0]} rows")
        if not rel <= LOGIT_RTOL:
            raise SmokeFailure(f"{route} kernel logits differ from jnp by "
                               f"{rel:.3e}")


def run_train(argv):
    from repro.launch import train
    res = train.main(argv)
    if len(res.losses) != TRAIN_STEPS or not all(
            map(lambda x: x == x and abs(x) != float("inf"), res.losses)):
        raise SmokeFailure(f"train losses {res.losses}")
    return res


def train_argv(*extra):
    return ["--arch", ARCH, "--attn", "fastmax2-kernel", "--layers",
            str(TRAIN_LAYERS), "--steps", str(TRAIN_STEPS), "--batch", "1",
            "--seq", str(TRAIN_SEQ), "--log-every", "1", *extra]


def require_train_kernels(cp: int = 1):
    """The train step at the smoke shape lowers to the forward and
    backward kernels."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.steps import make_train_step, pick_optimizer
    from repro.models import init_model

    cfg = dataclasses.replace(with_attn(get_config(ARCH), "fastmax2-kernel"),
                              n_layers=TRAIN_LAYERS)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0),
                                               cfg)[0])
    _, opt = pick_optimizer(cfg, 1, total_steps=TRAIN_STEPS)
    opt_state = jax.eval_shape(opt[0], params)
    batch = {k: jax.ShapeDtypeStruct((1, TRAIN_SEQ), jnp.int32)
             for k in ("tokens", "targets")}
    require_kernels(jax.jit(make_train_step(cfg, opt)).lower(
        params, opt_state, batch),
        ["fastmax_causal_p2", "fastmax_causal_bwd_p2"])


def timed(name, fn, clock, results):
    t0, c0 = time.perf_counter(), clock.seconds
    print(f"[{name}]", flush=True)
    fn()
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    results[name] = {"wall_s": wall, "compile_s": comp,
                     "peak_bytes": peak_bytes()}
    print(f"[{name}] ok: {wall:.1f}s wall, {comp:.1f}s compiling, peak "
          f"{results[name]['peak_bytes'] / 2**30:.2f} GiB "
          f"(one smoke run, not a benchmark)", flush=True)


def one_chip(args, clock, routes, results):
    import jax

    from repro.configs import get_config
    from repro.models import init_model

    base = get_config(ARCH)
    cfg_k = with_attn(base, "fastmax2-kernel")
    print(f"model {ARCH}: {base.n_layers} layers, d_model {base.d_model}, "
          f"heads {base.n_heads}/{base.n_kv_heads}, head_dim "
          f"{base.head_dim}, vocab {base.vocab_size}, {base.param_dtype}; "
          f"weights from seed {args.seed}", flush=True)
    params, _ = init_model(jax.random.PRNGKey(args.seed), cfg_k)
    timed("serve", lambda: phase_serve(params, cfg_k, seed=args.seed),
          clock, results)
    routes.check(expect=(
        "decode: fastmax-kernel native-state kernel",
        "prefill: fastmax-kernel whole-prompt kernel",
        "prefill: fastmax-kernel resumable (offset) chunk, kernel seeded"))
    timed("check", lambda: phase_check(params, cfg_k,
                                       with_attn(base, "fastmax2"),
                                       seed=args.seed), clock, results)
    del params
    gc.collect()
    print(f"train depth: {TRAIN_LAYERS} of {base.n_layers} layers at full "
          f"width", flush=True)

    def train():
        require_train_kernels()
        run_train(train_argv())

    timed("train", train, clock, results)


def four_chips(args, clock, routes, results):
    import jax
    import numpy as np

    print(f"train depth: {TRAIN_LAYERS} layers at full width, seq "
          f"{TRAIN_SEQ}", flush=True)
    out = {}

    def cp4():
        res = run_train(train_argv("--cp", "4"))
        ids = {d.id for d in res.mesh.devices.flat}
        if res.mesh.shape != {"data": 1, "seq": 4} or len(ids) != 4:
            raise SmokeFailure(f"mesh {res.mesh.shape} over devices {ids}")
        leaf = jax.tree.leaves(res.params)[0]
        print(f"  mesh (data=1, seq=4) over devices {sorted(ids)}; a param "
              f"leaf lives on {len(leaf.sharding.device_set)} devices")
        out["cp"] = res.losses

    def single():
        out["one"] = run_train(train_argv()).losses

    timed("train_cp4", cp4, clock, results)
    timed("train_1chip", single, clock, results)
    cp, one = np.asarray(out["cp"]), np.asarray(out["one"])
    rel = np.abs(cp - one) / np.abs(one)
    print(f"  losses cp=4 {cp.tolist()} vs one chip {one.tolist()}: max "
          f"rel diff {rel.max():.3e} (tolerance {CP_LOSS_RTOL:.0e})")
    if not rel.max() <= CP_LOSS_RTOL:
        raise SmokeFailure("context-parallel losses differ from one chip")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    for var in REROUTE_VARS:
        if os.environ.pop(var, None) is not None:
            print(f"chip_smoke: ignoring {var} (the smoke run takes the "
                  f"default kernel route)", flush=True)
    label = device_label(args.chips)
    print(f"device: {label}", flush=True)
    sys.path.insert(0, SRC)
    from repro.kernels.ops import use_interpret
    from repro.launch.compile_cache import setup_compile_cache

    print(f"compile cache: {setup_compile_cache()}", flush=True)
    if use_interpret():
        raise SmokeFailure("Pallas kernels would run in interpret mode")
    routes = RouteLog()
    log = logging.getLogger("repro.attention")
    log.addHandler(routes)
    log.setLevel(logging.INFO)
    clock = CompileClock()
    results = {}
    (four_chips if args.chips == 4 else one_chip)(args, clock, routes,
                                                  results)
    routes.check()
    print("routing lines:", routes.lines, flush=True)
    print(f"compile: {clock.seconds:.1f}s in all, "
          f"{clock.cache_hits} persistent-cache hits", flush=True)
    print(json.dumps({"ok": True, "device": label}), flush=True)


if __name__ == "__main__":
    main()
