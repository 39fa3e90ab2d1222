"""The serving driver: one ServeEngine on one chip, fed by the traffic
generator as a closed loop: a fixed number of clients, each sending its
next request when the last one finished.

Set-up makes the weights from the seed, builds the engine, and runs a
scripted warm-up that hits every tick variant the traffic can (prefill of
a full and of a padded chunk, each with and without decode riding along,
and decode alone). The traffic then starts; after `lead_s` seconds, when
the slots are full, the window opens, and it closes at the end of the
first tick past `--seconds`. Tokens are timed as the host receives them
through the engine's token callback.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench import counts, program, traffic
from bench.device import BenchError, Laps, require_kernels, say

# (do_prefill, do_decode, full chunk) of every tick the warm-up must hit
TICK_VARIANTS = {(True, False, True), (True, False, False),
                 (True, True, True), (True, True, False),
                 (False, True, None)}
WARM_PROMPTS = ((1, 3), (1, 12), (1, 12), (1, 2))   # (chunks, outputs)
DRAIN_S = 120.0


class TickRecorder:
    """Forwards the engine's jitted tick and keeps the abstract arguments
    of each variant it sees, so the timed programs can be lowered again."""

    def __init__(self, fn):
        self.fn = fn
        self.seen = {}

    def __call__(self, *args, **kw):
        full = None if args[3] is None else args[4] is None
        key = (kw["do_prefill"], kw["do_decode"], full)
        if key not in self.seen:
            self.seen[key] = (jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args), kw)
        return self.fn(*args, **kw)

    def texts(self):
        return {k: self.fn.lower(*a, **kw).as_text()
                for k, (a, kw) in self.seen.items()}


class Client:
    """Token times per request, from the engine's callback."""

    def __init__(self):
        self.times = {}
        self.tokens = {}

    def callback(self, rid, tok):
        self.times.setdefault(rid, []).append(time.perf_counter())
        self.tokens.setdefault(rid, []).append(int(tok))


def _warm(eng, rec, chunk, vocab, seed):
    rng = np.random.default_rng([seed, 1])
    for chunks, out in WARM_PROMPTS:
        eng.submit(rng.integers(0, vocab, chunks * chunk + chunk // 3,
                                dtype=np.int32), out)
    eng.run()
    missing = TICK_VARIANTS - set(rec.seen)
    if missing:
        raise BenchError(f"warm-up missed tick variants {sorted(missing)}")


def run(ctx):
    cfg, mix, s = ctx.cfg, ctx.mix, ctx.sizes
    run_cfg = cfg["run"]
    mcfg = program.model_config(cfg)
    make = ctx.weight_maker()
    params = make(ctx.key)
    jax.block_until_ready(params)
    from bench.weights import check_layout
    check_layout(params, program.abstract_params(mcfg))
    eng = program.serve_engine(params, mcfg, run_cfg)
    rec = TickRecorder(eng._tick_fn)
    eng._tick_fn = rec
    _warm(eng, rec, eng.chunk, s["V"], ctx.seed)
    ctx.routes.check(expect=run_cfg.get("routes", ()))
    reqs = traffic.serve_requests(mix, ctx.seed, s["V"])
    longest = max(len(r.prompt) + r.max_new for r in reqs)
    if longest > eng.slots.max_len:
        raise BenchError(f"traffic needs {longest} positions, max_len is "
                         f"{eng.slots.max_len}")

    client = Client()
    rid_req, submit_at, fins, refused = {}, {}, [], []
    nxt = 0
    start = time.perf_counter()
    t0 = ctx.t0 = start + float(mix["lead_s"])
    t_end = t0 + ctx.seconds
    t_trace_end = t0 + ctx.trace_seconds
    tracing = None
    window_compiles = None
    trace_counts = {}
    laps = Laps(("engine step", "send"))

    def send(i):
        """Submit the i-th request of the list, which repeats."""
        now = time.perf_counter()
        r = reqs[i % len(reqs)]
        try:
            with jax.profiler.TraceAnnotation("bench.submit"):
                rid = eng.submit(r.prompt, r.max_new,
                                 callback=client.callback)
        except Exception as e:  # noqa: BLE001 - a refusal is a failure
            refused.append((i, now, repr(e)))
            return
        rid_req[rid] = i % len(reqs)
        submit_at[rid] = now

    for _ in range(int(mix["clients"])):
        send(nxt)
        nxt += 1
    while True:
        now = time.perf_counter()
        if window_compiles is None and now >= t0:
            window_compiles = ctx.clock.compiles
            gc.disable()
            if ctx.trace:
                tracing = ctx.start_trace()
                trace_counts["start"] = (dict(eng.stats()),
                                         _emitted(client, now))
        if tracing is not None and now >= t_trace_end:
            trace_counts["end"] = (dict(eng.stats()), _emitted(client, now))
            ctx.stop_trace(tracing)
            tracing = None
        if now >= t_end:
            t1 = now
            gc.enable()
            break
        if not eng.pending:
            continue
        laps.begin()
        with jax.profiler.TraceAnnotation("bench.engine.step"):
            done = eng.step()
        laps.mark()
        fins.extend(done)
        for _ in done:          # each client sends its next request
            send(nxt)
            nxt += 1
        laps.end()
    in_window_compiles = ctx.clock.compiles - window_compiles
    ctx.memory_peak = ctx.read_memory()
    _drain(eng, fins, rid_req, mix["check"])

    # -- end-to-end numbers over [t0, t1] ---------------------------------
    win = t1 - t0
    emitted = sum(1 for ts in client.times.values() for t in ts
                  if t0 < t <= t1)
    gaps = [b - a for ts in client.times.values()
            for a, b in zip(ts, ts[1:]) if a >= t0 and b <= t1]
    status = {f.rid: program.finished_ok(f) for f in fins}
    sent = [rid for rid, at in submit_at.items() if t0 <= at < t1]
    refused_in = [r for r in refused if t0 <= r[1] < t1]
    failed = sum(1 for rid in sent if status.get(rid) is False) \
        + len(refused_in)
    say(f"window {win:.3f}s after {t0 - ctx.t_start:.3f}s set-up: {emitted} tokens, "
        f"{len(gaps)} token gaps, {len(sent) + len(refused_in)} requests "
        f"sent ({failed} failed), {len(fins)} finished in all")
    if gaps:
        say(f"token gaps ms: median {1e3 * float(np.median(gaps)):.2f} "
            f"p95 {1e3 * traffic.percentile(gaps, 95):.2f} "
            f"max {1e3 * max(gaps):.2f}")
    say(laps.slowest(since=t0))
    say(f"compiles inside the window: {in_window_compiles}; engine "
        f"{eng.stats()}")
    metrics = {"serve_tok_s": emitted / win,
               "itl_p95_ms": 1e3 * traffic.percentile(gaps, 95)}
    ctx.window_compiles = in_window_compiles

    # -- the timed programs hold the compiled kernels ----------------------
    texts = rec.texts()
    for (pre, dec, _), text in texts.items():
        require_kernels(text, (["fastmax_causal_p2"] if pre else [])
                        + (["fastmax_decode_p2"] if dec else []))
    ctx.kernel_calls = [c for t in texts.values()
                        for c in counts.kernel_calls(t)]
    if "start" in trace_counts and "end" in trace_counts:
        (a, ea), (b, eb) = trace_counts["start"], trace_counts["end"]
        toks = (b["prefill_tokens"] - a["prefill_tokens"]
                + b["decode_tokens"] - a["decode_tokens"])
        ctx.model_flops = counts.forward_flops(ctx.reference(), s, toks,
                                              eb - ea)
        ctx.step_span = "bench.engine.step"

    # -- correctness: a sample of finished requests against the reference --
    done = [f for f in fins if program.finished_ok(f) and f.rid in rid_req]
    sample = _sample(done, rid_req, reqs, mix["check"], ctx.seed)
    # the tokens as the client received them through the callback
    pairs = [(reqs[rid_req[f.rid]].prompt, np.asarray(client.tokens[f.rid]))
             for f in sample]
    del eng, rec, params, fins, done, sample
    gc.collect()
    ctx.set_result(attempted=len(sent) + len(refused_in), failed=failed,
                   metrics=metrics)
    return pairs


def _drain(eng, fins, rid_req, check):
    """After the close, no new request is sent; the engine keeps stepping
    until the finished requests hold the tokens the check samples, or
    nothing is left in flight, or DRAIN_S have passed."""
    end = time.perf_counter() + DRAIN_S
    while eng.pending and time.perf_counter() < end:
        done = [f for f in fins if program.finished_ok(f)
                and f.rid in rid_req]
        if len(done) >= check["max_requests"] and sum(
                len(f.tokens) for f in done) >= check["tokens"]:
            return
        fins.extend(eng.step())


def _emitted(client, now):
    return sum(1 for ts in client.times.values() for t in ts if t <= now)


def _sample(done, rid_req, reqs, check, seed):
    """The longest finished request and others drawn from the seed, until
    `tokens` served tokens or `max_requests` requests."""
    if not done:
        raise BenchError("no request finished: nothing to check")
    size = lambda f: len(reqs[rid_req[f.rid]].prompt) + len(f.tokens)  # noqa: E731
    done = sorted(done, key=lambda f: f.rid)
    first = max(done, key=size)
    rest = [f for f in done if f is not first]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out, total = [first], len(first.tokens)
    for i in order:
        if total >= check["tokens"] or len(out) >= check["max_requests"]:
            break
        out.append(rest[i])
        total += len(rest[i].tokens)
    return out


def check(ctx, pairs):
    """The reference over each sampled prompt and its served tokens: the
    widest gap by which a served token's logit lies below the reference's
    best. With a control in the program's place, the gap of the token that
    the control puts first at each of those positions."""
    ref = ctx.reference()
    w = ctx.weight_maker()(ctx.key)
    mode = ctx.control or "f32"
    allg = np.concatenate(ref.served_gaps(w, ctx.sizes, pairs, mode=mode))
    say(f"reference ({mode}): {len(pairs)} requests, {allg.size} served "
        f"tokens, longest {max(len(p) + len(t) for p, t in pairs)} "
        f"positions; gap median {float(np.median(allg)):.4g}, share "
        f"exactly 0 {float(np.mean(allg == 0)):.3f}")
    return {"logit_gap_max": float(allg.max())}
