"""The training driver: the program's jitted AdamW train step (params and
optimizer state donated), fed seeded token batches as `launch.train` feeds
its data, one step at a time with the loss read back after each.

Set-up makes the weights from the seed and the optimizer state, and drives
that same step object through its first `check_steps` steps (the first
compiles): their losses, the first gradient as the optimizer got it (its
first moment after one step, over 1 - b1) and the change of the float32
master weights after the last of them are the program's readings for the
check, with each step's global gradient norm before the clip. The window
then opens and runs whole steps until `--seconds` have
passed; the rate is every token of those steps over their time.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, program, traffic
from bench.device import Laps, require_kernels, say

F32 = jnp.float32


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))),
                        tree)


@jax.jit
def _change_norms(new, old):
    return jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)
                                                 - b.astype(F32)))), new, old)


def _floats(tree):
    return [float(x) for x in jax.tree.leaves(tree)]


def _feed(batch):
    tok, tgt = batch
    return {"tokens": jnp.asarray(tok), "targets": jnp.asarray(tgt)}


def run(ctx):
    cfg, mix, s = ctx.cfg, ctx.mix, ctx.sizes
    opt = mix["optimizer"]
    mcfg = program.model_config(cfg)
    make = ctx.weight_maker()
    params = make(ctx.key)
    from bench.weights import check_layout
    check_layout(params, program.abstract_params(mcfg))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    opt_init, step = program.train_step(mcfg, n_params, opt)
    opt_state = jax.jit(opt_init)(params)
    batches = traffic.train_batches(mix, ctx.seed, s["V"])
    k = int(mix["check_steps"])
    tokens_per_step = int(mix["batch"]) * int(mix["seq"])

    losses, gnorms, grad1 = [], [], None
    for i in range(k):
        params, opt_state, met = step(params, opt_state, _feed(batches[i]))
        losses.append(float(met["loss"]))
        gnorms.append(float(met["gnorm"]))
        if i == 0:
            grad1 = [x / (1.0 - float(opt["b1"]))
                     for x in _floats(_leaf_norms(opt_state.m))]
    master = params if opt_state.master is None else opt_state.master
    change = _floats(_change_norms(master, make(ctx.key)))
    del master
    ctx.routes.check(expect=())
    say(f"set-up steps: losses {losses}, gradient norms {gnorms}")

    steps, bad, i = 0, 0, k
    ends = []
    t0 = ctx.t0 = time.perf_counter()
    window_compiles = ctx.clock.compiles
    t_trace_end = t0 + ctx.trace_seconds
    tracing = ctx.start_trace() if ctx.trace else None
    trace_steps = 0
    laps = Laps(("dispatch", "wait"))
    gc.disable()
    while True:
        laps.begin()
        with jax.profiler.TraceAnnotation("bench.train.step"):
            params, opt_state, met = step(params, opt_state,
                                          _feed(batches[i % len(batches)]))
            laps.mark()
            loss = float(met["loss"])
        laps.end()
        i += 1
        steps += 1
        bad += not math.isfinite(loss)
        now = time.perf_counter()
        ends.append(now)
        if tracing is not None and now >= t_trace_end:
            ctx.stop_trace(tracing)
            tracing = None
            trace_steps = steps
        if now - t0 >= ctx.seconds:
            break
    gc.enable()
    t1 = ends[-1]
    in_window_compiles = ctx.clock.compiles - window_compiles
    ctx.memory_peak = ctx.read_memory()
    durs = np.diff([t0] + ends)
    slow = int(np.argmax(durs))
    say(f"set-up {t0 - ctx.t_start:.3f}s; window {t1 - t0:.3f}s: {steps} "
        f"steps of {tokens_per_step} tokens, step ms median "
        f"{1e3 * float(np.median(durs)):.2f} max {1e3 * float(durs[slow]):.2f}"
        f" (step {slow} of the window; "
        f"{int((durs > 2 * np.median(durs)).sum())} over twice the median)"
        f"; compiles inside the window: {in_window_compiles}")
    say(laps.slowest())
    ctx.window_compiles = in_window_compiles
    ctx.set_result(attempted=steps, failed=bad,
                   metrics={"train_tok_s": steps * tokens_per_step / (t1 - t0)})

    text = step.lower(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     opt_state),
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     _feed(batches[0]))).as_text()
    require_kernels(text, ["fastmax_causal_p2", "fastmax_causal_bwd_p2"])
    ctx.kernel_calls = counts.kernel_calls(text)
    if trace_steps:
        ctx.model_flops = trace_steps * counts.train_flops(s, tokens_per_step)
        ctx.step_span = "bench.train.step"
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    del params, opt_state, step, met
    gc.collect()
    return {"losses": losses, "grad1": grad1, "change": change,
            "gnorms": gnorms, "batches": batches[:k], "names": names}


def _gap(prog, ref, keep=None, names=None, what=""):
    """Worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's."""
    ref = np.asarray(ref)
    prog = np.asarray(prog)
    med = float(np.median(ref))
    sel = np.ones(len(ref), bool) if keep is None else keep
    rel = np.where(sel, np.abs(prog - ref) / np.maximum(ref, med), 0.0)
    i = int(np.argmax(rel))
    if names is not None:
        say(f"{what}: worst leaf {names[i]}: program {prog[i]:.6g}, "
            f"reference {ref[i]:.6g}, median leaf {med:.6g}")
    return float(rel[i])


def check(ctx, readings):
    """The reference follows the same first steps; compared: each step's
    loss and global gradient norm before the clip, the first gradient's
    leaf norms, and the leaf norms of the change after the last step
    (leaves whose reference gradient is under a thousandth of the median
    leaf's are left out of the change: they move under Adam by round-off
    alone). With a control in the program's place, the control's readings
    are compared instead of the program's."""
    ref = ctx.reference()
    opt = ctx.mix["optimizer"]

    def follow(mode):
        out = ref.train_readings(ctx.weight_maker()(ctx.key),
                                 readings["batches"], opt, ctx.sizes,
                                 mode=mode)
        return dict(zip(("losses", "grad1", "change", "gnorms"), out))

    r = follow("f32")
    prog = readings if ctx.control is None else follow(ctx.control)
    what = ctx.control or "program"
    names = readings["names"]
    keep = np.asarray(r["grad1"]) >= 1e-3 * float(np.median(r["grad1"]))
    out = {"loss_gap": float(max(abs(a - b) / abs(b) for a, b in
                                 zip(prog["losses"], r["losses"]))),
           "gnorm_gap": float(max(abs(a - b) / b for a, b in
                                  zip(prog["gnorms"], r["gnorms"]))),
           "grad_norm_gap": _gap(prog["grad1"], r["grad1"], None, names,
                                 "first gradient " + what),
           "update_norm_gap": _gap(prog["change"], r["change"], keep, names,
                                   "change " + what)}
    say(f"reference against {what}: losses {r['losses']} and "
        f"{prog['losses']}; gradient norms {r['gnorms']} and "
        f"{prog['gnorms']}; {int((~keep).sum())} of {len(keep)} leaves left "
        f"out of the change")
    return out
