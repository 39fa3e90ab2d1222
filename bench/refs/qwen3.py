"""Plain float32 reference of qwen3 with fastmax attention, as served and
trained: forward, loss and gradient, and the AdamW step the program runs.

It imports nothing of the program. It follows the published Qwen3
architecture (huggingface.co/Qwen/Qwen3-1.7B: RMSNorm pre-norm blocks,
GQA with per-head RMS qk-norm, RoPE, SwiGLU MLP, tied embeddings) with
these departures, all of which the system under test makes too:

  * attention is the FAST paper's fastmax of order 2 (arXiv:2402.07901,
    Eqs. 5-12) in its plain quadratic form: q and k standardized per
    token over the head dim, score f(s) = 1 + s + s^2/2 with s = q.k,
    causal, normalized by the row sum (+1e-6);
  * qk-norm is applied after RoPE, not before (the two agree while the
    qk-norm scales are all one, as they are at initialization);
  * the tied output head is scaled by hidden_size^-1/2.

Matrix products run at `Precision.HIGHEST`. With `mode="fp8"` every
product's inputs are rounded through float8 e4m3 with a per-tensor scale:
that is the control, the step below the bfloat16 the configuration states.
Attention runs in blocks of query rows so that a 16k-token sequence fits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
DENOM_EPS = 1e-6
QK_EPS = 1e-6
QBLOCK = 256
LOSS_BLOCK = 512


def _round(x, mode):
    if mode == "f32":
        return x
    if mode != "fp8":
        raise ValueError(f"unknown reference mode {mode!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(eq, a, b, mode):
    return jnp.einsum(eq, _round(a, mode), _round(b, mode),
                      precision=HIGHEST, preferred_element_type=F32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def standardize(x):
    xc = x - jnp.mean(x, -1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + QK_EPS)


def rope(x, pos, theta):
    """x [H, N, D]; rotate the two halves of the head dim."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def fastmax_causal(q, k, v, mode):
    """q [Hq, N, D], k/v [Hkv, N, D]: causal fastmax p=2, quadratic form,
    QBLOCK query rows at a time. N must be a multiple of QBLOCK."""
    g = q.shape[0] // k.shape[0]
    k = jnp.repeat(k, g, axis=0)
    v = jnp.repeat(v, g, axis=0)
    n = q.shape[1]
    cols = jnp.arange(n)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK, axis=1)
        s = mm("hqd,hnd->hqn", qb, k, mode)
        f = 1.0 + s + 0.5 * s * s
        rows = i * QBLOCK + jnp.arange(QBLOCK)
        f = jnp.where(cols[None, None, :] <= rows[None, :, None], f, 0.0)
        num = mm("hqn,hnd->hqd", f, v, mode)
        return num / (jnp.sum(f, -1, keepdims=True) + DENOM_EPS)

    # each block is recomputed in the backward: its [H, QB, N] scores are
    # not kept for every block at once
    out = jax.lax.map(jax.checkpoint(block), jnp.arange(n // QBLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[0], n, v.shape[-1])


def layer(x, w, pos, s, mode):
    """One decoder block on x [N, d]; w holds that layer's leaves."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    at, ff = w["mixer"], w["ffn"]
    h = rms_norm(x, w["norm1"]["scale"], s["eps"])
    q = mm("nd,dhk->hnk", h, at["wq"], mode)
    k = mm("nd,dhk->hnk", h, at["wk"], mode)
    v = mm("nd,dhk->hnk", h, at["wv"], mode)
    q = rms_norm(rope(q, pos, s["theta"]), at["q_norm_scale"], QK_EPS)
    k = rms_norm(rope(k, pos, s["theta"]), at["k_norm_scale"], QK_EPS)
    o = fastmax_causal(standardize(q), standardize(k), v, mode)
    x = x + mm("hnk,hkd->nd", o, at["wo"], mode)
    h = rms_norm(x, w["norm2"]["scale"], s["eps"])
    u = jax.nn.silu(mm("nd,df->nf", h, ff["wi_gate"], mode)) \
        * mm("nd,df->nf", h, ff["wi_up"], mode)
    return x + mm("nf,fd->nd", u, ff["wo"], mode)


def hidden(w, tokens, s, mode):
    """Final normed hidden states [N, d] of one sequence (N a multiple of
    QBLOCK; trailing padding cannot reach earlier rows, being causal)."""
    x = w["embed"][tokens].astype(F32)
    pos = jnp.arange(tokens.shape[0])
    body = jax.checkpoint(lambda x, wl: (layer(x, wl, pos, s, mode), None))
    x, _ = jax.lax.scan(body, x, w["blocks_0"])
    return rms_norm(x, w["final_norm"]["scale"].astype(F32), s["eps"])


def head(w, mode):
    """The tied output head, rounded once for the control."""
    return _round(w["embed"].astype(F32), mode)


def logits(e, h, s, mode):
    """Logits of hidden rows h against the head e (from `head`)."""
    return jnp.einsum("nd,vd->nv", _round(h, mode), e, precision=HIGHEST,
                      preferred_element_type=F32) * s["d"] ** -0.5


@functools.partial(jax.jit, static_argnames=("s", "mode"))
def row_stats(w, tokens, targets, *, s, mode):
    """Per position of the sequence `tokens`: the best logit, the logit of
    `targets` at that position, and the argmax. Logits are formed
    LOSS_BLOCK rows at a time, so one program serves any set of rows."""
    s = dict(s)
    h = hidden(w, tokens, s, mode)
    e = head(w, mode)

    def block(i):
        hb = jax.lax.dynamic_slice_in_dim(h, i * LOSS_BLOCK, LOSS_BLOCK)
        tb = jax.lax.dynamic_slice_in_dim(targets, i * LOSS_BLOCK, LOSS_BLOCK)
        lg = logits(e, hb, s, mode)
        return (lg.max(-1), jnp.take_along_axis(lg, tb[:, None], -1)[:, 0],
                lg.argmax(-1).astype(jnp.int32))

    out = jax.lax.map(block, jnp.arange(tokens.shape[0] // LOSS_BLOCK))
    return tuple(x.reshape(-1) for x in out)


def bucket(n: int) -> int:
    """Padded length for a sequence of n tokens: a power of two from 1024,
    so that few programs serve every length."""
    b = 1024
    while b < n:
        b *= 2
    return b


def served_gaps(w, s, requests, mode="f32"):
    """For each (prompt, served tokens) pair, teacher-force prompt + served
    and return, per served token, how far the reference's logit of that
    token lies below the reference's best ("f32"). With mode="fp8", the
    control: per position, the gap of the token the fp8 reference puts
    first. Returns one numpy array of gaps per request."""
    key = tuple(sorted(s.items()))
    out = []
    for prompt, served in requests:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        n = len(seq)
        pad = np.zeros(bucket(n), np.int32)
        pad[:n] = seq
        rows = np.arange(len(prompt) - 1, n)
        want = np.zeros_like(pad)
        if mode == "f32":
            want[rows] = served
        else:
            arg = np.asarray(row_stats(w, pad, want, s=key, mode=mode)[2])
            want[rows] = arg[rows]
        best, at, _ = (np.asarray(x) for x in
                       row_stats(w, pad, want, s=key, mode="f32"))
        out.append((best - at)[rows])
    return out


# -- training ---------------------------------------------------------------


def loss(w, tokens, targets, s, mode="f32"):
    """Mean next-token cross-entropy of a batch [B, N] over every position
    but each row's last, as the program masks it. Rows are padded to a
    multiple of LOSS_BLOCK; causal, the padding reaches no real row."""
    b, n = tokens.shape
    npad = -(-n // LOSS_BLOCK) * LOSS_BLOCK
    tokens = jnp.pad(tokens, ((0, 0), (0, npad - n)))
    targets = jnp.pad(targets, ((0, 0), (0, npad - n)))
    mask = (jnp.arange(npad) < n - 1).astype(F32)
    e = head(w, mode)

    def row(i):
        h = hidden(w, tokens[i], s, mode)

        @jax.checkpoint
        def block(j):
            hb = jax.lax.dynamic_slice_in_dim(h, j * LOSS_BLOCK, LOSS_BLOCK)
            tb = jax.lax.dynamic_slice_in_dim(targets[i], j * LOSS_BLOCK,
                                              LOSS_BLOCK)
            mb = jax.lax.dynamic_slice_in_dim(mask, j * LOSS_BLOCK,
                                              LOSS_BLOCK)
            lg = logits(e, hb, s, mode)
            nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
                lg, tb[:, None], -1)[:, 0]
            return jnp.sum(nll * mb)

        return jnp.sum(jax.lax.map(block, jnp.arange(npad // LOSS_BLOCK)))

    total = jnp.sum(jax.lax.map(row, jnp.arange(b)))
    return total / (b * jnp.sum(mask))


def lr_at(step, opt):
    """Warmup then cosine to a floor of 0.1 of the peak."""
    peak, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    step = float(step)
    if step < warm:
        return peak * step / max(1, warm)
    frac = min(max((step - warm) / max(1, total - warm), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * frac)))


_NO_DECAY = ("scale", "bias")


def _decays(path) -> bool:
    name = "/".join(str(getattr(p, "key", p)) for p in path)
    return not name.endswith(_NO_DECAY)


@functools.partial(jax.jit, static_argnames=("s", "mode", "hyper"),
                   donate_argnums=(0, 1, 2))
def adamw_step(w, m, v, tokens, targets, t, lr, *, s, mode, hyper):
    """One step: loss and gradient, global-norm clip, AdamW with decoupled
    weight decay on all but norm scales. Returns the new (w, m, v), the
    loss, the clipped gradient's per-leaf norms and the global norm before
    the clip."""
    b1, b2, eps, wd, clip = hyper
    s = dict(s)
    val, g = jax.value_and_grad(loss)(w, tokens, targets, s, mode)
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (gn + 1e-9)), g)
    b1c = 1.0 - b1 ** t
    b2c = 1.0 - b2 ** t
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)

    def upd(path, p, a, b):
        d = (a / b1c) / (jnp.sqrt(b / b2c) + eps)
        if _decays(path):
            d = d + wd * p
        return p - lr * d

    w = jax.tree_util.tree_map_with_path(upd, w, m, v)
    norms = jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), g)
    return w, m, v, val, norms, gn


def train_readings(w0, batches, opt, s, mode="f32"):
    """Follow the program's first len(batches) steps from the weights w0
    (float32). Returns (losses, first-gradient leaf norms, leaf norms of
    the parameters' change after the last step, each step's global
    gradient norm before the clip), the leaf norms as flat lists in tree
    order."""
    key = tuple(sorted(s.items()))
    w_init = jax.tree.map(np.asarray, w0)      # host copy, served dtype
    w = jax.tree.map(lambda x: x.astype(F32), w0)
    del w0
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, g1, gnorms = [], None, []
    for i, (tok, tgt) in enumerate(batches, start=1):
        w, m, v, val, gn, total = adamw_step(
            w, m, v, jnp.asarray(tok), jnp.asarray(tgt), jnp.float32(i),
            jnp.float32(lr_at(i, opt)), s=key, mode=mode,
            hyper=(opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
                   opt["clip_norm"]))
        losses.append(float(val))
        gnorms.append(float(total))
        if g1 is None:
            g1 = [float(x) for x in jax.tree.leaves(gn)]
    del m, v
    change = [float(jnp.sqrt(jnp.sum((a - jnp.asarray(b).astype(F32)) ** 2)))
              for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(w_init))]
    return losses, g1, change, gnorms
