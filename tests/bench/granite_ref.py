"""Plain float32 reference of the program's granite-20b layout, for the
tiny cell of a second architecture: RMSNorm pre-norm blocks, GQA down to
one kv head, RoPE, a SwiGLU MLP, fastmax of order 2 as in qwen3's
reference, but no qk-norm and an untied output head (`unembed`, not
scaled).

A cell's root holds qwen3's reference beside this file as `qwen3.py`.
This module runs that source in its own namespace and replaces the leaf
tables, the layer and the head: the jitted forward, loss and AdamW step
it defined look these names up here when they trace, so they compute
granite's model, and qwen3's module is left as it is."""
# ruff: noqa: F821 - the names come from qwen3's source, run below
import os

_QWEN3 = os.path.join(os.path.dirname(os.path.abspath(__file__)), "qwen3.py")
with open(_QWEN3) as _f:
    exec(compile(_f.read(), _QWEN3, "exec"))

_TOP = _TOP + (("unembed", lambda s: (s["d"], s["V"]), "normal"),)
_LAYER = tuple(leaf for leaf in _LAYER
               if not leaf[0].endswith("_norm_scale"))


def layer(x, w, pos, s, mode):
    """One decoder block on x [N, d]; w holds that layer's leaves."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    at, ff = w["mixer"], w["ffn"]
    h = rms_norm(x, w["norm1"]["scale"], s["eps"])
    q = rope(mm("nd,dhk->hnk", h, at["wq"], mode), pos, s["theta"])
    k = rope(mm("nd,dhk->hnk", h, at["wk"], mode), pos, s["theta"])
    v = mm("nd,dhk->hnk", h, at["wv"], mode)
    o = fastmax_causal(standardize(q), standardize(k), v, mode)
    x = x + mm("hnk,hkd->nd", o, at["wo"], mode)
    h = rms_norm(x, w["norm2"]["scale"], s["eps"])
    u = jax.nn.silu(mm("nd,df->nf", h, ff["wi_gate"], mode)) \
        * mm("nd,df->nf", h, ff["wi_up"], mode)
    return x + mm("nf,fd->nd", u, ff["wo"], mode)


def head(w, mode):
    """The untied output head [d, V], rounded once for the control."""
    return _round(w["unembed"].astype(F32), mode)


def logits(e, h, s, mode):
    """Logits of hidden rows h against the head e (from `head`)."""
    return jnp.einsum("nd,dv->nv", _round(h, mode), e,
                      precision=HIGHEST, preferred_element_type=F32)
