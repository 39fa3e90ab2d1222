"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Every parameter carries a tuple of logical axis names (repro.models.param);
`spec_for` maps them to a PartitionSpec against the active mesh with
divisibility fallback: if a mesh-axis product does not divide the dim (e.g.
kv_heads=8 on model=16, or kv=1 MQA), the dim falls back to fewer axes or
replication — never an invalid spec.

Parallelism map (single pod (data=16, model=16); multi-pod adds "pod"):
  DP    batch            -> ("pod", "data")
  FSDP  weights' embed    -> "data"  (ZeRO-3 within pod; pods replicate,
                                      optimizer state can add "pod")
  TP    heads/ff/vocab    -> "model"
  EP    experts           -> "model"
  SP    long-context decode state feature dims -> ("data","model") when the
        batch can't use "data" (batch=1 long_500k)
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["DEFAULT_RULES", "spec_for", "param_shardings", "batch_spec",
           "decode_state_shardings", "maybe_constraint", "replicate",
           "active_mesh", "shard_stacked", "kv_cache_spec",
           "constrain_kv_cache", "model_axis_size"]


def active_mesh():
    """The mesh sharding constraints should target, or None: the abstract
    mesh that `jax.set_mesh` installs, else the concrete mesh of an
    enclosing `with mesh:` block. Keep this discovery HERE only (a stale
    copy once left `feature_shard_flag` returning False on every call)."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        from jax._src import mesh as mesh_lib
        mesh = mesh_lib.thread_resources.env.physical_mesh
    return mesh if mesh.axis_names else None


def replicate(x, *, batch_dim=None):
    """with_sharding_constraint to model-replicated; no-op without an active
    mesh. Used to pin small tensors (queries/denominators on the serve
    combine path) so XLA doesn't propagate a large-tensor sharding conflict
    through them. `batch_dim` keeps data parallelism on that dim (greedy
    pod/data axes when they divide it) while every other dim is pinned
    replicated."""
    mesh = active_mesh()
    if mesh is None:
        return x
    entries = [None] * x.ndim
    if batch_dim is not None:
        entries[batch_dim], _ = _batch_entry(mesh, x.shape[batch_dim])
    return jax.lax.with_sharding_constraint(x, P(*entries))


def model_axis_size(mesh=None) -> int:
    """Size of the 'model' (TP) axis of the given/active mesh; 1 if none."""
    mesh = mesh if mesh is not None else active_mesh()
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return 1
    return mesh.shape["model"]


def shard_stacked(x, *, batch_dim=1, model_dim=None, seq_dim=None):
    """Pin a scan-stacked chunk tensor [nc, B, ...] to one total layout.

    The chunked-scan paths stack their per-chunk inputs/outputs along a
    leading axis and `lax.scan` over it. Once the scan CARRY is feature-TP
    constrained (`_constrain_moments_j`), the partitioner back-propagates
    'model' shardings into the stacked chunks and flip-flops against the
    batch layout they arrived with — the measured 0→12 involuntary-remat
    regression on train_4k (ROADMAP). Pinning each stacked tensor totally —
    DP axes on `batch_dim`, 'model' on `model_dim` (the value-feature dim of
    v/output chunks; None = model-replicated), everything else replicated —
    gives the scan one consistent layout at its boundary, so enabling
    feature-TP on the scan no longer induces remats.

    `seq_dim` pins the stacked-chunk axis itself to the "seq" (context-
    parallel) mesh axis when present and dividing: contiguous chunk runs
    then live on the device that owns those tokens, so a jnp chunked path
    under a CP mesh keeps its stacked buffers token-local instead of
    replicating nc full-size chunk tensors per device.

    No-op without an active mesh; axes that don't divide degrade to
    replication like every rule here.
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    entries = [None] * x.ndim
    entries[batch_dim], _ = _batch_entry(mesh, x.shape[batch_dim])
    if model_dim is not None:
        model_dim = model_dim % x.ndim
        tp = model_axis_size(mesh)
        if tp > 1 and x.shape[model_dim] % tp == 0:
            entries[model_dim] = "model"
    if seq_dim is not None and "seq" in mesh.axis_names:
        seq_dim = seq_dim % x.ndim
        cp = mesh.shape["seq"]
        if cp > 1 and entries[seq_dim] is None \
                and x.shape[seq_dim] % cp == 0:
            entries[seq_dim] = "seq"
    return jax.lax.with_sharding_constraint(x, P(*entries))


def maybe_constraint(x, *want_axes):
    """with_sharding_constraint that degrades gracefully: applies only the
    axes present in the active mesh AND dividing the dim; no-op without a
    mesh (smoke tests on 1 device)."""
    mesh = active_mesh()
    if mesh is None:
        return x
    used: set = set()
    entries = []
    for dim, cand in enumerate(want_axes):
        if cand is None:
            entries.append(None)
            continue
        cands = cand if isinstance(cand, tuple) else (cand,)
        chosen = []
        prod = 1
        for a in cands:
            if a in mesh.axis_names and a not in used \
                    and x.shape[dim] % (prod * mesh.shape[a]) == 0:
                chosen.append(a)
                prod *= mesh.shape[a]
        used.update(chosen)
        entries.append(None if not chosen
                       else (chosen[0] if len(chosen) == 1
                             else tuple(chosen)))
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(x, P(*entries))

DEFAULT_RULES = {
    "embed": ("data",),       # FSDP/ZeRO-3 for weight matrices
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "layers": (),
}

NO_FSDP_RULES = {**DEFAULT_RULES, "embed": ()}


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def spec_for(axes: tuple, shape: tuple, mesh: Mesh,
             rules: Optional[dict] = None) -> P:
    rules = rules or DEFAULT_RULES
    used: set = set()
    out = []
    for logical, size in zip(axes, shape):
        if logical is None:
            out.append(None)
            continue
        want = [a for a in rules.get(logical, ()) if a not in used
                and a in mesh.axis_names]
        # greedy prefix that divides the dim size
        chosen = []
        prod = 1
        for a in want:
            if size % (prod * _axis_size(mesh, a)) == 0:
                chosen.append(a)
                prod *= _axis_size(mesh, a)
        if not chosen:
            out.append(None)
        elif len(chosen) == 1:
            out.append(chosen[0])
            used.add(chosen[0])
        else:
            out.append(tuple(chosen))
            used.update(chosen)
    return P(*out)


def param_shardings(axes_tree, shape_tree, mesh: Mesh,
                    rules: Optional[dict] = None):
    """Tree of NamedSharding matching a param (or same-shaped state) tree."""
    is_axes_leaf = lambda x: isinstance(x, tuple)  # noqa: E731

    def one(axes, leaf):
        return NamedSharding(mesh, spec_for(axes, leaf.shape, mesh, rules))

    return jax.tree.map(one, axes_tree, shape_tree, is_leaf=is_axes_leaf)


def batch_spec(mesh: Mesh, *, batch_size: int) -> P:
    """Batch-dim sharding: as much DP as divides the global batch."""
    axes = []
    prod = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and batch_size % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
    if not axes:
        return P(None)
    return P(tuple(axes) if len(axes) > 1 else axes[0])


def _dim_spec(size: int, mesh: Mesh, prefer: list, used: set):
    """Greedy: shard `size` over the first unused axes that divide it."""
    chosen = []
    prod = 1
    for a in prefer:
        if a in mesh.axis_names and a not in used \
                and size % (prod * mesh.shape[a]) == 0:
            chosen.append(a)
            prod *= mesh.shape[a]
    for a in chosen:
        used.add(a)
    if not chosen:
        return None
    return chosen[0] if len(chosen) == 1 else tuple(chosen)


def _batch_entry(mesh: Mesh, size: int):
    """Greedy DP entry for a batch-like dim, plus the axes it consumed."""
    chosen, prod = [], 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and size > 1 \
                and size % (prod * mesh.shape[a]) == 0:
            chosen.append(a)
            prod *= mesh.shape[a]
    entry = (None if not chosen
             else (chosen[0] if len(chosen) == 1 else tuple(chosen)))
    return entry, set(chosen)


def kv_cache_spec(shape: tuple, mesh: Mesh, *, lead: int = 0) -> P:
    """PartitionSpec of a KV-cache leaf [*lead, B, Hkv, Nmax, *feat].

    Matches what the cache's CONSUMERS (`softmax_attention` inside the
    decode step) can use: kv heads over 'model' when they divide it, else
    the SEQUENCE dim over 'model' (each device scans its slice of the
    timeline; softmax's max/sum become clean partial reductions). The
    head_dim/Dv trailing dim is deliberately never sharded — the old
    last-dim-first generic policy put 'model' there, which no consumer
    matmul could keep, and the partitioner answered with involuntary full
    rematerializations of cache-sized tensors every step (the 3 SOFTMAX
    32k-decode warnings, ROADMAP).
    """
    entries = [None] * len(shape)
    b_entry, used = _batch_entry(mesh, shape[lead])
    entries[lead] = b_entry
    tp = model_axis_size(mesh)
    if tp > 1 and len(shape) > lead + 2:
        hkv, nmax = shape[lead + 1], shape[lead + 2]
        if hkv % tp == 0:
            entries[lead + 1] = "model"
        elif nmax % tp == 0:
            entries[lead + 2] = "model"
    return P(*entries)


def constrain_kv_cache(x, *, lead: int = 0):
    """with_sharding_constraint to `kv_cache_spec` (no-op without a mesh).

    Applied by the softmax decode/prefill step to the freshly-updated
    cache so the in-step tensors keep the committed inter-step layout."""
    mesh = active_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, kv_cache_spec(x.shape, mesh, lead=lead))


# base ndims of the Moments fields (batch, kv-heads leading): any extra
# leading axes on a state leaf are layer-stacking (scan-over-layers groups)
_MOMENT_NDIM = {"m0": 3, "m1": 4, "m2": 5, "g0": 2, "g1": 3, "g2": 4}


def _moments_shardings(mom, mesh: Mesh):
    """Shardings of a Moments(-shaped) state: the SAME partitioning the
    shard_map-wrapped kernels use (repro.kernels.sharded) — for decode,
    prefill, AND the feature-TP trainable custom_vjp residual (the
    Dv-blocked backward consumes the carry in exactly this layout) — so
    the committed inter-step layout and every kernel launch agree with
    zero resharding:

      heads mode    (Hkv % tp == 0): kv-head dim over 'model';
      feature mode  (else, Dv % tp == 0): value-feature (last) dim of
                    m0/m1/m2 over 'model', scalar g-moments REPLICATED
                    across 'model' (they are Dv-times smaller than their m
                    partners; replicating them keeps the decode step's
                    denominator exact shard-locally instead of resharding
                    g2 over the ICI every token).
    """
    tp = model_axis_size(mesh)
    fields = type(mom)._fields if hasattr(type(mom), "_fields") else \
        tuple(_MOMENT_NDIM)

    hkv = None
    dv = None
    lead = mom[0].ndim - _MOMENT_NDIM["m0"]
    if lead >= 0:
        hkv = mom[0].shape[lead + 1]
        dv = mom[0].shape[-1]
    heads_mode = tp > 1 and hkv is not None and hkv % tp == 0
    feat_mode = (not heads_mode and tp > 1 and dv is not None
                 and dv % tp == 0)

    def one(name, leaf):
        nd = _MOMENT_NDIM.get(name)
        if nd is None or leaf.ndim < nd:
            return NamedSharding(mesh, P())
        ld = leaf.ndim - nd
        entries = [None] * leaf.ndim
        entries[ld], _ = _batch_entry(mesh, leaf.shape[ld])
        if heads_mode:
            entries[ld + 1] = "model"
        elif feat_mode and name in ("m0", "m1", "m2"):
            entries[-1] = "model"
        return NamedSharding(mesh, P(*entries))

    return type(mom)(*(one(n, leaf) for n, leaf in zip(fields, mom)))


def decode_state_shardings(state_shapes, mesh: Mesh, *, batch: int):
    """Shard a decode-state tree (KV caches / fastmax moments / ssm states).

    Structured nodes get consumer-matched policies — `Moments` the
    shard_map kernel partitioning (`_moments_shardings`), `KVCache`
    k/v/mask the `kv_cache_spec` head-or-sequence layout. Generic leaves
    (ssm/xlstm states) keep the greedy policy: batch -> (pod, data) when
    divisible, then the LARGEST remaining dims -> remaining mesh axes
    (model first), realizing full feature sharding ("data"+"model") for
    batch=1 long-context decode.
    """
    from repro.attention.state import KVCache
    from repro.core.fastmax import Moments

    def generic(leaf):
        shape = leaf.shape
        if not shape:
            return NamedSharding(mesh, P())
        out = []
        # dim 0 = batch
        b_entry, used = _batch_entry(mesh, shape[0])
        out.append(b_entry)
        # remaining dims: LAST dim first (feature dims combine locally when
        # sharded; scan-sliced dims must stay unsharded), then largest
        order = sorted(range(1, len(shape)),
                       key=lambda i: (0 if i == len(shape) - 1 else 1,
                                      -shape[i]))
        specs = {i: None for i in order}
        for i in order:
            specs[i] = _dim_spec(shape[i], mesh,
                                 ["model", "data", "pod"], used)
        out.extend(specs[i] for i in range(1, len(shape)))
        return NamedSharding(mesh, P(*out))

    def kv_shardings(kv):
        lead = kv.k.ndim - 4
        def one(name, leaf):
            if name in ("k", "v", "mask"):
                return NamedSharding(
                    mesh, kv_cache_spec(leaf.shape, mesh, lead=lead))
            return NamedSharding(mesh, P())  # length scalar
        return type(kv)(*(one(n, leaf)
                          for n, leaf in zip(type(kv)._fields, kv)))

    def node(x):
        if isinstance(x, Moments):
            return _moments_shardings(x, mesh)
        if isinstance(x, KVCache):
            return kv_shardings(x)
        return jax.tree.map(generic, x)

    return jax.tree.map(
        node, state_shapes,
        is_leaf=lambda x: isinstance(x, (Moments, KVCache)))
