"""Slot-indexed batched decode state: a fixed pool of B sequence slots.

The continuous-batching engine keeps ONE model decode state allocated for
`max_slots` sequences and treats its batch dimension as a pool of slots.
Admitting or evicting a request is a write of one slot's leaves — a
`dynamic_update_slice` per leaf, O(1) in pool size and fully jitted, so the
engine never retraces as requests come and go.

What makes this work for every backend family:

  fastmax  -> a slot's state is the constant-size moment tuple
              (O(D^2 Dv) per kv head, independent of context length) — a
              500k-context slot costs the same bytes as a 64-token one.
              Continuous batching needs NONE of the paged-KV block-table
              machinery softmax serving requires.
  softmax  -> a slot's state is `max_len` masked KV-cache rows with a
              per-slot write cursor (`KVCache.length` as a [B] lane) — the
              O(N) baseline the benchmark compares against.

Because a model decode state is an arbitrary pytree (stacked layer groups
put the slot axis at position 1; `KVCache.length` lanes have it last; SSM
states lead with it), the slot axis of every leaf is discovered ONCE per
(config, pool) by comparing `jax.eval_shape` trees at two different batch
sizes — the one axis whose extent changes with batch is the slot axis.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.attention.state import KVCache

__all__ = ["SlotPool", "SlotManager", "to_slotted", "slot_batch_axes",
           "write_slot", "read_slot"]


def to_slotted(state: Any):
    """Give every `KVCache` in a freshly-initialized decode state a
    PER-SLOT write cursor: `length` [] -> [B] (or [n_groups] ->
    [n_groups, B] under the stacked layer groups), so slots can sit at
    different context lengths inside one batched step."""
    def fix(node):
        if isinstance(node, KVCache):
            b = node.k.shape[node.length.ndim]
            return node._replace(
                length=jnp.zeros(node.length.shape + (b,), jnp.int32))
        return node

    return jax.tree.map(fix, state,
                        is_leaf=lambda x: isinstance(x, KVCache))


def slot_batch_axes(make_state):
    """Per-leaf slot-axis pytree for states built by `make_state(batch)`.

    Compares abstract shapes at batch 2 vs 3: exactly one axis must differ
    per leaf (the slot axis). A leaf whose shape does not depend on batch
    would be shared across slots — that is a bug (it cannot be admitted or
    evicted per-request), so it raises.
    """
    s2 = jax.eval_shape(lambda: make_state(2))
    s3 = jax.eval_shape(lambda: make_state(3))

    def one_axis(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y]
        if len(diffs) != 1:
            raise ValueError(
                f"decode-state leaf {a.shape} has no unique slot axis "
                f"(vs {b.shape}) — a shared leaf cannot be slot-pooled")
        return diffs[0]

    return jax.tree.map(one_axis, s2, s3)


def write_slot(pool_state, unit_state, slot, axes):
    """Write a batch-1 unit state into slot `slot` (traced index): one
    dynamic_update_slice per leaf — O(1) admit/evict, no retrace."""
    def w(p, u, ax):
        return jax.lax.dynamic_update_slice_in_dim(
            p, u.astype(p.dtype), slot, axis=ax)

    return jax.tree.map(w, pool_state, unit_state, axes)


def read_slot(pool_state, slot, axes):
    """Gather slot `slot` as a batch-1 unit state (prefix-cache snapshots,
    chunked-prefill gather)."""
    def r(p, ax):
        return jax.lax.dynamic_slice_in_dim(p, slot, 1, axis=ax)

    return jax.tree.map(r, pool_state, axes)


class SlotPool(NamedTuple):
    """Device-side pool + host-side per-slot lanes (numpy mirrors)."""
    state: Any             # model decode state, slot axis per `axes`
    position: Any          # np [B] int32: committed tokens (next position)
    active: Any            # np [B] bool: decoding (prefill done, not eos)
    eos: Any               # np [B] bool: finished (eos / budget), evictable


class SlotManager:
    """Owns the pooled decode state and the per-slot lanes.

    Device state stays on device between ticks; the tiny int/bool lanes
    live host-side (numpy) because the engine reads and branches on them
    every tick anyway (admission, eviction, streaming).
    """

    def __init__(self, cfg, max_slots: int, max_len: int):
        import numpy as np

        from repro.models import init_decode_state

        self.cfg = cfg
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self._make = lambda b: to_slotted(init_decode_state(cfg, b, max_len))
        self.axes = slot_batch_axes(self._make)
        self.state = self._make(max_slots)
        self.position = np.zeros(max_slots, np.int32)
        self.active = np.zeros(max_slots, bool)
        self.eos = np.zeros(max_slots, bool)
        # every write DONATES the pool, so XLA updates the slot in place
        # instead of holding a second copy of the pool (a fastmax2 slot at
        # D = Dv = 128 is ~1.9 GB); a cold admit builds the fresh unit
        # (slstm's `m` lane inits to -1e9 — zeros_like would be wrong)
        # inside the same launch, so no template stays resident
        self._write = jax.jit(
            functools.partial(write_slot, axes=self.axes),
            donate_argnums=(0,))
        self._write_fresh = jax.jit(
            lambda pool, slot: write_slot(pool, self._make(1), slot,
                                          self.axes),
            donate_argnums=(0,))
        self._read = jax.jit(
            functools.partial(read_slot, axes=self.axes))

    @property
    def fresh_unit(self):
        """A freshly initialized batch-1 unit state (built on demand)."""
        return self._make(1)

    # -- O(1) admit / evict --------------------------------------------------

    def admit(self, slot: int, unit_state=None, position: int = 0):
        """Install a unit state (fresh, or a prefix-cache snapshot covering
        `position` tokens) into `slot`."""
        slot_ = jnp.asarray(slot, jnp.int32)
        if unit_state is None:
            self.state = self._write_fresh(self.state, slot_)
        else:
            self.state = self._write(self.state, unit_state, slot_)
        self.position[slot] = position
        self.active[slot] = False
        self.eos[slot] = False

    def evict(self, slot: int):
        """Free a slot. The state is NOT cleared — the next admit fully
        overwrites every leaf of the slot, so eviction is pure
        host bookkeeping."""
        self.active[slot] = False
        self.eos[slot] = False
        self.position[slot] = 0

    def quarantine(self, slot: int):
        """Free a slot AND re-initialize its device state from the fresh
        template. Unlike `evict`, the state write matters here: a poisoned
        slot (NaN/Inf leaves) must not sit in the pool where a deep state
        check (`REPRO_SERVE_CHECK_STATE=1`) would see it.
        The slot is immediately reusable."""
        self.state = self._write_fresh(self.state,
                                       jnp.asarray(slot, jnp.int32))
        self.evict(slot)

    def snapshot(self, slot: int):
        """Batch-1 copy of a slot's state (prefix cache entries)."""
        return self._read(self.state, jnp.asarray(slot, jnp.int32))

    def state_bytes_per_slot(self) -> int:
        """Slot cost in bytes — constant in context for fastmax, linear for
        the softmax KV baseline (see core.decode_state.decode_state_bytes)."""
        from repro.core.decode_state import decode_state_bytes
        return decode_state_bytes(self.cfg, 1, self.max_len)
