"""Continuous-batching serving engine over the unified decode protocol.

One `ServeEngine` owns a `SlotManager` pool of `max_slots` sequences and
advances the whole pool one "tick" at a time. Each tick is ONE jitted
launch that mixes the two kinds of work (3 traces total, keyed by which
parts are present):

  prefill part  -> the next `chunk`-token slice of ONE pending request's
                   prompt runs through `lm_prefill(offset=...)` against
                   that slot's state (read_slot -> prefill -> write_slot).
                   The chunk that completes the prompt also emits the
                   request's FIRST token (argmax of the last valid row).
  decode part   -> every active slot takes one `lm_decode_step` with its
                   own last token and its own position lane; slots that
                   are inactive / mid-prefill ride through the batched
                   compute with a `live` flag off, which keeps their state
                   unchanged layer by layer (inside the decode kernel,
                   or by a per-layer select elsewhere).

All backends route through the same `init_state`/`prefill`/`step`
protocol, so the engine works unchanged for softmax-KV, fastmax (chunked
or kernel), GQA/MQA, and SSM-mixer architectures. Greedy decoding matches
`launch.serve.generate` token-for-token (the parity contract
`tests/test_serve.py` pins for every registered backend).

Chunked prefill decomposition equals `generate()`'s internal scan when
`chunk == cfg.chunk_size` (the default) — for fastmax backends the moment
arithmetic is then bit-identical, not merely close.

Robustness layer (`serve/errors.py`, `docs/serving.md`):

  * every request carries a `RequestStatus`; all terminal outcomes
    (FINISHED / FAILED / CANCELLED / TIMED_OUT / REJECTED) are reported
    as `FinishedRequest` records with a diagnostic, never silently lost;
  * `submit()` enforces a bounded queue (depth + prompt-token budget,
    `EngineOverloaded` on overflow) and the engine sheds the
    newest/largest waiters under sustained saturation — memory and
    latency degrade predictably instead of unboundedly;
  * per-request TTFT / total deadlines and `cancel(rid)` free slots
    mid-prefill or mid-decode and drop the request's prefix-cache
    snapshots;
  * a cheap per-tick non-finite guard on emitted logits (fastmax's
    unnormalized moment sums can overflow low precision at long context)
    fails ONLY the poisoned request and quarantines + re-initializes its
    slot; `REPRO_SERVE_CHECK_STATE=1` adds a deep per-tick check over
    every floating decode-state leaf;
  * a watchdog (`repro.ft.StragglerMonitor` underneath) raises
    `EngineStalled` with an engine snapshot on sustained no-progress
    ticks, blown per-tick wall-clock budgets, or `run()` exhausting
    `max_ticks` with requests still pending — the engine never silently
    spins;
  * `stats()` exposes the counters (admitted / rejected / shed /
    timed_out / cancelled / quarantined / failed / finished, queue depth,
    slot occupancy, prefill and decode ticks and tokens) the CLI and the
    benchmark report.

Each tick records profiler spans (`serve.step` with its phases
`serve.admit`, `serve.schedule`, `serve.launch`, `serve.wait`,
`serve.emit`) on the clock of the device trace; with no profiler running
they cost about a microsecond each (`docs/serving.md`, "Tracing the
engine").

Deterministic chaos for all of the above lives in `serve/faults.py`
(`ServeEngine(..., faults=FaultInjector())`), driven by
`tests/test_serve_faults.py` (`make test-faults`).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ft import StragglerMonitor
from repro.models.transformer import ModelConfig, lm_decode_step, lm_prefill
from repro.serve.errors import (TERMINAL_STATUSES, EngineOverloaded,
                                EngineStalled, RequestStatus)
from repro.serve.prefix_cache import PrefixCache
from repro.serve.scheduler import Request, Scheduler
from repro.serve.slots import SlotManager, read_slot, write_slot

__all__ = ["ServeEngine", "FinishedRequest"]

# status -> stats() counter bumped when a request reaches that terminal
_TERMINAL_COUNTER = {
    RequestStatus.FINISHED: "finished",
    RequestStatus.FAILED: "failed",
    RequestStatus.CANCELLED: "cancelled",
    RequestStatus.TIMED_OUT: "timed_out",
    RequestStatus.REJECTED: "shed",
}


def _check_eos_id(eos) -> Optional[int]:
    """eos_id must be a non-negative integer token id (bool is an int
    subclass and always a bug here, so it is rejected explicitly)."""
    if eos is None:
        return None
    if isinstance(eos, bool) or not isinstance(eos, (int, np.integer)):
        raise ValueError(
            f"eos_id must be an integer token id, got "
            f"{type(eos).__name__}: {eos!r}")
    if eos < 0:
        raise ValueError(f"eos_id must be non-negative, got {eos}")
    return int(eos)


@dataclasses.dataclass
class FinishedRequest:
    rid: int
    tokens: np.ndarray            # [n_generated] int32 (includes eos if hit)
    prompt_len: int
    ttft: Optional[float]         # submit -> first token (s); None if never
    latency: float                # submit -> terminal state (s)
    status: RequestStatus = RequestStatus.FINISHED
    error: Optional[str] = None   # diagnostic on non-FINISHED terminals

    @property
    def ok(self) -> bool:
        return self.status is RequestStatus.FINISHED


class ServeEngine:
    def __init__(self, params, cfg: ModelConfig, *, max_slots: int = 4,
                 max_len: int = 512, eos_id: Optional[int] = None,
                 policy: str = "fcfs", chunk: Optional[int] = None,
                 prefix_cache_bytes: int = 0, max_wait: int = 64,
                 max_queue: int = 256, max_queue_tokens: int = 0,
                 shed_after: int = 64, tick_budget_s: Optional[float] = None,
                 stall_ticks: int = 64, faults=None):
        if cfg.encoder_layers > 0:
            raise NotImplementedError(
                "repro.serve targets decoder-only models; use "
                "launch.serve.generate for encoder-decoder")
        self.params = params
        self.cfg = cfg
        self.eos_id = _check_eos_id(eos_id)
        self.chunk = int(chunk or cfg.chunk_size)
        self.slots = SlotManager(cfg, max_slots, max_len)
        self.scheduler = Scheduler(policy, max_wait=max_wait,
                                   max_depth=max_queue,
                                   max_queued_tokens=max_queue_tokens)
        self.prefix_cache = (PrefixCache(prefix_cache_bytes, chunk=self.chunk)
                             if prefix_cache_bytes > 0 else None)
        # ragged final chunks are right-padded + kv_mask'ed, which only the
        # attention prefill path understands; SSM mixers get an exact-length
        # (retracing) ragged chunk instead
        self._pad_ragged = all(k.split(":")[0] == "attn"
                               for k in cfg.pattern)

        b = self.slots.max_slots
        self._rid: List[Optional[int]] = [None] * b
        self._req: Dict[int, Request] = {}
        self._prompt_len = np.zeros(b, np.int32)
        self._last_token = np.zeros(b, np.int32)
        self._generated: Dict[int, List[int]] = {}
        self._next_rid = 0
        self._prefill_cursor = 0      # round-robin over mid-prefill slots
        self.tick_count = 0
        self.decode_tokens = 0        # decode-part tokens (TPOT accounting)
        self.prefill_tokens = 0
        self.decode_ticks = 0         # ticks with a decode part
        self.decode_masked_slots = 0  # slot-ticks a decode part held as is
        self.prefill_ticks = 0        # ticks with a prefill part
        self.history: List[FinishedRequest] = []   # terminal records, in order
        self.statuses: Dict[int, RequestStatus] = {}  # rid -> last status

        # robustness knobs
        self.shed_after = int(shed_after)     # saturated ticks before shed
        self.tick_budget_s = tick_budget_s    # wall-clock budget per tick
        self.stall_ticks = int(stall_ticks)   # no-progress ticks -> stalled
        self.faults = faults                  # serve.faults.FaultInjector
        self.monitor = StragglerMonitor()     # tick-time stats (ft idiom)
        self.counters: Dict[str, int] = {
            "admitted": 0, "rejected": 0, "shed": 0, "timed_out": 0,
            "cancelled": 0, "quarantined": 0, "failed": 0, "finished": 0}
        self._saturated_ticks = 0
        self._stall_strikes = 0
        self._budget_strikes = 0
        self._budget_patience = 3
        self._check_state = os.environ.get("REPRO_SERVE_CHECK_STATE") == "1"
        self._finite_fn = None                # lazily jitted deep check

        # the pool state is donated: the tick updates it in place rather
        # than holding the old and the new pool at once
        self._tick_fn = jax.jit(
            functools.partial(_tick, cfg=cfg, axes=self.slots.axes),
            static_argnames=("do_prefill", "do_decode"),
            donate_argnums=(1,))

    # -- submission ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *, eos_id=None,
               callback=None, ttft_deadline: Optional[float] = None,
               deadline: Optional[float] = None) -> int:
        """Enqueue one request. Raises `ValueError` on malformed input and
        `EngineOverloaded` when the bounded queue refuses admission (the
        engine state is unchanged in both cases)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError(
                "empty prompt: at least one token must prefill to produce "
                "the first logits")
        if len(prompt) > self.slots.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the model context "
                f"(engine max_len {self.slots.max_len})")
        if max_new_tokens <= 0:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if len(prompt) + max_new_tokens > self.slots.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + gen {max_new_tokens} exceeds "
                f"max_len {self.slots.max_len}")
        eos = self.eos_id if eos_id is None else _check_eos_id(eos_id)
        for name, d in (("ttft_deadline", ttft_deadline),
                        ("deadline", deadline)):
            if d is not None and d < 0:
                raise ValueError(f"{name} must be >= 0 seconds, got {d}")
        req = Request(
            rid=self._next_rid, prompt=prompt,
            max_new_tokens=int(max_new_tokens), eos_id=eos,
            callback=callback, submit_tick=self.tick_count,
            submit_time=time.monotonic(),
            ttft_deadline=ttft_deadline, deadline=deadline)
        try:
            self.scheduler.push(req)
        except EngineOverloaded:
            self.counters["rejected"] += 1
            raise
        self._next_rid += 1
        self.statuses[req.rid] = RequestStatus.QUEUED
        return req.rid

    @property
    def pending(self) -> int:
        """Requests not yet finished (queued + in a slot)."""
        return len(self.scheduler) + sum(r is not None for r in self._rid)

    def status(self, rid: int) -> Optional[RequestStatus]:
        """Last known status of a request (None for unknown rids)."""
        return self.statuses.get(rid)

    def stats(self) -> Dict[str, int]:
        """Host-side health counters: terminal-outcome totals plus the
        instantaneous queue / slot occupancy, and the prefill and decode
        ticks and tokens."""
        return {
            **self.counters,
            "queue_depth": len(self.scheduler),
            "queued_tokens": self.scheduler.queued_tokens,
            "slots_occupied": sum(r is not None for r in self._rid),
            "slots_total": self.slots.max_slots,
            "ticks": self.tick_count,
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "prefill_ticks": self.prefill_ticks,
            "decode_ticks": self.decode_ticks,
            "decode_masked_slots": self.decode_masked_slots,
        }

    def snapshot(self) -> Dict[str, Any]:
        """Postmortem view of the engine (attached to `EngineStalled`)."""
        return {
            "tick": self.tick_count,
            "queue_depth": len(self.scheduler),
            "queued_tokens": self.scheduler.queued_tokens,
            "slots": [
                {"slot": i, "rid": self._rid[i],
                 "position": int(self.slots.position[i]),
                 "prompt_len": int(self._prompt_len[i]),
                 "active": bool(self.slots.active[i]),
                 "eos": bool(self.slots.eos[i])}
                for i in range(self.slots.max_slots)],
            "counters": dict(self.counters),
            "tick_time": self.monitor.stats(),
        }

    # -- cancellation --------------------------------------------------------

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it is — queued, mid-prefill, or
        mid-decode. Frees its slot immediately, drops its prefix-cache
        snapshots, and records a CANCELLED `FinishedRequest` (with the
        tokens generated so far) in `history`. Returns False for unknown
        or already-terminal rids."""
        req = self.scheduler.remove(rid)
        if req is not None:
            self._finalize(req, [], RequestStatus.CANCELLED,
                           "cancelled while queued", [])
            return True
        for slot in range(self.slots.max_slots):
            if self._rid[slot] == rid:
                req = self._req[rid]
                phase = "decode" if self.slots.active[slot] else "prefill"
                toks = self._generated.pop(rid, [])
                if self.prefix_cache is not None:
                    self.prefix_cache.invalidate(req.prompt)
                self._rid[slot] = None
                del self._req[rid]
                self.slots.evict(slot)
                self._finalize(req, toks, RequestStatus.CANCELLED,
                               f"cancelled mid-{phase}", [])
                return True
        return False

    # -- the tick ------------------------------------------------------------

    def step(self) -> List[FinishedRequest]:
        """Advance the pool by one mixed prefill+decode launch. Returns
        every request that reached a terminal state this tick (finished,
        failed, timed out, or shed)."""
        self.monitor.start_step()
        self.tick_count += 1
        with jax.profiler.StepTraceAnnotation("serve.step",
                                              step_num=self.tick_count):
            if self.faults is not None:
                self.faults.apply(self, self.tick_count)
            finished: List[FinishedRequest] = []
            with jax.profiler.TraceAnnotation("serve.admit"):
                self._expire_deadlines(finished)
                self._shed_if_saturated(finished)
                admitted = self._admit()

            with jax.profiler.TraceAnnotation("serve.schedule"):
                pre = self._pick_prefill()
                live = self.slots.active & ~self.slots.eos
                do_decode = bool(live.any())
                slot = chunk_tok = kv_mask = off = nvalid = None
                pre_args = dec_args = (None,) * 3
                if pre is not None:
                    slot, chunk_tok, kv_mask, off, nvalid = pre
                    pre_args = (jnp.asarray(slot, jnp.int32),
                                jnp.asarray(off, jnp.int32),
                                jnp.asarray(nvalid, jnp.int32))
                if do_decode:
                    dec_args = (jnp.asarray(self._last_token),
                                jnp.asarray(self.slots.position),
                                jnp.asarray(live))
            if pre is not None or do_decode:
                self.prefill_ticks += pre is not None
                self.decode_ticks += do_decode
                if do_decode:
                    self.decode_masked_slots += int((~live).sum())
                with jax.profiler.TraceAnnotation("serve.launch"):
                    self.slots.state, *outs = self._tick_fn(
                        self.params, self.slots.state, pre_args[0],
                        chunk_tok, kv_mask, *pre_args[1:], *dec_args,
                        do_prefill=pre is not None, do_decode=do_decode)
                # the tick's one host sync: every output in one fetch
                with jax.profiler.TraceAnnotation("serve.wait"):
                    first_tok, pre_ok, nxt, dec_ok = jax.device_get(outs)

                with jax.profiler.TraceAnnotation("serve.emit"):
                    if pre is not None:
                        self._after_prefill(slot, nvalid, first_tok,
                                            bool(pre_ok), finished)
                    if do_decode:
                        self._after_decode(live, nxt, dec_ok, finished)
                    if self._check_state:
                        self._deep_state_check(finished)

            progressed = bool(admitted or pre is not None or do_decode
                              or finished)
            self._watchdog(self.monitor.end_step(), progressed)
        return finished

    def run(self, *, max_ticks: int = 1_000_000) -> Dict[int, np.ndarray]:
        """Drive ticks until every submitted request reached a terminal
        state. Returns {rid: tokens} for every request that terminated
        inside the loop (failed/timed-out entries carry the tokens
        generated before the fault). Raises `EngineStalled` — with an
        engine snapshot — if `max_ticks` is exhausted with requests still
        pending, instead of silently returning a partial map."""
        done: Dict[int, np.ndarray] = {}
        for _ in range(max_ticks):
            if not self.pending:
                return done
            for fin in self.step():
                done[fin.rid] = fin.tokens
        if self.pending:
            raise EngineStalled(
                f"run() exhausted max_ticks={max_ticks} with {self.pending} "
                f"requests still pending "
                f"({len(self.scheduler)} of them queued)", self.snapshot())
        return done

    def stream(self, prompt, max_new_tokens: int, *,
               eos_id=None) -> Iterator[int]:
        """Submit one request and yield its tokens as they are produced
        (other already-submitted requests keep making progress). Stops
        cleanly if the request reaches ANY terminal state — a cancelled or
        failed stream simply ends after its last good token."""
        box: List[int] = []
        rid = self.submit(prompt, max_new_tokens, eos_id=eos_id,
                          callback=lambda _rid, tok: box.append(tok))
        while True:
            fins = self.step()
            while box:
                yield box.pop(0)
            if any(f.rid == rid for f in fins):
                return
            if self.statuses.get(rid) in TERMINAL_STATUSES:
                return              # cancelled/failed outside this tick

    # -- internals -----------------------------------------------------------

    def _finalize(self, req: Request, tokens, status: RequestStatus,
                  error: Optional[str],
                  finished: List[FinishedRequest]) -> FinishedRequest:
        """Single exit point for every terminal outcome: stamp the request,
        bump the status counter, and record the FinishedRequest."""
        req.finish_time = time.monotonic()
        req.status = status
        req.error = error
        fin = FinishedRequest(
            rid=req.rid,
            tokens=np.asarray(tokens, np.int32),
            prompt_len=len(req.prompt),
            ttft=(None if req.first_token_time is None
                  else req.first_token_time - req.submit_time),
            latency=req.finish_time - req.submit_time,
            status=status, error=error)
        self.statuses[req.rid] = status
        self.counters[_TERMINAL_COUNTER[status]] += 1
        self.history.append(fin)
        finished.append(fin)
        return fin

    def _expire_deadlines(self, finished: List[FinishedRequest]) -> None:
        now = time.monotonic()
        for req in self.scheduler.take_expired(now):
            self._finalize(
                req, [], RequestStatus.TIMED_OUT,
                f"RequestTimeout: deadline expired after "
                f"{now - req.submit_time:.3f}s in queue", finished)
        for slot in range(self.slots.max_slots):
            rid = self._rid[slot]
            if rid is None:
                continue
            req = self._req[rid]
            waited = now - req.submit_time
            if req.first_token_time is None and \
                    req.ttft_deadline is not None and \
                    waited > req.ttft_deadline:
                self._release_abnormal(
                    slot, RequestStatus.TIMED_OUT,
                    f"RequestTimeout: TTFT deadline {req.ttft_deadline}s "
                    f"expired after {waited:.3f}s (prefill at "
                    f"{int(self.slots.position[slot])}/"
                    f"{int(self._prompt_len[slot])})", finished)
            elif req.deadline is not None and waited > req.deadline:
                self._release_abnormal(
                    slot, RequestStatus.TIMED_OUT,
                    f"RequestTimeout: deadline {req.deadline}s expired "
                    f"after {waited:.3f}s", finished)

    def _shed_if_saturated(self, finished: List[FinishedRequest]) -> None:
        """Graceful degradation: once the bounded queue has been FULL for
        `shed_after` consecutive ticks, shed the newest/largest waiters
        down to 3/4 depth — predictable victims with a clear status instead
        of unbounded waiting for everyone."""
        depth_cap = self.scheduler.max_depth
        if not self.shed_after or not depth_cap:
            return
        if len(self.scheduler) >= depth_cap:
            self._saturated_ticks += 1
        else:
            self._saturated_ticks = 0
            return
        if self._saturated_ticks < self.shed_after:
            return
        target = max(1, (3 * depth_cap) // 4)
        while len(self.scheduler) > target:
            req = self.scheduler.shed()
            if req is None:
                break
            self._finalize(
                req, [], RequestStatus.REJECTED,
                f"shed after {self._saturated_ticks} ticks of sustained "
                f"queue saturation (depth {depth_cap})", finished)
        self._saturated_ticks = 0            # re-arm

    def _watchdog(self, dt: float, progressed: bool) -> None:
        """Stall detection: sustained blown tick budgets or sustained
        no-progress ticks (with requests pending) raise `EngineStalled`
        carrying `snapshot()` — the engine never silently spins."""
        if self.tick_budget_s is not None and dt > self.tick_budget_s:
            self._budget_strikes += 1
            if self._budget_strikes >= self._budget_patience:
                raise EngineStalled(
                    f"tick wall-clock budget blown "
                    f"{self._budget_strikes}x in a row (last tick "
                    f"{dt * 1e3:.1f}ms > budget "
                    f"{self.tick_budget_s * 1e3:.1f}ms)", self.snapshot())
        else:
            self._budget_strikes = 0
        if self.pending and not progressed:
            self._stall_strikes += 1
            if self._stall_strikes >= self.stall_ticks:
                raise EngineStalled(
                    f"no tick progress for {self._stall_strikes} ticks "
                    f"with {self.pending} requests pending", self.snapshot())
        else:
            self._stall_strikes = 0

    def _admit(self) -> int:
        n = 0
        for slot in range(self.slots.max_slots):
            if self._rid[slot] is not None:
                continue
            req = self.scheduler.pop(self.tick_count)
            if req is None:
                return n
            offset, snap = (0, None)
            if self.prefix_cache is not None:
                offset, snap = self.prefix_cache.lookup(req.prompt)
            self.slots.admit(slot, unit_state=snap, position=offset)
            self._rid[slot] = req.rid
            self._req[req.rid] = req
            self._prompt_len[slot] = len(req.prompt)
            self._generated[req.rid] = []
            req.status = RequestStatus.PREFILL
            self.statuses[req.rid] = RequestStatus.PREFILL
            self.counters["admitted"] += 1
            n += 1
        return n

    def _pick_prefill(self):
        """Next slot still owing prompt tokens -> its next chunk.

        Round-robin from a persistent cursor, NOT always the lowest slot:
        one tick prefills one chunk, so a lowest-first scan would feed
        slot 0's long prompt to completion while later slots (admitted the
        same tick) wait at position 0 — head-of-line bias that inflates
        their TTFT. The cursor resumes after the last-served slot so
        concurrent prompts interleave chunk-for-chunk."""
        b = self.slots.max_slots
        for i in range(b):
            slot = (self._prefill_cursor + i) % b
            rid = self._rid[slot]
            if rid is None or self.slots.active[slot] or self.slots.eos[slot]:
                continue
            pos = int(self.slots.position[slot])
            plen = int(self._prompt_len[slot])
            if pos >= plen:
                continue
            self._prefill_cursor = (slot + 1) % b
            n = min(self.chunk, plen - pos)
            toks = self._req[rid].prompt[pos:pos + n]
            if n == self.chunk:
                chunk_tok = jnp.asarray(toks[None], jnp.int32)
                kv_mask = None
            elif self._pad_ragged:
                padded = np.zeros(self.chunk, np.int32)
                padded[:n] = toks
                chunk_tok = jnp.asarray(padded[None], jnp.int32)
                kv_mask = jnp.asarray(
                    (np.arange(self.chunk) < n)[None].astype(np.float32))
            else:
                chunk_tok = jnp.asarray(toks[None], jnp.int32)
                kv_mask = None
            return slot, chunk_tok, kv_mask, pos, n

    def _after_prefill(self, slot: int, nvalid: int, first_tok, ok: bool,
                       finished: List[FinishedRequest]) -> None:
        if not ok:
            self._quarantine_slot(
                slot, "SlotQuarantined: non-finite logits in prefill chunk "
                      f"(position {int(self.slots.position[slot])})",
                finished)
            return
        rid = self._rid[slot]
        req = self._req[rid]
        self.slots.position[slot] += nvalid
        self.prefill_tokens += int(nvalid)
        pos = int(self.slots.position[slot])
        plen = int(self._prompt_len[slot])
        if self.prefix_cache is not None and pos % self.chunk == 0:
            self.prefix_cache.insert(req.prompt, pos,
                                     self.slots.snapshot(slot))
        if pos < plen:
            return
        # prompt complete: the prefill logits' last valid row is token #1
        tok = int(np.asarray(first_tok)[0])
        self.slots.active[slot] = True
        self._last_token[slot] = tok
        if req.first_token_time is None:
            req.first_token_time = time.monotonic()
        req.status = RequestStatus.DECODE
        self.statuses[rid] = RequestStatus.DECODE
        self._emit(slot, rid, tok, finished)

    def _after_decode(self, live: np.ndarray, nxt: np.ndarray,
                      ok: np.ndarray,
                      finished: List[FinishedRequest]) -> None:
        for slot in np.nonzero(live)[0]:
            slot = int(slot)
            rid = self._rid[slot]
            if rid is None:
                continue            # freed earlier this tick
            if not ok[slot]:
                self._quarantine_slot(
                    slot, "SlotQuarantined: non-finite logits in decode "
                          f"step (position {int(self.slots.position[slot])})",
                    finished)
                continue
            tok = int(nxt[slot])
            self.slots.position[slot] += 1
            self._last_token[slot] = tok
            self.decode_tokens += 1
            self._emit(slot, rid, tok, finished)

    def _quarantine_slot(self, slot: int, error: str,
                         finished: List[FinishedRequest]) -> None:
        """Fail ONLY the poisoned request: drop its prefix-cache snapshots
        (they may carry the same non-finite state), re-initialize the slot
        from the fresh template, and keep every other slot serving."""
        rid = self._rid[slot]
        req = self._req[rid]
        toks = self._generated.pop(rid, [])
        if self.prefix_cache is not None:
            self.prefix_cache.invalidate(req.prompt)
        self._rid[slot] = None
        del self._req[rid]
        self.slots.quarantine(slot)
        self.counters["quarantined"] += 1
        self._finalize(req, toks, RequestStatus.FAILED, error, finished)

    def _release_abnormal(self, slot: int, status: RequestStatus,
                          error: str,
                          finished: List[FinishedRequest]) -> None:
        """Free a slot whose request terminated abnormally (deadline).
        Plain evict — the state is finite, just no longer wanted."""
        rid = self._rid[slot]
        req = self._req[rid]
        toks = self._generated.pop(rid, [])
        self._rid[slot] = None
        del self._req[rid]
        self.slots.evict(slot)
        self._finalize(req, toks, status, error, finished)

    def _deep_state_check(self, finished: List[FinishedRequest]) -> None:
        """REPRO_SERVE_CHECK_STATE=1: one jitted reduction over every
        floating decode-state leaf per tick -> per-slot finite flags.
        Catches moment-lane overflow BEFORE it surfaces in logits (and
        before a poisoned snapshot can enter the prefix cache)."""
        if self._finite_fn is None:
            self._finite_fn = jax.jit(functools.partial(
                _finite_per_slot, axes=self.slots.axes,
                n=self.slots.max_slots))
        ok = np.asarray(self._finite_fn(self.slots.state))
        for slot in np.nonzero(~ok)[0]:
            slot = int(slot)
            if self._rid[slot] is None:
                # free slot holding stale non-finite leaves: scrub quietly
                self.slots.quarantine(slot)
                continue
            self._quarantine_slot(
                slot, "SlotQuarantined: non-finite decode-state leaf "
                      "(REPRO_SERVE_CHECK_STATE deep check)", finished)

    def _emit(self, slot: int, rid: int, tok: int,
              finished: List[FinishedRequest]) -> None:
        req = self._req[rid]
        self._generated[rid].append(tok)
        if req.callback is not None:
            try:
                req.callback(rid, tok)
            except Exception as e:  # noqa: BLE001 — user code must not
                # kill the pool: fail only this request, keep serving
                toks = self._generated.pop(rid, [])
                self._rid[slot] = None
                del self._req[rid]
                self.slots.evict(slot)
                self._finalize(
                    req, toks, RequestStatus.FAILED,
                    f"on_token callback raised: {e!r}", finished)
                return
        hit_eos = req.eos_id is not None and tok == req.eos_id
        if hit_eos or len(self._generated[rid]) >= req.max_new_tokens:
            toks = self._generated.pop(rid)
            self._rid[slot] = None
            del self._req[rid]
            self.slots.evict(slot)
            self._finalize(req, toks, RequestStatus.FINISHED, None, finished)


def _tick(params, state, slot, chunk_tok, kv_mask, off, nvalid,
          tokens, positions, live, *, cfg, axes,
          do_prefill: bool, do_decode: bool):
    """One mixed launch: chunked prefill for one slot + a batched decode
    step for the live slots, on the shared pool state. Static
    do_prefill/do_decode flags -> at most 3 traces. Alongside the emitted
    tokens, each part returns a finite-logits flag (scalar for the prefill
    chunk, per-slot [B] for decode) — the cheap non-finite guard the
    quarantine path keys on."""
    first_tok = pre_ok = None
    if do_prefill:
        unit = read_slot(state, slot, axes)
        logits, unit = lm_prefill(params, chunk_tok, cfg, unit,
                                  offset=off, kv_mask=kv_mask)
        last_row = jax.lax.dynamic_index_in_dim(logits, nvalid - 1, axis=1,
                                                keepdims=False)
        first_tok = jnp.argmax(last_row, axis=-1).astype(jnp.int32)
        pre_ok = jnp.isfinite(last_row).all()
        state = write_slot(state, unit, slot, axes)
    nxt = dec_ok = None
    if do_decode:
        logits, state = lm_decode_step(params, state, tokens, cfg,
                                       position=positions, live=live)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        dec_ok = jnp.isfinite(logits).all(axis=-1)
        nxt = jnp.where(live, nxt, tokens)
    return state, first_tok, pre_ok, nxt, dec_ok


def _finite_per_slot(state, *, axes, n):
    """[n] bool: slot i's floating leaves are all finite. Integer lanes
    (cursors, token ids) are skipped — they cannot hold NaN/Inf."""
    ok = jnp.ones((n,), bool)
    for leaf, ax in zip(jax.tree.leaves(state), jax.tree.leaves(axes)):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        flat = jnp.moveaxis(leaf, ax, 0).reshape(n, -1)
        ok = ok & jnp.isfinite(flat).all(axis=1)
    return ok
