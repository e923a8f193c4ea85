"""Continuous batching: admission, per-step join/evict, preemption (Orca).

Static batching serves a batch until its LONGEST member finishes — every
other slot idles at the tail, and new arrivals wait for the whole batch.
Continuous batching (Yu et al., OSDI 2022) rebuilds the batch every
iteration instead: finished sequences evict and free their cache blocks at
the step they finish, queued requests join (prefill) the moment a slot and
blocks are available, and the decode step always runs the full fixed-shape
batch with inactive slots masked (so the compiled program never changes).

Block-pool pressure resolves by **preempting the longest active sequence**
(free all its blocks, push the request back to the queue front): longest
frees the most blocks per eviction, and its recompute-prefill is the one
most amortized by batching.  Preemption is recompute-style (vLLM's default):
the re-prefilled prefix is ``prompt + tokens generated so far``, and because
sampling keys derive from ``(request id, position)`` only
(:mod:`theanompi_tpu.serving.engine`), the replayed sequence continues
exactly where it left off — greedy or sampled.

The decode loop is a pipeline one step deep (ISSUE 32): ``step`` launches
decode step n and only then reads step n-1's tokens, so the device always
has its next program queued and the host's own work hides under a device
step.  What a launch needs is known by counting — a continuing slot's
input token is the previous step's output, taken on the device; its length
is the old one plus one; a request that ends by ``max_new_tokens`` gives
its slot up when its last step is launched.  Tokens therefore reach
``req.generated`` one step after they are computed; a request is returned
``done`` by the step that reads its last token; a request that ends on a
stop token is found one step late and has then run one step for nothing
(``n_overrun_slots``).  Whatever needs token values — preemption, a
deadline's expiry, ``preempt_all``, ``expire_all_active``, an injected
fault — first reads the launch still out (``_drain``), and the next launch
starts the pipeline anew.

**Block diffusion** (an engine whose model has a ``block_len`` B): a step
is one pass over every slot's block of B positions, and a pass commits
several positions, out of order (the engine's module docstring).  The
schedule is static, so the host knows without reading a token which pass
each slot is in: a block opened with ``m`` positions masked takes ``ceil(m
/ c)`` denoising passes (``c`` = the engine's ``commits_per_pass``), then one
pass over its final tokens that writes its K/V, after which ``L`` — the
tokens cached, ``_lengths`` — advances by B and the next block opens with
all B masked.  The prompt's whole blocks are prefilled; its tail opens the
first block committed.  ``req.generated`` grows only by tokens whose
position and every position before it are committed, in position order,
to exactly ``max_new_tokens``: the positions of the last block past that
are computed, dropped, and kept in ``req.committed`` (position -> (token,
pass, confidence)) with every other committed position, for a check that
needs the state each token was chosen in.  The schedule and the rows'
layout are the engine's (``block_row``, ``advance_block_row``,
``block_result``).  The last block needs no K/V pass: the
request gives its slot up when that block's last denoising pass is
launched.

Request lifecycle (ISSUE 14): every request ends in exactly one typed
terminal state —

- ``done``     — generation completed (max tokens or EOS);
- ``expired``  — a per-request deadline (``ttft_deadline_ms`` before the
  first token, ``total_deadline_ms`` overall) passed; checked at the queue
  front BEFORE a prefill is burned (a preempted-and-requeued request past
  its deadline expires immediately) and between scheduler steps for both
  queued and active requests;
- ``shed``     — refused at admission: load shedding (the queue's backlog
  at the recently measured token rate cannot meet the request's deadline)
  or a graceful drain in progress;
- ``failed``   — the livelock guard: a request that can never fit the KV
  pool is refused with a typed terminal state instead of crashing the
  server or preempting forever.

All telemetry flows through the names registered in
:mod:`theanompi_tpu.telemetry.metrics` (``SERVE_*``); latency percentiles
are also tracked host-side so the SERVE report works with telemetry off,
and every step is a ``serve.step`` span of the process's ring
(:mod:`theanompi_tpu.telemetry.spans`) with the admission pass and the
engine's calls beneath it.
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from dataclasses import field

import numpy as np

from theanompi_tpu.resilience.faults import FaultInjected, FaultPlan
from theanompi_tpu.serving.kv_cache import BlockPool, PagedKVCache, blocks_for
from theanompi_tpu.serving.lifecycle import DRAIN_OP, read_jsonl_since
from theanompi_tpu.serving.prefix_cache import PrefixCache
from theanompi_tpu.telemetry import spans
from theanompi_tpu.telemetry.metrics import (  # registered names (ISSUE 6)
    SERVE_COUNTERS,
    SERVE_HISTOGRAMS,
    SERVE_INSTANTS,
    SERVE_LIFECYCLE_COUNTERS,
    SERVE_LIFECYCLE_INSTANTS,
    SERVE_PREFIX_COUNTERS,
    SERVE_PREFIX_INSTANTS,
    SERVE_STEP_SPANS,
)

_SPAN_STEP, _SPAN_ADMIT = SERVE_STEP_SPANS
_INST_ADMIT, _INST_PREEMPT, _INST_FINISH = SERVE_INSTANTS
_HIST_TOKEN_MS, _HIST_TTFT_MS = SERVE_HISTOGRAMS
_CNT_TOKENS, _CNT_PREEMPTIONS, _CNT_REQUESTS = SERVE_COUNTERS
_INST_EXPIRE, _INST_SHED, _INST_FAIL, _INST_DRAIN = SERVE_LIFECYCLE_INSTANTS
_CNT_EXPIRED, _CNT_SHED, _CNT_FAILED = SERVE_LIFECYCLE_COUNTERS
_CNT_PREFIX_HIT, _CNT_PREFIX_TOKENS = SERVE_PREFIX_COUNTERS
(_INST_PREFIX_INVALIDATE,) = SERVE_PREFIX_INSTANTS

#: every request ends in exactly one of these (ISSUE 14)
TERMINAL_STATES = ("done", "expired", "shed", "failed")


@dataclasses.dataclass
class Request:
    """One generation request.  ``arrival_s`` is the open-loop arrival
    offset (seconds from traffic start) — the driver submits the request
    when the clock passes it, regardless of server state (open loop).
    Deadlines are milliseconds from ``t_submit`` (None = no deadline)."""

    rid: int
    prompt: list[int]
    max_new_tokens: int
    temperature: float = 0.0
    arrival_s: float = 0.0
    ttft_deadline_ms: float | None = None
    total_deadline_ms: float | None = None
    # -- filled in by the scheduler -----------------------------------------
    state: str = "queued"       # queued | active | done|expired|shed|failed
    reason: str | None = None   # why a non-done terminal state was reached
    generated: list[int] = field(default_factory=list)
    n_preemptions: int = 0
    t_submit: float | None = None
    t_first_token: float | None = None
    t_last_token: float | None = None  # stamp of the newest token
    t_done: float | None = None
    #: block diffusion: every position a pass committed -> (token, the
    #: pass's index in its block, the token's log-probability in that pass;
    #: -1 and None: committed before the block's first pass, the tail of
    #: the prompt the block was prefilled from)
    committed: dict = field(default_factory=dict)

    @property
    def terminal(self) -> bool:
        return self.state in TERMINAL_STATES


class Scheduler:
    """Continuous-batching scheduler over one :class:`InferenceEngine`.

    ``shed=True`` enables admission-time load shedding for requests that
    carry a deadline; ``fault_plan`` arms the ``serve:raise``/
    ``serve:stall`` chaos sites at decode-step ordinals (constructor-only
    here — the CLI threads the ``THEANOMPI_FAULT_PLAN`` env through);
    ``prefix_cache=True`` turns on the radix prefix cache over the block
    pool (ISSUE 17): admissions reuse cached full-block prompt-prefix K/V
    via partial prefill, finished/evicted sequences offer their full
    blocks back, and the whole tree invalidates when the engine's
    ``params_version`` moves (live rollout).  Token streams are unchanged
    by the cache — bit-equal to ``prefix_cache=False`` — only the prefill
    work is.  A model that keeps per-slot recurrent state, or has no
    partial prefill (``HybridLM``), is refused with the reason.

    The scheduler sets ``engine.run_ahead_for`` around its decode calls:
    they return the PREVIOUS launch's tokens (module docstring).  An engine
    that reads its own launch all the same (a test double) is served as it
    answers: tokens a call returns with nothing unread before it are that
    call's own.
    """

    def __init__(self, engine, telemetry=None, eos_token: int | None = None,
                 shed: bool = False,
                 fault_plan: FaultPlan | None = None,
                 prefix_cache: bool = False):
        self.engine = engine
        self.telemetry = telemetry
        self.eos_token = eos_token
        self.shed = shed
        self.fault_plan = fault_plan
        self.pool = BlockPool(engine.num_blocks)
        #: the engine's model keeps per-slot recurrent state
        self._stateful = bool(getattr(engine, "stateful", False))
        if prefix_cache and self._stateful:
            # the cache shares K/V BLOCKS between sequences; a recurrent
            # layer's state after the shared prefix lives in no block, so a
            # hit would resume from a state that was never computed
            raise ValueError(
                "prefix_cache=True with a model that keeps per-slot "
                "recurrent state (cache_spec()['state']): the prefix cache "
                "shares K/V blocks and holds no state snapshot to resume "
                "from; serve this model without it")
        if prefix_cache and not getattr(engine, "partial_prefill", True):
            raise ValueError(
                "prefix_cache=True with a model that has no partial prefill "
                "(apply_prefill_partial): a hit prefills only the suffix "
                "over the cached blocks, which this model cannot; serve it "
                "without the prefix cache")
        # ISSUE 17: radix prefix cache over the pool — OFF by default (the
        # cache-OFF token streams are the bit-equality reference)
        self.prefix_cache = (PrefixCache(self.pool, engine.block_size)
                             if prefix_cache else None)
        self.n_prefix_hits = 0
        self.n_prefix_lookups = 0
        self.prefix_tokens_saved = 0
        self.queue: deque[Request] = deque()
        b, nb = engine.max_batch, engine.max_blocks_per_seq
        self.slots: list[Request | None] = [None] * b
        self._blocks: list[list[int]] = [[] for _ in range(b)]
        self._tables = np.zeros((b, nb), np.int32)
        self._lengths = np.zeros((b,), np.int32)
        #: block diffusion's positions a pass (None: one token a step); a
        #: slot's row of ``_tokens`` is then the engine's (``block_row``)
        self.block = getattr(engine, "block_len", None)
        #: what a read launch's tokens go through: one a slot, or a pass's
        #: rows (block diffusion)
        self._account_launch = (self._account if self.block is None
                                else self._account_block)
        self._tokens = np.zeros(
            (b,) if self.block is None else (b, 2 + self.block), np.int32)
        self._temps = np.zeros((b,), np.float32)
        self._rids = np.zeros((b,), np.int32)
        #: the launch whose tokens are still on the device: slot -> the
        #: request it ran for (None: the host holds every token)
        self._unread: dict[int, Request] | None = None
        #: requests a drain outside ``step`` found done: the next step's
        self._early: list[Request] = []
        self.n_steps = 0
        #: launches that went out with the one before them unread
        self.n_ran_ahead = 0
        #: slot-steps run for a request that had ended on a stop token
        self.n_overrun_slots = 0
        self.token_ms: list[float] = []
        self.step_ms: list[float] = []  # one entry per decode step
        self.ttft_ms: list[float] = []
        self.n_preemptions = 0
        self.n_done = 0
        self.n_expired = 0
        self.n_shed = 0
        self.n_failed = 0
        self.draining = False
        # recent decode throughput: (host time, tokens emitted that step),
        # the load-shedding estimator's evidence window
        self._rate: deque[tuple[float, int]] = deque(maxlen=64)

    # -- introspection -------------------------------------------------------
    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.slots)

    @property
    def idle(self) -> bool:
        """Nothing queued, active, launched and unread, or waiting to be
        returned."""
        return (self.n_active == 0 and not self.queue
                and self._unread is None and not self._early)

    def recent_token_rate(self) -> float | None:
        """Decoded tokens/sec over the recent window; None until at least
        4 decode steps spanning a measurable interval exist (shedding
        never fires on guesswork)."""
        if len(self._rate) < 4:
            return None
        span = self._rate[-1][0] - self._rate[0][0]
        if span <= 1e-6:
            return None
        return sum(n for _, n in self._rate) / span

    def _backlog_tokens(self) -> int:
        """Tokens the server still owes the queue + active slots."""
        owed = 0
        for req in list(self.queue):
            owed += max(req.max_new_tokens - len(req.generated), 0)
        for req in self.slots:
            if req is not None:
                owed += max(req.max_new_tokens - len(req.generated), 0)
        return owed

    def snapshot(self) -> dict:
        """Live load for the router's balancer (ISSUE 19 satellite):
        backlog, recent rate, terminal tallies, prefix-hit rate.  Plain
        host ints/floats only — this dict goes straight through
        :func:`theanompi_tpu.serving.lifecycle.publish_snapshot`."""
        rate = self.recent_token_rate()
        return {
            # wall (not perf_counter) so the ROUTER side can judge
            # freshness across processes
            "updated": time.time(),  # lint: wall-ok — cross-process stamp
            "backlog_tokens": self._backlog_tokens(),
            "queue_len": len(self.queue),
            "n_active": self.n_active,
            "token_rate": round(rate, 3) if rate is not None else None,
            "decode_steps": self.n_steps,
            "n_done": self.n_done,
            "n_expired": self.n_expired,
            "n_shed": self.n_shed,
            "n_failed": self.n_failed,
            "draining": self.draining,
            "prefix_hit_rate": (
                round(self.n_prefix_hits / self.n_prefix_lookups, 4)
                if self.n_prefix_lookups else 0.0),
        }

    # -- submission ----------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Queue ``req``; -> True when admitted, False when it was SHED
        (a typed terminal state — load shedding or a drain in progress).
        Structurally invalid requests still raise ValueError."""
        total = len(req.prompt) + req.max_new_tokens
        if not req.prompt:
            raise ValueError(f"request {req.rid}: empty prompt")
        if self.block is not None:
            # the last block is computed whole
            total = -(-total // self.block) * self.block
            mask = self.engine.mask_id
            if req.temperature > 0 or mask in req.prompt:
                raise ValueError(
                    f"request {req.rid}: block diffusion commits greedily "
                    f"and reads the mask token {mask} as a position "
                    f"still to fill; no temperature, no mask in a prompt")
        if total > self.engine.max_context:
            raise ValueError(
                f"request {req.rid}: prompt+max_new_tokens = {total} > "
                f"max context {self.engine.max_context}")
        if blocks_for(total, self.engine.block_size) > self.pool.num_blocks - 1:
            raise ValueError(
                f"request {req.rid}: needs "
                f"{blocks_for(total, self.engine.block_size)} blocks, pool "
                f"has {self.pool.num_blocks - 1} — num_blocks too small for "
                f"even one sequence")
        req.t_submit = time.perf_counter()
        if self.draining:
            self.mark_shed(req, "draining")
            return False
        if self.shed:
            est_ms = self._shed_estimate_ms(req)
            if est_ms is not None:
                self.mark_shed(
                    req, f"backlog needs ~{est_ms:.0f}ms at the recent "
                    f"token rate, past the deadline", est_wait_ms=est_ms)
                return False
        req.state = "queued"
        self.queue.append(req)
        return True

    def _shed_estimate_ms(self, req: Request) -> float | None:
        """Estimated wait (ms) when it provably exceeds the request's
        deadline budget, else None (admit).  Deadline-less requests are
        never shed; neither is anything before the rate is measurable."""
        budget = min((d for d in (req.ttft_deadline_ms,
                                  req.total_deadline_ms) if d is not None),
                     default=None)
        if budget is None:
            return None
        rate = self.recent_token_rate()
        if rate is None or rate <= 0:
            return None
        est_ms = self._backlog_tokens() / rate * 1e3
        return est_ms if est_ms > budget else None

    # -- internals -----------------------------------------------------------
    def _emit(self, name: str, **fields) -> None:
        if self.telemetry is not None:
            self.telemetry.instant(name, **fields)

    def _clear_slot(self, slot: int) -> None:
        self.slots[slot] = None
        self._blocks[slot] = []
        self._tables[slot, :] = PagedKVCache.NULL_BLOCK
        self._lengths[slot] = 0
        self._tokens[slot] = 0
        self._temps[slot] = 0.0
        self._rids[slot] = 0

    def _evict(self, slot: int) -> Request:
        """Release a slot's blocks.  With the prefix cache on, the FULL
        blocks are offered back to the radix tree first (their K/V is
        complete and valid — a multi-turn follow-up or this request's own
        recompute-prefill hits them); the partial tail block stays
        exclusive and frees normally (copy-on-write by construction:
        shared blocks are never written again)."""
        req = self.slots[slot]
        blocks = self._blocks[slot]
        if self.prefix_cache is not None and blocks:
            cached = int(self._lengths[slot])  # tokens with K/V in blocks
            n_full = cached // self.engine.block_size
            tokens = (req.prompt + req.generated)[
                :n_full * self.engine.block_size]
            self.prefix_cache.insert(tokens, blocks[:n_full])
            self.pool.free(blocks[n_full:])
        else:
            self.pool.free(blocks)
        self._clear_slot(slot)
        return req

    def _finish(self, slot: int, finished: list[Request]) -> None:
        self._complete(self._evict(slot), finished)

    def _complete(self, req: Request, finished: list[Request]) -> None:
        """Typed terminal ``done``; the request's slot is given up already."""
        req.state = "done"
        req.t_done = time.perf_counter()
        self.n_done += 1
        if self.telemetry is not None:
            self.telemetry.count(_CNT_REQUESTS)
        self._emit(_INST_FINISH, request=req.rid,
                   generated=len(req.generated))
        finished.append(req)

    def _expire(self, req: Request, which: str, where: str,
                finished: list[Request]) -> None:
        """Typed terminal: a deadline passed.  The caller already removed
        ``req`` from the queue or evicted its slot."""
        req.state = "expired"
        req.reason = f"{which} deadline exceeded ({where})"
        req.t_done = time.perf_counter()
        self.n_expired += 1
        if self.telemetry is not None:
            self.telemetry.count(_CNT_EXPIRED)
        self._emit(_INST_EXPIRE, request=req.rid, which=which, where=where)
        finished.append(req)

    def mark_shed(self, req: Request, reason: str,
                  est_wait_ms: float | None = None) -> None:
        """Typed terminal: refused at admission (shedding or drain).  The
        request was never queued — no blocks, no prefill, no tokens."""
        now = time.perf_counter()
        if req.t_submit is None:
            req.t_submit = now
        req.state = "shed"
        req.reason = reason
        req.t_done = now
        self.n_shed += 1
        if self.telemetry is not None:
            self.telemetry.count(_CNT_SHED)
        fields = {"request": req.rid, "reason": reason}
        if est_wait_ms is not None:
            fields["est_wait_ms"] = round(est_wait_ms, 1)
        self._emit(_INST_SHED, **fields)

    def _fail(self, req: Request, need: int,
              finished: list[Request]) -> None:
        """Typed terminal: the livelock guard.  A request whose prefix can
        never fit the pool is refused — NOT crashed on, NOT preempted
        around forever (the pre-ISSUE-14 behavior raised RuntimeError and
        took the whole server down with it)."""
        req.state = "failed"
        req.reason = (f"needs {need} KV blocks, pool has "
                      f"{self.pool.num_blocks - 1} — can never be admitted")
        req.t_done = time.perf_counter()
        self.n_failed += 1
        if self.telemetry is not None:
            self.telemetry.count(_CNT_FAILED)
        self._emit(_INST_FAIL, request=req.rid, need_blocks=need,
                   pool_blocks=self.pool.num_blocks - 1)
        finished.append(req)

    def _deadline_overrun(self, req: Request,
                          now: float | None = None) -> str | None:
        """Which deadline ``req`` has blown ("ttft" | "total"), or None."""
        if req.t_submit is None:
            return None
        now = time.perf_counter() if now is None else now
        elapsed_ms = (now - req.t_submit) * 1e3
        if (req.total_deadline_ms is not None
                and elapsed_ms > req.total_deadline_ms):
            return "total"
        if (req.t_first_token is None and req.ttft_deadline_ms is not None
                and elapsed_ms > req.ttft_deadline_ms):
            return "ttft"
        return None

    def _sweep_deadlines(self, finished: list[Request]) -> None:
        """Between-steps deadline enforcement: expire overrun queued AND
        active requests (active ones free their blocks — an expired
        request must stop consuming decode slots immediately)."""
        now = time.perf_counter()
        if any(req is not None and self._deadline_overrun(req, now)
               for req in self.slots):
            self._drain(finished)  # an expired request keeps all its tokens
        kept: deque[Request] = deque()
        while self.queue:
            req = self.queue.popleft()
            which = self._deadline_overrun(req, now)
            if which:
                self._expire(req, which, "queued", finished)
            else:
                kept.append(req)
        self.queue = kept
        for slot in range(self.engine.max_batch):
            req = self.slots[slot]
            if req is None:
                continue
            which = self._deadline_overrun(req, now)
            if which:
                self._evict(slot)
                self._expire(req, which, "active", finished)

    def _preempt(self, slot: int) -> None:
        req = self._evict(slot)
        req.n_preemptions += 1
        self.n_preemptions += 1
        req.state = "queued"
        if self.telemetry is not None:
            self.telemetry.count(_CNT_PREEMPTIONS)
        self._emit(_INST_PREEMPT, request=req.rid,
                   held_tokens=len(req.prompt) + len(req.generated))
        self.queue.appendleft(req)  # rejoin first: it already holds work

    def preempt_all(self) -> int:
        """Evict every active request back to the queue front (recompute
        preemption) — the rollout watcher's weight-swap barrier: the KV
        cache was computed under the OLD weights, so active sequences
        re-prefill under the new ones.  The launch still out is read first
        (a request it finishes is returned by the next ``step``).
        -> number preempted."""
        self._drain(self._early)
        n = 0
        for slot in range(self.engine.max_batch):
            if self.slots[slot] is not None:
                self._preempt(slot)
                n += 1
        return n

    def _alloc(self, n: int) -> list[int] | None:
        """Pool allocation with prefix-cache pressure relief: when the
        free list can't cover ``n``, ask the radix tree to evict LRU
        zero-ref leaves before giving up (cached-but-unreferenced blocks
        are reclaimable capacity, not leaks)."""
        row = self.pool.alloc(n)
        if row is None and self.prefix_cache is not None:
            self.prefix_cache.evict(n - self.pool.free_blocks)
            row = self.pool.alloc(n)
        return row

    def _admit(self, finished: list[Request]) -> None:
        """Prefill queued requests into free slots while blocks last."""
        if self.prefix_cache is not None:
            # cached K/V is only valid under the weights that computed it:
            # a rollout swap/rollback bumped the engine's params_version,
            # so the whole tree drops BEFORE any lookup (ISSUE 17)
            version = self.engine.params_version
            if self.prefix_cache.params_version != version:
                dropped = self.prefix_cache.n_nodes
                if self.prefix_cache.check_version(version):
                    self._emit(_INST_PREFIX_INVALIDATE,
                               params_version=version, dropped=dropped)
        while self.queue:
            req = self.queue[0]
            # deadline check BEFORE any prefill work (ISSUE 14 satellite):
            # preemption re-queues to the FRONT unconditionally, so a
            # requeued request past its deadline must expire here, not
            # burn a recompute-prefill first
            which = self._deadline_overrun(req)
            if which:
                self.queue.popleft()
                self._expire(req, which, "queued", finished)
                continue
            try:
                slot = self.slots.index(None)
            except ValueError:
                return
            prefix = req.prompt + req.generated
            need = blocks_for(len(prefix), self.engine.block_size)
            if need > self.pool.num_blocks - 1:
                # livelock guard: this prefix can NEVER fit, even into an
                # empty pool — refuse it and keep serving everyone else
                self.queue.popleft()
                self._fail(req, need, finished)
                continue
            matched: list[int] = []
            prefix_len = 0
            if self.prefix_cache is not None:
                self.n_prefix_lookups += 1
                matched = self.prefix_cache.match(prefix)
                prefix_len = len(matched) * self.engine.block_size
            new = self._alloc(need - len(matched))
            if new is None:
                if matched:
                    # release the acquired prefix refs: admission failed,
                    # and holding them would wedge the eviction pressure
                    # valve (the tree's own refs keep the entries alive)
                    self.pool.free(matched)
                if self.n_active == 0 and (self.prefix_cache is None
                                           or self.prefix_cache.n_nodes
                                           == 0):
                    # an empty server (and a drained cache) that still
                    # can't allocate means the pool leaked: refuse THIS
                    # request (typed terminal) instead of raising and
                    # killing every other request
                    self.queue.popleft()
                    self._fail(req, need, finished)
                    continue
                return
            row = matched + new
            self.queue.popleft()
            # prefill returns a host int — already materialized, so the
            # engine's serve.prefill span measures execution, not dispatch
            # the slot's recurrent state (a model that has one) is replaced
            # whole by this prefill: a reused slot, and a preempted request
            # recomputed over prompt + generated, both start clean
            own = {"slot": slot} if self._stateful else {}
            tok, _ = self.engine.prefill(row, prefix, req.temperature,
                                         req.rid, prefix_len=prefix_len,
                                         **own)
            if self.block is not None:
                self._open_first_block(slot, req, row, need, prefix, tok)
                continue
            if prefix_len:
                # exact accounting: tokens_saved is the sum of matched-
                # prefix lengths — prefill K/V the engine did not recompute
                self.n_prefix_hits += 1
                self.prefix_tokens_saved += prefix_len
                if self.telemetry is not None:
                    self.telemetry.count(_CNT_PREFIX_HIT)
                    self.telemetry.count(_CNT_PREFIX_TOKENS, prefix_len)
            now = time.perf_counter()
            if req.t_first_token is None:
                req.t_first_token = now
                ttft = (now - req.t_submit) * 1e3
                self.ttft_ms.append(ttft)
                if self.telemetry is not None:
                    self.telemetry.observe(_HIST_TTFT_MS, ttft)
            # a prefill's token opens the request's series of token gaps
            # (again after a preemption: the recompute is no gap)
            req.t_last_token = now
            req.generated.append(tok)
            if self.telemetry is not None:
                self.telemetry.count(_CNT_TOKENS)
            self._emit(_INST_ADMIT, request=req.rid, slot=slot,
                       prefix=len(prefix), blocks=need,
                       prefix_cached=prefix_len,
                       resumed=req.n_preemptions > 0)
            req.state = "active"
            self.slots[slot] = req
            self._blocks[slot] = row
            self._tables[slot, :] = PagedKVCache.NULL_BLOCK
            self._tables[slot, :need] = row
            self._lengths[slot] = len(prefix)
            self._tokens[slot] = tok
            self._temps[slot] = req.temperature
            self._rids[slot] = req.rid
            if self._done(req):
                self._finish(slot, finished)

    def _open_first_block(self, slot: int, req: Request, row, need: int,
                          prefix: list[int], tail: list[int]) -> None:
        """Block diffusion's half of an admission: the prompt's whole blocks
        are cached; its ``tail`` opens the slot's first block, the rest of
        the block masked.  No token is produced here."""
        n = len(prefix) - len(tail)
        # a request admitted again after a preemption: what the passes of
        # its unfinished block had committed out of order is computed anew,
        # and the generated tokens in the tail are now committed before it
        for pos in [p for p in req.committed if p >= n]:
            del req.committed[pos]
        for pos in range(max(n, len(req.prompt)), len(prefix)):
            req.committed[pos] = (prefix[pos], -1, None)
        self._emit(_INST_ADMIT, request=req.rid, slot=slot,
                   prefix=len(prefix), blocks=need, prefix_cached=0,
                   resumed=req.n_preemptions > 0)
        req.state = "active"
        self.slots[slot] = req
        self._blocks[slot] = row
        self._tables[slot, :] = PagedKVCache.NULL_BLOCK
        self._tables[slot, :need] = row
        self._lengths[slot] = n
        self._tokens[slot] = self.engine.block_row(tail)
        self._temps[slot] = req.temperature
        self._rids[slot] = req.rid

    def _advance_block(self, slot: int) -> bool:
        """The host's half of a launched pass: the slot's next pass,
        counted (module docstring).  -> whether the launched pass was the
        request's last: the last denoising pass of the block that holds its
        ``max_new_tokens``-th token."""
        row, req = self._tokens[slot], self.slots[slot]
        if self.engine.advance_block_row(row):  # it wrote the block's K/V
            self._lengths[slot] += self.block
            return False
        end = len(req.prompt) + req.max_new_tokens
        return row[1] == 0 and self._lengths[slot] + self.block >= end

    def _account_block(self, launch: dict[int, Request], out, now: float,
                       finished: list[Request]) -> int:
        """:meth:`_account` for block passes: ``out`` a row a slot (the
        engine's ``block_result``).  Each position the pass committed is
        kept in ``req.committed`` with the pass and its confidence;
        ``req.generated`` takes every committed position in order from
        where it stands, up to ``max_new_tokens``."""
        ahead = self._unread or {}
        b, n = self.block, 0
        for slot, req in launch.items():
            if req.state != "active":
                continue  # ended on a stop token a pass ago: an overrun
            at, pas, toks, states, conf = self.engine.block_result(out[slot])
            for i in np.flatnonzero(states == 2):
                req.committed[at + int(i)] = (int(toks[i]), pas,
                                              float(conf[i]))
            pos = len(req.prompt) + len(req.generated)
            end = len(req.prompt) + req.max_new_tokens
            new = []
            while pos < end and pos in req.committed:
                new.append(req.committed[pos][0])
                pos += 1
            if self.eos_token is not None and self.eos_token in new:
                new = new[:new.index(self.eos_token) + 1]
            holds = self.slots[slot] is req
            if holds and not ahead and self._tokens[slot, 2] < 0:
                # nothing is out to carry the block: the host hands it over
                self._tokens[slot, 2:] = np.where(
                    states == 0, self.engine.mask_id, toks)
            if new:
                self._stamp(req, new, now)
                n += len(new)
            # done once its last block is whole (the positions past
            # ``max_new_tokens`` recorded too), or on a stop token
            if not ((pos == end and all(
                    p in req.committed for p in range(end, -(-end // b) * b)))
                    or (new and new[-1] == self.eos_token)):
                continue
            if ahead.get(slot) is req:
                self.n_overrun_slots += 1
                self.engine.overrun()
            if holds:
                self._finish(slot, finished)
            else:  # its last pass was launched: the slot went then
                self._complete(req, finished)
        return n

    def _stamp(self, req: Request, new: list[int], now: float) -> None:
        """Tokens that reached the host together: appended, stamped, the
        first with the wait since the request's previous token (a stall for
        another request's prefill and the scheduler's own time are inside
        it, as the request's user feels them) and the rest with none (they
        arrived with it)."""
        if req.t_first_token is None:
            req.t_first_token = now
            self.ttft_ms.append((now - req.t_submit) * 1e3)
            if self.telemetry is not None:
                self.telemetry.observe(_HIST_TTFT_MS, self.ttft_ms[-1])
        gaps = [(now - (req.t_last_token or now)) * 1e3] + [0.0] * (
            len(new) - 1)
        req.t_last_token = now
        req.generated.extend(new)
        self.token_ms.extend(gaps)
        if self.telemetry is not None:
            for gap_ms in gaps:
                self.telemetry.count(_CNT_TOKENS)
                self.telemetry.observe(_HIST_TOKEN_MS, gap_ms)

    def _done(self, req: Request) -> bool:
        if len(req.generated) >= req.max_new_tokens:
            return True
        return (self.eos_token is not None
                and req.generated
                and req.generated[-1] == self.eos_token)

    def _ensure_capacity(self, finished: list[Request]) -> None:
        """Every active slot whose NEXT token starts a new cache block must
        get one before the decode step; exhaustion reads the launch still
        out (the victim is re-queued with every token it has), preempts the
        longest active sequence and retries."""
        for slot in range(self.engine.max_batch):
            if self.slots[slot] is None:
                continue
            if self.block is not None:  # the pass writes [L, L + B)
                if blocks_for(int(self._lengths[slot]) + self.block,
                              self.engine.block_size) <= len(self._blocks[slot]):
                    continue
            elif self._lengths[slot] % self.engine.block_size != 0:
                continue
            while self.slots[slot] is not None:
                got = self.pool.alloc(1)
                if got is not None:
                    n_used = blocks_for(int(self._lengths[slot]),
                                        self.engine.block_size)
                    self._blocks[slot].extend(got)
                    self._tables[slot, n_used] = got[0]
                    break
                if self._unread is not None:
                    self._drain(finished)
                    continue
                victim = max(
                    (s for s in range(self.engine.max_batch)
                     if self.slots[s] is not None),
                    key=lambda s: int(self._lengths[s]))
                self._preempt(victim)

    def _fire_faults(self, finished: list[Request]) -> None:
        """serve:raise / serve:stall chaos sites, indexed by decode-step
        ordinal.  Action-narrowed fires: the rollout watcher counts a
        DIFFERENT ordinal (candidates) for serve:rollout_corrupt.  A fault
        that fires finds the host holding every token launched so far."""
        if self.fault_plan is None:
            return
        if self.fault_plan.fire("serve", self.n_steps, "stall"):
            self._drain(finished)
            time.sleep(float(os.environ.get("THEANOMPI_SERVE_STALL_S",
                                            "2.0")))
        if self.fault_plan.fire("serve", self.n_steps, "raise"):
            self._drain(finished)
            raise FaultInjected(
                f"serve:raise at decode step {self.n_steps}")

    def _account(self, launch: dict[int, Request], nxt, now: float,
                 finished: list[Request]) -> int:
        """One launch's tokens have reached the host: append and stamp
        them, finish what they end; -> tokens appended.  ``self._unread``
        is the launch after it, where one is out."""
        ahead = self._unread or {}
        n = 0
        for slot, req in launch.items():
            if req.state != "active":
                continue  # ended on a stop token a step ago: an overrun
            tok = int(nxt[slot])
            self._stamp(req, [tok], now)
            n += 1
            holds = self.slots[slot] is req
            if holds and not ahead:
                self._tokens[slot] = tok  # the next launch takes it from here
            if not self._done(req):
                continue
            if ahead.get(slot) is req:
                # found a step late: the launch that is out runs this slot
                # once more, into a block the request still owned when it
                # went; its token will be dropped
                self.n_overrun_slots += 1
                self.engine.overrun()
            if holds:
                self._finish(slot, finished)
            else:  # ended by length: the slot went when this was launched
                self._complete(req, finished)
        return n

    def _drain(self, finished: list[Request]) -> None:
        """Read the launch still out, if one is: the host then holds every
        token, as after a step of a loop that never ran ahead."""
        if self._unread is None:
            return
        launch, self._unread = self._unread, None
        nxt = self.engine.collect()
        self._account_launch(launch, nxt, time.perf_counter(), finished)

    def step(self) -> list[Request]:
        """One scheduler iteration: enforce deadlines, admit, secure
        blocks, LAUNCH decode step n over the fixed batch, then read and
        account step n-1's tokens; -> every request that reached a TERMINAL
        state this step (done + expired + failed — run loops key on
        ``req.state``).

        Tokens reach ``req.generated`` one step after the device computes
        them, and a request is returned ``done`` by the step that reads its
        last token (its slot and blocks went a step earlier, when that
        token's step was launched).  A stop token is seen one step late:
        the request has then run one more step, whose token is dropped
        (``n_overrun_slots``).  Preemption, a deadline's expiry and an
        injected fault first read the launch that is out (``_drain``); so
        do ``preempt_all`` and ``expire_all_active``."""
        with spans.span(_SPAN_STEP, step=self.n_steps) as step:
            finished, self._early = self._early, []
            self._sweep_deadlines(finished)
            with spans.span(_SPAN_ADMIT, queued=len(self.queue)):
                self._admit(finished)
            if self.n_active:
                self._ensure_capacity(finished)
            launch = {s: r for s, r in enumerate(self.slots) if r is not None}
            if not launch:  # nothing to run (or pressure preempted it all)
                self._drain(finished)
                return finished
            self._fire_faults(finished)
            step.tag(batch=len(launch))
            self.n_ran_ahead += self._unread is not None
            t0 = time.perf_counter()
            # host arrays, lengths not yet advanced; what comes back is the
            # PREVIOUS launch's tokens: this one's stay on the device
            self.engine.run_ahead_for = self
            try:
                nxt, _ = self.engine.decode(self._tables, self._lengths,
                                            self._tokens, self._temps,
                                            self._rids)
            finally:
                self.engine.run_ahead_for = None
            t1 = time.perf_counter()
            self.step_ms.append((t1 - t0) * 1e3)
            self.n_steps += 1
            last = set()
            for slot in launch:
                if self.block is not None:
                    if self._advance_block(slot):
                        last.add(slot)
                    continue
                self._lengths[slot] += 1  # the fed token is now cached
                self._tokens[slot] = -1   # the next one is on the device
            read, self._unread = self._unread, launch
            if read is None and len(nxt):
                # an engine that read its own launch: nothing stays out
                read, self._unread = launch, None
            self._rate.append(
                (t1, self._account_launch(read, nxt, t1, finished)
                 if read else 0))
            for slot, req in (self._unread or {}).items():
                # the launch that is out is the last of a request that ends
                # by length: its slot and blocks are free for the next step
                if self.slots[slot] is req and (
                        slot in last if self.block is not None
                        else len(req.generated) + 1 >= req.max_new_tokens):
                    self._evict(slot)
            if self.telemetry is not None and self.n_steps % 16 == 0:
                # periodic flush (ISSUE 13): the ttft/token histograms must
                # reach the event stream while serving is LIVE — the health
                # monitor's SLO detector reads p99 from ``metrics`` events,
                # and a flush only at shutdown would blind it
                self.telemetry.flush_metrics(step=self.n_steps)
            return finished

    # -- graceful drain (ISSUE 14) -------------------------------------------
    def begin_drain(self) -> list[Request]:
        """Stop admitting: every queued request is shed (typed terminal,
        reason "draining") and further ``submit`` calls shed on arrival.
        Active requests keep decoding — the drain loop finishes or
        expires them.  -> the newly shed requests."""
        self.draining = True
        shed: list[Request] = []
        self._emit(_INST_DRAIN, phase="begin",
                   in_flight=self.n_active + len(self.queue))
        while self.queue:
            req = self.queue.popleft()
            self.mark_shed(req, "draining")
            shed.append(req)
        return shed

    def expire_all_active(self, reason: str) -> list[Request]:
        """Force every in-flight request terminal (drain deadline): read
        the launch still out, then evict and expire with ``reason``.
        -> the expired requests (and those that launch finished)."""
        out: list[Request] = []
        self._drain(out)
        for slot in range(self.engine.max_batch):
            if self.slots[slot] is None:
                continue
            req = self._evict(slot)
            self._expire(req, "drain", reason, out)
        return out

    def end_drain(self) -> None:
        self._emit(_INST_DRAIN, phase="end", in_flight=self.n_active)


def run_open_loop(scheduler: Scheduler, requests: list[Request],
                  poll_s: float = 0.002, *, drain=None,
                  drain_s: float = 5.0, on_terminal=None,
                  between_steps=None,
                  snapshot=None) -> tuple[dict[int, Request], float]:
    """Drive synthetic open-loop traffic: each request is submitted when the
    wall clock passes its ``arrival_s`` (arrivals never wait on the server —
    that is what makes the load open-loop), then the scheduler steps until
    every request reaches a TERMINAL state (done/expired/shed/failed — no
    request is ever silently lost).  -> ({rid: terminal request}, wall s).

    ``drain``: a zero-arg callable polled every loop pass; once true the
    loop stops admitting (queued + not-yet-arrived requests shed with
    reason "draining"), keeps decoding in-flight requests for up to
    ``drain_s`` seconds, then force-expires the remainder — the SIGTERM
    half of ``tmserve --drain-s``.  ``on_terminal(req)`` fires once per
    terminal request (the CLI's REQUESTS.jsonl writer).
    ``between_steps(scheduler)`` runs every pass — the rollout watcher's
    between-steps poll point.  ``snapshot``: an optional
    :class:`~theanompi_tpu.serving.lifecycle.SnapshotPublisher` whose
    ``maybe`` is offered the live scheduler load every pass (ISSUE 19
    satellite — the router balances on this, not the end-of-drive
    SERVE.json).
    """
    pending = deque(sorted(requests, key=lambda r: r.arrival_s))
    results: dict[int, Request] = {}

    def _terminal(req: Request) -> None:
        results[req.rid] = req
        if on_terminal is not None:
            on_terminal(req)

    draining = False
    drain_deadline = 0.0
    t0 = time.perf_counter()
    while len(results) < len(requests):
        if between_steps is not None:
            between_steps(scheduler)
        if snapshot is not None:
            snapshot.maybe(scheduler.snapshot, scheduler.n_steps)
        if drain is not None and not draining and drain():
            draining = True
            drain_deadline = time.perf_counter() + drain_s
            for req in scheduler.begin_drain():
                _terminal(req)
            while pending:  # never-submitted arrivals shed too: every id
                req = pending.popleft()  # must reach a terminal state
                scheduler.mark_shed(req, "draining")
                _terminal(req)
        now = time.perf_counter() - t0
        if not draining:
            while pending and pending[0].arrival_s <= now:
                req = pending.popleft()
                if not scheduler.submit(req):
                    _terminal(req)
        if scheduler.idle:
            if draining:
                break
            if pending:
                time.sleep(min(poll_s, max(pending[0].arrival_s - now, 0.0)))
            continue
        for req in scheduler.step():
            _terminal(req)
        if draining and time.perf_counter() >= drain_deadline:
            for req in scheduler.expire_all_active("drain deadline"):
                _terminal(req)
            break
    if draining:
        scheduler.end_drain()
    if snapshot is not None:  # final publish: terminal tallies land
        snapshot.maybe(scheduler.snapshot, scheduler.n_steps, force=True)
    return results, time.perf_counter() - t0


def run_queue_loop(scheduler: Scheduler, queue_path: str,
                   poll_s: float = 0.002, *, drain=None,
                   drain_s: float = 5.0, on_terminal=None,
                   between_steps=None, snapshot=None,
                   answered: set[int] | None = None,
                   ) -> tuple[dict[int, Request], float]:
    """Drive a replica off its durable admission queue (ISSUE 19).

    The router appends request entries to ``queue_path`` (see
    :func:`theanompi_tpu.serving.lifecycle.append_queue`); this loop tails
    the file by byte offset, submits each entry as it appears, and keeps
    running until a ``{"op": "drain"}`` sentinel arrives (finish what is
    in flight, then exit) or the ``drain`` callable trips (the SIGTERM
    path: shed queued work with reason "draining", decode in-flight
    requests for up to ``drain_s``, force-expire the rest).

    ``answered``: rids already terminal in a previous attempt (restart
    dedup off REQUESTS.jsonl) — their queue entries are skipped silently,
    NOT re-served and NOT re-recorded.  Each terminal callback receives
    the extra ``queue_wait_ms`` (wall delta from the entry's ``enq_wall``
    stamp to submission) so the router can reconstruct router-visible
    TTFT without a shared monotonic clock.

    -> ({rid: terminal request}, wall seconds).
    """
    results: dict[int, Request] = {}
    answered = set() if answered is None else set(answered)
    queue_wait_ms: dict[int, float] = {}

    def _terminal(req: Request) -> None:
        results[req.rid] = req
        if on_terminal is not None:
            extra = {}
            if req.rid in queue_wait_ms:
                extra["queue_wait_ms"] = queue_wait_ms[req.rid]
            on_terminal(req, **extra)

    def _entry_to_request(e: dict) -> Request:
        return Request(
            rid=int(e["rid"]),
            prompt=list(e["prompt"]),
            max_new_tokens=int(e.get("max_new_tokens", 16)),
            temperature=float(e.get("temperature", 0.0)),
            ttft_deadline_ms=e.get("ttft_deadline_ms"),
            total_deadline_ms=e.get("total_deadline_ms"),
        )

    offset = 0
    drain_seen = False        # durable sentinel: finish in-flight, exit
    sig_draining = False      # SIGTERM: shed + bounded decode + expire
    drain_deadline = 0.0
    t0 = time.perf_counter()
    while True:
        if between_steps is not None:
            between_steps(scheduler)
        if snapshot is not None:
            snapshot.maybe(scheduler.snapshot, scheduler.n_steps)
        if not sig_draining:
            entries, offset = read_jsonl_since(queue_path, offset)
            for e in entries:
                if e.get("op") == DRAIN_OP:
                    drain_seen = True
                    continue
                if "rid" not in e or int(e["rid"]) in answered:
                    continue
                req = _entry_to_request(e)
                if "enq_wall" in e:
                    # wall (not perf_counter): the enqueue stamp came from
                    # the router's process
                    now = time.time()  # lint: wall-ok — cross-process dwell
                    queue_wait_ms[req.rid] = round(
                        max(now - float(e["enq_wall"]), 0.0) * 1e3, 3)
                answered.add(req.rid)  # one submission per rid per attempt
                if not scheduler.submit(req):
                    _terminal(req)
        if drain is not None and not sig_draining and drain():
            sig_draining = True
            drain_deadline = time.perf_counter() + drain_s
            for req in scheduler.begin_drain():
                _terminal(req)
        if scheduler.idle:
            if drain_seen or sig_draining:
                break
            time.sleep(poll_s)
            continue
        for req in scheduler.step():
            _terminal(req)
        if sig_draining and time.perf_counter() >= drain_deadline:
            for req in scheduler.expire_all_active("drain deadline"):
                _terminal(req)
            break
    if sig_draining:
        scheduler.end_drain()
    if snapshot is not None:
        snapshot.maybe(scheduler.snapshot, scheduler.n_steps, force=True)
    return results, time.perf_counter() - t0


def serve_report(results: dict[int, Request], wall_s: float,
                 scheduler: Scheduler) -> dict:
    """The SERVE.json artifact: throughput + latency percentiles."""
    eng = scheduler.engine
    n_tokens = sum(len(r.generated) for r in results.values())

    def pct(xs):
        if not xs:
            return {}
        arr = np.asarray(xs)
        return {"p50": round(float(np.percentile(arr, 50)), 3),
                "p99": round(float(np.percentile(arr, 99)), 3)}

    states = {s: 0 for s in TERMINAL_STATES}
    for r in results.values():
        states[r.state] = states.get(r.state, 0) + 1
    return {
        "metric": "serve_tokens_per_sec",
        "value": round(n_tokens / wall_s, 2) if wall_s > 0 else 0.0,
        "unit": "tokens/sec",
        "requests": len(results),
        "generated_tokens": n_tokens,
        "wall_s": round(wall_s, 3),
        "ttft_ms": pct(scheduler.ttft_ms),
        "token_ms": pct(scheduler.token_ms),
        "preemptions": scheduler.n_preemptions,
        "decode_steps": scheduler.n_steps,
        # ISSUE 18 kernel A/B: which decode path served this run, plus its
        # per-step wall percentiles — the variant key the ledger trends
        "decode_kernel": eng.decode_impl,
        "decode_step_ms": pct(scheduler.step_ms),
        # ISSUE 32: the decode pipeline — launches, those that went out
        # with the one before them unread, and slot-steps run for a request
        # whose stop token was read a step late (the serve.decode tags'
        # sums)
        "run_ahead": {"launched": scheduler.n_steps,
                      "ran_ahead": scheduler.n_ran_ahead,
                      "overrun_slots": scheduler.n_overrun_slots},
        "terminal_states": states,
        "drained": scheduler.draining,
        "quantized_int8": eng.quantized,
        # ISSUE 17 prefix-cache accounting (exact: tokens_saved is the sum
        # of matched-prefix lengths across admissions; zeros when off)
        "prefix_cache": scheduler.prefix_cache is not None,
        "prefix_hit_rate": (
            round(scheduler.n_prefix_hits / scheduler.n_prefix_lookups, 4)
            if scheduler.n_prefix_lookups else 0.0),
        "prefill_tokens_saved": scheduler.prefix_tokens_saved,
        "config": {
            "block_size": eng.block_size,
            "num_blocks": eng.num_blocks,
            "max_batch": eng.max_batch,
            "max_context": eng.max_context,
        },
    }
