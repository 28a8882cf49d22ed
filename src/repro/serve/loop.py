"""Continuous-batching decode: ServeLoop over a slot-managed DecodeCache.

One fixed-shape ``decode_step`` program serves any mix of in-flight
requests (DESIGN.md §12):

  * the cache is ONE ``DecodeCache`` of ``n_slots`` rows with ``capacity``
    KV slots each (ring of `window` for SWA models) — never reallocated;
  * admission prefills a single request (batch 1, prompt padded to a
    length bucket for full-attention models) and writes its cache row in
    place via the masked-update path (``insert_cache_slot``), so a request
    joins a mid-flight batch without recompiling the decode program;
  * per-slot pos/active vectors make retired and never-filled slots exact
    device no-ops — the same masked-padding trick as the masked-tau scan
    in ``core/engine.client_update_many``;
  * each tick runs admit -> decode -> retire -> admit again, so a slot
    freed by retirement (or by an instant-finishing admit) is re-filled
    within the SAME tick instead of idling until the next one;
  * EOS / max-len retirement frees the slot (the stale row stays on
    device; active=False masks it exactly); an oversized request is
    recorded as failed on the ``Request`` and the trace keeps serving.

``PagedServeLoop`` swaps the per-slot worst-case rows for a shared page
pool (``n_pages`` x ``page_size`` KV rows) with per-slot page tables —
short requests hold only the pages they need, so ``n_slots`` can grow at
the same memory budget; admission backpressures (queues, doesn't crash)
while the pool is exhausted. Both loops take a ``SamplerConfig`` for
temperature/top-k sampling with per-request ``fold_in`` streams;
``temperature=0`` is bit-identical to greedy argmax.

Greedy token streams are parity-tested token-for-token against
``serial_generate`` (the old request-at-a-time loop) in
tests/test_serve_loop.py and tests/test_serve_paged.py.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize as _sanitize

# CPU backends that predate donation support ignore the hint; scoped filter
# so the warning doesn't fire once per serve dispatch
from repro.core.engine import _quiet_donation
from repro.core.scheduler import AdmissionScheduler
from repro.kernels.paged_attention.kernel import live_pages
from repro.models.attention import KVCache
from repro.models.model import Model, decode_capability
from repro.models.transformer import (DecodeCache, insert_cache_pages,
                                      insert_cache_slot,
                                      warn_kernel_extend_fallback)
from repro.serve.sampling import GREEDY, SamplerConfig, make_sample_fn
from repro.serve.slots import (PageAllocator, PrefixCache, Request,
                               RequestQueue, SlotTable)


class ServeUnsupportedError(RuntimeError):
    """Model has no decode path (e.g. whisper) — carries the reason."""


def _check_servable(model: Model):
    """decode_capability as a raise-with-reason gate."""
    ok, why = decode_capability(model)
    if not ok:
        raise ServeUnsupportedError(why)


def _request_batch(cfg, req: Request, tokens) -> dict:
    """Prefill inputs for one request; vlm prompts MUST carry patches —
    serving them text-only would silently ignore the vision input."""
    if cfg.vision_dim:
        if req.patches is None:
            raise ServeUnsupportedError(
                f"{cfg.name}: request {req.rid} has no `patches`; vlm "
                "prompts need the vision input alongside tokens "
                "(Request.patches)")
        if req.plen < cfg.num_patches:
            # embed_tokens only splices patches in when they fit inside
            # the prompt (num_patches <= seq len); a shorter prompt would
            # silently drop the image — and bucket padding would make the
            # batched and serial loops disagree about whether it fired
            raise ServeUnsupportedError(
                f"{cfg.name}: request {req.rid} prompt ({req.plen} tokens) "
                f"is shorter than num_patches={cfg.num_patches}; the image "
                "would be silently dropped")
    batch = {"tokens": tokens}
    if req.patches is not None:
        batch["patches"] = jnp.asarray(req.patches, jnp.float32)[None]
    return batch


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


class ServeLoop(AdmissionScheduler):
    """Continuous-batching driver: admission + one decode_step per tick.

    An ``AdmissionScheduler`` instance (DESIGN.md §13): admission fills
    free cache slots from the request queue, the fold is one fixed-shape
    ``decode_step`` over every slot, and the commit appends the sampled
    tokens and retires finished requests — the same admit/fold/commit
    contract the buffered training engine runs.

    Args:
      model, params: any Model with a decode path (decode_capability).
      n_slots: device batch rows (B_slots). Throughput scales with the
        number of simultaneously live rows; the decode program shape is
        fixed at [n_slots] forever.
      capacity: KV slots per row — must cover max(plen + max_new) over the
        requests this loop will ever see (SWA models use their ring of
        `window` slots instead and ignore larger capacities). A request
        that doesn't fit is REJECTED (``Request.failed`` + run() stats),
        not a trace-killing exception.
      bucket: prompt-length rounding for full-attention prefill (one
        compile per distinct bucket, not per distinct prompt length).
        Recurrent (SSM/hybrid/xLSTM) and SWA models must prefill at the
        exact prompt length (state absorbs padding / the ring drops live
        tokens), so they retrace per distinct plen instead.
      cache_update: "mask" (default; shardable) or "scatter".
      sampler: SamplerConfig — temperature/top-k sampling with per-request
        fold_in(rid)/fold_in(nstep) streams (sample streams never depend
        on slot or batch composition). Default GREEDY; temperature=0 is
        bit-identical to greedy argmax.

    Parity note: token streams match SerialLoop bit-for-bit for dense /
    SWA / recurrent families. MoE capacity dropping is batch-composition
    dependent by construction (Switch/GShard static cap over the live
    batch), so a live MoE request's stream can diverge from its
    single-request run exactly when experts overflow — retired/empty
    slots still never influence anyone (tested).
    """

    def __init__(self, model: Model, params, *, n_slots: int = 8,
                 capacity: int = 256, bucket: int = 16,
                 cache_update: str = "mask", unroll: int = 1,
                 sampler: Optional[SamplerConfig] = None,
                 sanitize=None):
        _check_servable(model)
        cfg = model.config
        self.model, self.params, self.cfg = model, params, cfg
        self.n_slots, self.capacity, self.bucket = n_slots, capacity, bucket
        self.cache_update = cache_update
        # analysis lane (DESIGN.md §14): a sanitized run() first drains
        # the trace on cloned requests (warmup — every prefill bucket and
        # program compiles there), then replays it measured: NaN checks
        # armed, per-tick pool audits (paged), and ZERO recompiles.
        self.sanitizer = _sanitize.coerce(sanitize, label="serve-loop")
        self.sampler = sampler or GREEDY
        self._sample = make_sample_fn(self.sampler)
        # exact-length prefill families: recurrent state absorbs padded
        # tokens; the SWA ring keeps the last W slots of the PADDED prompt
        self.exact_prefill = bool(cfg.sliding_window) \
            or cfg.family == "ssm" or cfg.hybrid_parallel_ssm

        self._build_programs(model, unroll)
        self.reset()

    # -- compiled programs (PagedServeLoop overrides) ------------------------
    def _build_programs(self, model, unroll):
        sample, cache_update = self._sample, self.cache_update

        def _decode(p, cache, tok, pos, active, rid, nstep):
            logits, new_cache = model.decode_step(
                p, cache, tok, pos, unroll=unroll,
                cache_update=cache_update, active=active)
            return sample(logits, rid, nstep), new_cache

        self._decode = jax.jit(_decode, donate_argnums=(1,))
        self._insert = jax.jit(insert_cache_slot, donate_argnums=(0,))
        self._build_prefill(model)

    def _build_prefill(self, model):
        cfg, sample, exact = self.cfg, self._sample, self.exact_prefill
        pkw = {} if cfg.family == "ssm" else {"pad_to": self.capacity}

        def _prefill_step(p, batch, length, rid):
            lkw = dict(pkw)
            if not exact:
                lkw["length"] = length
            logits, cache = model.prefill(p, batch, **lkw)
            # the first generated token is sample stream index 0
            return sample(logits, rid, jnp.zeros_like(rid)), cache

        # one jit: its own shape cache gives one compile per prompt bucket
        self._prefill_jit = jax.jit(_prefill_step)

    def _init_cache(self):
        return self.model.init_cache(self.n_slots, self.capacity)

    def reset(self):
        """Fresh slot table + cache; compiled programs are kept (reusing a
        loop across traces never recompiles)."""
        self.cache = self._init_cache()
        self.table = SlotTable(self.n_slots)
        self.t = 0
        self._queue: Optional[RequestQueue] = None
        self.decode_dispatches = 0
        self.prefill_dispatches = 0
        self.prefilled_tokens = 0  # real prompt rows sent through prefill
        self.tick_walls: List[float] = []  # wall clock at each tick start
        self.rejected = []

    # -- admission -----------------------------------------------------------
    def _admission_error(self, req: Request) -> Optional[str]:
        """Reason this request can NEVER be served by this loop (reject),
        or None. Transient shortage is _can_admit's business instead."""
        if not self.cfg.sliding_window and \
                req.plen + req.max_new - 1 > self.capacity:
            # pos % W would wrap the full-attention cache and silently
            # overwrite live prompt KV
            return (f"plen {req.plen} + max_new {req.max_new} exceeds "
                    f"cache capacity {self.capacity}")
        return None

    def _can_admit(self, req: Request) -> bool:
        """Transient admission gate (paged: page-pool backpressure)."""
        return True

    def _prefill(self, req: Request):
        plen = req.plen
        padded = plen if self.exact_prefill else \
            min(_round_up(plen, self.bucket), self.capacity)
        toks = np.zeros((1, padded), np.int32)
        toks[0, :plen] = req.tokens
        batch = _request_batch(self.cfg, req, jnp.asarray(toks))
        first, one = self._prefill_jit(
            self.params, batch, jnp.full((1,), plen, jnp.int32),
            jnp.full((1,), req.rid, jnp.int32))
        self.prefill_dispatches += 1
        self.prefilled_tokens += plen
        return int(first[0]), one

    def _insert_request(self, slot: int, req: Request, one):
        with _quiet_donation():
            self.cache = self._insert(self.cache, one, jnp.int32(slot))

    def _retire(self, slot: int):
        self.table.retire(slot, self.t)

    def _begin_request(self, slot: int, req: Request):
        """Prefill + cache insert + slot bind for one admitted request;
        instantly-finished requests (max_new == 1 / instant EOS) retire
        in place so the slot is reconsidered by the caller's loop."""
        first, one = self._prefill(req)
        self._insert_request(slot, req, one)
        self.table.admit(slot, req, first, self.t)
        if req.finished():
            self._retire(slot)

    def _admit(self):
        """Fill free slots from the arrived queue; loops until no slot or
        no admissible request is left, so a slot freed by an instant-
        finishing admit is reconsidered immediately. Oversized requests
        are recorded as failed (the trace keeps serving); a request the
        loop COULD serve but can't right now (paged pool exhausted) stays
        queued — admission backpressure, FIFO order preserved."""
        queue = self._queue
        if queue is None:
            return
        while True:
            free = self.table.free_slots()
            if not free:
                return
            req = queue.peek_arrived(self.t)
            if req is None:
                return
            err = self._admission_error(req)
            if err is not None:
                queue.pop_arrived(self.t)
                req.failed = f"request {req.rid}: {err}"
                req.done_tick = self.t
                self.rejected.append(req)
                continue
            if not self._can_admit(req):
                return
            queue.pop_arrived(self.t)
            self._begin_request(free[0], req)

    # -- one tick ------------------------------------------------------------
    def _dispatch_decode(self, rid, nstep):
        table = self.table
        with _quiet_donation():
            return self._decode(
                self.params, self.cache,
                jnp.asarray(table.last_tok), jnp.asarray(table.pos),
                jnp.asarray(table.active),
                jnp.asarray(rid), jnp.asarray(nstep),
            )

    def _has_work(self) -> bool:
        return self.table.any_active()

    def _pending(self) -> bool:
        return self._queue is not None and len(self._queue) > 0

    def _fold(self):
        """One fixed-shape decode_step over every slot (retired and
        never-filled rows are exact device no-ops)."""
        table = self.table
        rid = np.array([r.rid if r else 0 for r in table.req], np.int32)
        nstep = np.array([len(r.out) if r else 0 for r in table.req],
                         np.int32)
        nxt, self.cache = self._dispatch_decode(rid, nstep)
        self.decode_dispatches += 1
        return np.asarray(nxt)

    def _commit(self, nxt_np) -> None:
        """Append this tick's sampled tokens; retire finished requests
        (their slots are re-filled by the trailing admit of the same
        tick — the retire-then-admit property)."""
        table = self.table
        for slot in table.live_slots():
            table.append(slot, int(nxt_np[slot]))
            if table.req[slot].finished():
                self._retire(slot)

    def tick(self, queue: Optional[RequestQueue] = None):
        """Admit -> one decode_step -> retire -> admit again
        (``AdmissionScheduler.tick``; the trailing admission re-fills
        slots freed by this tick's retirement: the new request prefills
        NOW — its first token lands this tick — and joins the decode
        batch next tick instead of idling a full tick)."""
        if queue is not None:
            self._queue = queue
        # tick_walls[t] = wall clock when tick t began: arrival-to-first-
        # token latency (TTFT) = req.tok_walls[0] - tick_walls[req.arrival]
        # (benchmarks/serve_slo.py)
        self.tick_walls.append(time.time())
        super().tick()

    def _extra_stats(self) -> Dict:
        return {}

    def run(self, requests: Sequence[Request]) -> Dict:
        """Drive every request to completion; returns per-run stats.

        Starts from a fresh slot table / tick clock (reset()), so stats
        and arrival ticks are per-trace; compiled programs are reused.

        Under ``sanitize=`` the trace runs twice: once on cloned
        requests (warmup — every program and prefill bucket compiles),
        then measured with NaN checks armed and the steady-state
        assertion: the replay must compile NOTHING. Stats and request
        outputs come from the measured pass.
        """
        if self.sanitizer is not None and not self.sanitizer.active:
            with self.sanitizer:
                self._drain_trace([r.clone() for r in requests])
                self.sanitizer.mark_steady()
                stats = self._drain_trace(requests)
                self.sanitizer.assert_steady_state()
            return stats
        return self._drain_trace(requests)

    def _drain_trace(self, requests: Sequence[Request]) -> Dict:
        self.reset()
        self._queue = RequestQueue(requests)
        t0 = time.time()
        self.drain()
        jax.block_until_ready(self.cache)
        wall = time.time() - t0
        toks = sum(len(r.out) for r in requests)
        return dict(
            wall_s=wall,
            ticks=self.t,
            tokens=toks,
            tok_s=toks / max(wall, 1e-9),
            decode_dispatches=self.decode_dispatches,
            prefill_dispatches=self.prefill_dispatches,
            prefilled_tokens=self.prefilled_tokens,
            failed=len(self.rejected),
            failed_rids=[r.rid for r in self.rejected],
            **self._extra_stats(),
        )


@dataclasses.dataclass
class _PrefillJob:
    """An admitted request whose prompt is still being chunk-prefilled:
    its slot holds pool pages and a page-table row but is NOT yet live in
    the SlotTable (decode skips it) until the last chunk lands."""
    req: Request
    done: int  # prompt rows already in the pool (prefix hits + chunks)


@dataclasses.dataclass
class _Preempted:
    """An evicted mid-decode request staged on host (DESIGN.md §12.2):
    its pool pages were copied out verbatim and freed; restore allocates
    fresh pages, writes the staged rows back and rebinds the slot —
    decode resumes bit-identically (content is position-addressed through
    the page table, physical page ids never enter the math)."""
    req: Request
    k: np.ndarray  # [L, pages_per_slot, page_size, Hkv, hd] staged pages
    v: np.ndarray
    ssm: object  # hybrid models' per-slot recurrent row, or None
    pages: int  # allocated pages to re-acquire on restore


class PagedServeLoop(ServeLoop):
    """Continuous batching over a shared KV page pool (DESIGN.md §12).

    Device layout: ``PagedDecodeCache`` holds ONE pool of ``n_pages``
    pages x ``page_size`` KV rows shared by every slot; the host
    ``PageAllocator`` hands each admitted request exactly
    ``ceil(min(plen + max_new - 1, window or inf) / page_size)`` pages,
    recorded in a per-slot page-table row that rides into every paged
    ``decode_step`` dispatch. Short requests stop reserving worst-case
    rows, so ``n_slots`` can grow at the same KV-memory budget
    (``n_pages * page_size`` rows vs contiguous ``n_slots * capacity``).

    Admission backpressure: when the pool can't cover the head request's
    pages it WAITS in the queue (FIFO) until retirement frees pages —
    never a crash; a request whose demand exceeds the whole pool (or the
    per-slot logical ``capacity``) is rejected gracefully like the
    oversized case in the contiguous loop. Retirement returns the slot's
    pages to the free list; a reused page is overwritten IN FULL at the
    next admission and arithmetically masked until then, so stale KV can
    never poison a new request (tests/test_serve_paged.py).

    Greedy token streams are bit-identical to ``ServeLoop`` and
    ``SerialLoop`` whenever the logical per-slot capacities match
    (capacity a multiple of page_size; SWA rings page their `window`
    rows). Recurrent-only families (xLSTM) have no KV to page — use
    ``ServeLoop``; hybrid models keep dense per-slot SSM state rows.

    ``cache_update`` adds a third value here: "kernel" dispatches decode
    attention AND admission page writes to kernels/paged_attention (the
    Pallas page-walk kernel with the fused pool write — no dense
    [B, P*page_size, ...] gather, no full-pool selector); greedy streams
    stay bit-identical to "mask" (tests/test_paged_kernel.py and the
    serve_paged.py --smoke CI stage assert it).

    Front-end scheduler features (DESIGN.md §12.2; all default OFF, all
    greedy-parity-preserving — per-request fold_in sample streams make
    token streams scheduling-independent, so only KV corruption could
    break parity, and the tests force each feature and assert none does):

      prefix_cache: content-addressed page sharing. Admission looks up
        the prompt's page-aligned prefixes in a host :class:`PrefixCache`;
        hit pages are aliased read-only into the new slot's page table
        (refcounted in the ``PageAllocator``) and only the SUFFIX is
        prefilled straight into the pool via ``paged_prefill_chunk``.
        Decode writes land past every shared prefix (pages are
        write-exclusive), so no copy-on-write is ever needed.
      prefill_chunk: admission prefills at most ``prefill_chunk`` prompt
        tokens per tick (one fixed-width compile), interleaved with
        decode — a long prompt no longer stalls every live stream for a
        full-prompt prefill dispatch (bounded per-tick latency).
      preempt: when the pool is exhausted and the FIFO head has been
        blocked for ``preempt_after`` ticks, the youngest live request
        (largest page footprint tiebreak) is evicted — its pages staged
        to host buffers and freed — and re-admitted with priority once
        pages free up. Head-of-line blocking cannot starve the queue.

    prefix_cache / prefill_chunk require full attention (the SWA ring
    wraps decode writes into early — possibly shared — pages), KV-only
    models (recurrent carries don't live in pool pages) and text-only
    prompts; preemption works for every paged family (hybrid SSM rows are
    staged alongside the pages).
    """

    def __init__(self, model: Model, params, *, n_slots: int = 8,
                 capacity: int = 256, page_size: int = 16,
                 n_pages: Optional[int] = None, bucket: int = 16,
                 cache_update: str = "mask", unroll: int = 1,
                 sampler: Optional[SamplerConfig] = None,
                 prefix_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 preempt: bool = False, preempt_after: int = 2,
                 sanitize=None):
        _check_servable(model)
        cfg = model.config
        if cfg.family == "ssm" or model.init_paged_cache is None:
            raise ServeUnsupportedError(
                f"{cfg.name}: family={cfg.family!r} keeps O(1) recurrent "
                "state per slot — there is no KV cache to page; use the "
                "contiguous ServeLoop")
        self.page_size = page_size
        W = cfg.sliding_window
        logical = W if W else capacity
        self.pages_per_slot = -(-logical // page_size)
        if not W:  # prefill pad_to must equal the paged logical capacity
            capacity = self.pages_per_slot * page_size
        self.n_pages = n_slots * self.pages_per_slot if n_pages is None \
            else n_pages
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.prefix_cache_on = bool(prefix_cache)
        self.prefill_chunk = prefill_chunk
        self.preempt, self.preempt_after = bool(preempt), preempt_after
        # pool-direct suffix/chunk prefill path (vs legacy whole-prompt
        # prefill-then-insert); preempt alone keeps the legacy prefill
        self._use_extend = self.prefix_cache_on or prefill_chunk is not None
        self._sched_on = self._use_extend or self.preempt
        if self._use_extend:
            why = None
            if cfg.sliding_window:
                why = ("the SWA ring wraps KV writes into early (possibly "
                       "shared) pages")
            elif cfg.family == "ssm" or cfg.hybrid_parallel_ssm:
                why = "recurrent carries do not live in pool pages"
            elif cfg.vision_dim:
                why = ("vlm patch splicing needs the whole prompt in one "
                       "prefill dispatch")
            if why is not None:
                raise ServeUnsupportedError(
                    f"{cfg.name}: prefix caching / chunked prefill is "
                    f"full-attention text-only — {why}")
        super().__init__(model, params, n_slots=n_slots, capacity=capacity,
                         bucket=bucket, cache_update=cache_update,
                         unroll=unroll, sampler=sampler, sanitize=sanitize)

    def _build_programs(self, model, unroll):
        sample, cache_update = self._sample, self.cache_update

        def _decode(p, cache, page_table, tok, pos, active, rid, nstep):
            logits, new_cache = model.paged_decode_step(
                p, cache, page_table, tok, pos, unroll=unroll,
                cache_update=cache_update, active=active)
            return sample(logits, rid, nstep), new_cache

        self._decode = jax.jit(_decode, donate_argnums=(1,))
        self._insert = jax.jit(
            functools.partial(insert_cache_pages, cache_update=cache_update),
            donate_argnums=(0,))
        self._build_prefill(model)
        if self._use_extend:
            # chunk writes reuse the mask path under "kernel" (decode still
            # dispatches the Pallas kernel); start/length are traced scalars
            # so there is ONE compile per chunk width, not per (start, len)
            if cache_update == "kernel":
                warn_kernel_extend_fallback("serve.PagedServeLoop")
            cu = "mask" if cache_update == "kernel" else cache_update
            unroll_ = unroll

            def _extend(p, cache, row, toks, start, length, rid):
                logits, new_cache = model.paged_prefill_chunk(
                    p, cache, row, toks, start, length, unroll=unroll_,
                    cache_update=cu)
                # completion chunk holds row plen-1: its logits seed the
                # stream at sample index 0 (intermediate chunks' samples
                # are discarded by the driver)
                return sample(logits, rid, jnp.zeros_like(rid)), new_cache

            self._extend = jax.jit(_extend, donate_argnums=(1,))
        if self.preempt:
            def _stage(cache, row):
                safe = jnp.maximum(row, 0)  # -1 rows gathered then ignored
                return cache.kv.k[:, safe], cache.kv.v[:, safe]

            self._stage = jax.jit(_stage)

    def _init_cache(self):
        self.allocator = PageAllocator(self.n_pages, self.page_size)
        self.page_table = np.full((self.n_slots, self.pages_per_slot), -1,
                                  np.int32)
        return self.model.init_paged_cache(self.n_slots, self.n_pages,
                                           self.page_size)

    def reset(self):
        super().reset()
        self._prefilling: Dict[int, _PrefillJob] = {}
        self._preempted: deque = deque()
        self._blocked_since: Optional[int] = None
        self._chunk_left: Optional[int] = None
        self._admit_plan = None
        self.prefix = PrefixCache(self.allocator) if self.prefix_cache_on \
            else None
        self.prefix_hit_tokens = 0
        self.preemptions = 0
        # pages the decode kernel's walk bound reads, and pages the slots'
        # tables list, summed over decode dispatches
        self.kv_pages_walked = 0
        self.kv_pages_listed = 0
        self.extend_dispatches = 0
        self.restore_dispatches = 0

    def tick(self, queue: Optional[RequestQueue] = None):
        self._chunk_left = self.prefill_chunk  # per-tick chunk token budget
        super().tick(queue)
        if self.sanitizer is not None and self.sanitizer.active:
            # sanitize lane: full refcount-conservation audit every tick —
            # a leaked/double-freed page fails AT the tick that broke it
            self.check_invariants()

    def _rows_needed(self, req: Request) -> int:
        rows = req.plen + req.max_new - 1
        W = self.cfg.sliding_window
        return min(rows, W) if W else rows

    def _admission_error(self, req: Request) -> Optional[str]:
        err = super()._admission_error(req)
        if err is not None:
            return err
        need = self.allocator.pages_for(self._rows_needed(req))
        if need > self.n_pages:
            return (f"needs {need} pages ({self._rows_needed(req)} KV rows) "
                    f"but the pool has only {self.n_pages} — can never be "
                    "admitted")
        return None

    def _can_admit(self, req: Request) -> bool:
        return self.allocator.free_pages >= \
            self.allocator.pages_for(self._rows_needed(req))

    def _insert_request(self, slot: int, req: Request, one):
        need = self.allocator.pages_for(self._rows_needed(req))
        ids = self.allocator.alloc(need)
        assert ids is not None, "admission raced the allocator"
        row = np.full(self.pages_per_slot, -1, np.int32)
        row[:need] = ids
        self.page_table[slot] = row
        with _quiet_donation():
            self.cache = self._insert(self.cache, one, jnp.int32(slot),
                                      jnp.asarray(row))

    def _retire(self, slot: int):
        self.allocator.free(self.page_table[slot])
        self.page_table[slot] = -1
        super()._retire(slot)

    # -- front-end scheduler (DESIGN.md §12.2) -------------------------------
    def _admit(self):
        """Scheduler admission order: (1) advance in-flight chunk-prefill
        jobs (they hold pages — finishing them frees decode throughput
        first), (2) restore preempted requests FIFO (they already burned
        prefill work), (3) admit new requests FIFO. A blocked head
        triggers prefix-cache eviction, then — after ``preempt_after``
        stalled ticks — slot preemption."""
        if not self._sched_on:
            super()._admit()
            return
        self._advance_prefills()
        queue = self._queue
        while True:
            free = [s for s in self.table.free_slots()
                    if s not in self._prefilling]
            if not free:
                return
            if self._preempted:
                ent = self._preempted[0]
                if not self._ensure_pages(ent.pages):
                    if not self._try_preempt(ent.pages):
                        return
                    continue
                self._preempted.popleft()
                self._blocked_since = None
                self._restore(free[0], ent)
                continue
            if queue is None:
                return
            req = queue.peek_arrived(self.t)
            if req is None:
                return
            err = self._admission_error(req)
            if err is not None:
                queue.pop_arrived(self.t)
                req.failed = f"request {req.rid}: {err}"
                req.done_tick = self.t
                self.rejected.append(req)
                continue
            if not self._plan_admission(req):
                if not self._try_preempt(self._short_pages):
                    return
                continue
            queue.pop_arrived(self.t)
            self._blocked_since = None
            if self._use_extend:
                self._start_job(free[0], req)
            else:
                self._admit_plan = None
                self._begin_request(free[0], req)

    def _plan_admission(self, req: Request) -> bool:
        """Can the head request start NOW? Pins its prefix-cache hits
        (``share`` BEFORE any eviction can free them), then checks the
        pool covers the private remainder — evicting cache-only pages if
        short. On success the plan (shared pages, total need) is stashed
        for ``_start_job``; on failure the pins are released."""
        need = self.allocator.pages_for(self._rows_needed(req))
        shared: List[int] = []
        if self.prefix is not None:
            shared = self.prefix.lookup(req.tokens)
            self.allocator.share(shared)
        if self._ensure_pages(need - len(shared)):
            self._admit_plan = (req.rid, shared, need)
            return True
        if shared:
            self.allocator.free(shared)
        self._short_pages = need - len(shared)
        return False

    def _ensure_pages(self, n: int) -> bool:
        """Free pool pages >= n, evicting LRU cache-only prefix pages
        (refcount 1) to close a shortfall — cached prefixes are a
        best-effort optimization, live work is not."""
        short = n - self.allocator.free_pages
        if short > 0 and self.prefix is not None:
            self.prefix.evict_for(short)
        return self.allocator.free_pages >= n

    def _try_preempt(self, need_pages: int) -> bool:
        """The head has been refused pages: start (or continue) the
        blocked clock, and once it has stalled ``preempt_after`` ticks,
        evict the youngest live request (most-pages tiebreak — youngest
        loses the least progress, largest frees the most) until the head
        fits. Returns True when pages were freed and the head now fits."""
        if self._blocked_since is None:
            self._blocked_since = self.t
        if not self.preempt or \
                self.t - self._blocked_since < self.preempt_after:
            return False
        evicted = False
        while not self._ensure_pages(need_pages):
            victims = [s for s in self.table.live_slots()
                       if s not in self._prefilling]
            if not victims:
                return False
            victim = max(victims, key=lambda s: (
                self.table.req[s].admit_tick,
                int((self.page_table[s] >= 0).sum()), s))
            self._evict(victim)
            evicted = True
        return evicted

    def _evict(self, slot: int):
        """Preempt a live slot: stage its pool pages (and hybrid SSM row)
        to host buffers, unbind the slot, free the pages. The request
        resumes — bit-identically — via ``_restore``."""
        row = self.page_table[slot].copy()
        k, v = self._stage(self.cache, jnp.asarray(row))
        ssm = None
        if self.cache.ssm is not None:
            ssm = jax.device_get(
                jax.tree.map(lambda x: x[:, slot], self.cache.ssm))
        self._preempted.append(_Preempted(
            req=self.table.evict(slot), k=np.asarray(k), v=np.asarray(v),
            ssm=ssm, pages=int((row >= 0).sum())))
        self.allocator.free(row)
        self.page_table[slot] = -1
        self.preemptions += 1

    def _restore(self, slot: int, ent: _Preempted):
        """Re-admit a preempted request: fresh pages, staged rows written
        back verbatim (page content is position-addressed through the
        page table — physical ids never enter the math), slot rebound."""
        ids = self.allocator.alloc(ent.pages)
        assert ids is not None, "restore raced the allocator"
        row = np.full(self.pages_per_slot, -1, np.int32)
        row[:ent.pages] = ids
        self.page_table[slot] = row
        L, P, ps, Hkv, hd = ent.k.shape
        one = DecodeCache(
            kv=KVCache(k=jnp.asarray(ent.k).reshape(L, 1, P * ps, Hkv, hd),
                       v=jnp.asarray(ent.v).reshape(L, 1, P * ps, Hkv, hd),
                       pos=jnp.zeros((L, 1, P * ps), jnp.int32)),
            ssm=jax.tree.map(lambda x: jnp.asarray(x)[:, None], ent.ssm)
            if ent.ssm is not None else None,
            xlstm_m=None, xlstm_s=None)
        with _quiet_donation():
            self.cache = self._insert(self.cache, one, jnp.int32(slot),
                                      jnp.asarray(row))
        self.table.rebind(slot, ent.req)
        self.restore_dispatches += 1

    def _start_job(self, slot: int, req: Request):
        """Begin pool-direct admission: bind shared prefix pages + freshly
        allocated private pages into the slot's page-table row, then run
        the suffix through the chunk-prefill budget."""
        rid, shared, need = self._admit_plan
        assert rid == req.rid, "admission plan raced the queue"
        self._admit_plan = None
        priv = self.allocator.alloc(need - len(shared))
        assert priv is not None, "admission raced the allocator"
        row = np.full(self.pages_per_slot, -1, np.int32)
        row[:len(shared)] = shared
        row[len(shared):need] = priv
        self.page_table[slot] = row
        hit = len(shared) * self.page_size
        self.prefix_hit_tokens += hit
        self._prefilling[slot] = _PrefillJob(req=req, done=hit)
        self._advance_job(slot)

    def _advance_prefills(self):
        for slot in list(self._prefilling):
            self._advance_job(slot)

    def _advance_job(self, slot: int):
        """Push one prefill job forward within this tick's chunk budget;
        on the last chunk (the one holding prompt row plen-1) its sampled
        logits seed the output stream and the slot goes live."""
        job = self._prefilling[slot]
        req, row = job.req, self.page_table[slot]
        first = None
        while job.done < req.plen:
            remaining = req.plen - job.done
            if self.prefill_chunk is None:  # suffix in one bucketed shot
                step = remaining
                width = min(_round_up(remaining, self.bucket), self.capacity)
            else:
                if self._chunk_left is not None and self._chunk_left <= 0:
                    return  # budget spent; the job resumes next tick
                step = min(self.prefill_chunk, remaining)
                width = self.prefill_chunk  # fixed width: one compile
            toks = np.zeros((1, width), np.int32)
            toks[0, :step] = req.tokens[job.done:job.done + step]
            first = self._dispatch_extend(row, toks, job.done, step, req.rid)
            job.done += step
            self.prefilled_tokens += step
            if self._chunk_left is not None:
                self._chunk_left -= step
        del self._prefilling[slot]
        self.table.admit(slot, req, first, self.t)
        if self.prefix is not None:
            self.prefix.register(req.tokens, row, req.plen)
        if req.finished():  # max_new == 1 or instant EOS
            self._retire(slot)

    def _dispatch_extend(self, row, toks, start, length, rid) -> int:
        with _quiet_donation():
            first, self.cache = self._extend(
                self.params, self.cache, jnp.asarray(row),
                jnp.asarray(toks), jnp.int32(start), jnp.int32(length),
                jnp.full((1,), rid, jnp.int32))
        self.extend_dispatches += 1
        return int(first[0])

    def _pending(self) -> bool:
        return super()._pending() or bool(self._prefilling) \
            or bool(self._preempted)

    def check_invariants(self):
        """Full refcount-conservation audit: every in-use page's refcount
        must equal its page-table references plus its prefix-cache pin
        (tests call this mid-churn)."""
        self.allocator.check(
            page_tables=list(self.page_table),
            cached_pages=self.prefix.pages if self.prefix else None)

    def _dispatch_decode(self, rid, nstep):
        table = self.table
        self.kv_pages_walked += int(live_pages(
            table.pos, table.active, self.page_size, self.cfg.sliding_window,
            self.pages_per_slot, xp=np).sum())
        self.kv_pages_listed += self.n_slots * self.pages_per_slot
        with _quiet_donation():
            return self._decode(
                self.params, self.cache, jnp.asarray(self.page_table),
                jnp.asarray(table.last_tok), jnp.asarray(table.pos),
                jnp.asarray(table.active),
                jnp.asarray(rid), jnp.asarray(nstep),
            )

    def _extra_stats(self) -> Dict:
        return dict(
            n_pages=self.n_pages,
            page_size=self.page_size,
            kv_rows=self.n_pages * self.page_size,
            peak_pages=self.allocator.peak_in_use,
            prefix_hit_tokens=self.prefix_hit_tokens,
            preemptions=self.preemptions,
            extend_dispatches=self.extend_dispatches,
            restore_dispatches=self.restore_dispatches,
            kv_pages_walked=self.kv_pages_walked,
            kv_pages_listed=self.kv_pages_listed,
            prefix_pages=len(self.prefix) if self.prefix else 0,
        )


# ---------------------------------------------------------------------------
# request-at-a-time baseline (the pre-serve examples/serve_decode.py loop)
# ---------------------------------------------------------------------------


class SerialLoop:
    """One request at a time: prefill [1, plen], then decode_step with
    batch 1 until EOS/max_new. The parity oracle for ServeLoop — token
    streams must match token-for-token (greedy argmax, and sampled decode
    too: the per-request fold_in streams are batch-independent).

    `capacity`: fixed KV capacity shared by every request (one decode
    compile, one prefill compile per distinct plen); None sizes each
    request's cache exactly (retraces per (plen, max_new) pair — the old
    examples/serve_decode.py behavior).

    The decode jit donates its cache like ServeLoop's (one live copy per
    step, not two) so benchmarks/serve_loop.py compares equal-memory
    loops; the capacity guard still RAISES here (oracle semantics —
    the batched loops reject gracefully instead).
    """

    def __init__(self, model: Model, params, *, capacity: int = None,
                 cache_update: str = "mask", unroll: int = 1,
                 sampler: Optional[SamplerConfig] = None):
        _check_servable(model)
        cfg = model.config
        self.model, self.params, self.cfg = model, params, cfg
        self.capacity = capacity
        self.sampler = sampler or GREEDY
        sample = make_sample_fn(self.sampler)

        def _decode(p, cache, tok, pos, rid, nstep):
            logits, new_cache = model.decode_step(
                p, cache, tok, pos, unroll=unroll, cache_update=cache_update)
            return sample(logits, rid, nstep), new_cache

        # donate the cache: the request-at-a-time baseline must not hold
        # two live copies per step (it would skew memory comparisons)
        self._decode = jax.jit(_decode, donate_argnums=(1,))
        self._sample_jit = jax.jit(sample)

        @functools.lru_cache(maxsize=None)
        def _prefill_fn(cap: int):
            kw = {} if cfg.family == "ssm" else {"pad_to": cap}
            return jax.jit(lambda p, b: model.prefill(p, b, **kw))

        self._prefill_fn = _prefill_fn

    def run(self, requests: Sequence[Request]) -> Dict:
        t0 = time.time()
        steps = 0
        for req in requests:
            cap = self.capacity or (req.plen + req.max_new - 1)
            if req.plen + req.max_new - 1 > cap and not self.cfg.sliding_window:
                # pos % W would wrap the full-attention cache and silently
                # overwrite live prompt KV
                raise ValueError(
                    f"request {req.rid}: plen {req.plen} + max_new "
                    f"{req.max_new} exceeds cache capacity {cap}")
            batch = _request_batch(self.cfg, req,
                                   jnp.asarray(req.tokens[None, :]))
            rid = jnp.full((1,), req.rid, jnp.int32)
            logits, cache = self._prefill_fn(cap)(self.params, batch)
            req.out.append(int(self._sample_jit(
                logits, rid, jnp.zeros((1,), jnp.int32))[0]))
            pos = req.plen
            while not req.finished():
                with _quiet_donation():
                    tok, cache = self._decode(
                        self.params, cache,
                        jnp.asarray(req.out[-1:], jnp.int32),
                        jnp.full((1,), pos, jnp.int32),
                        rid, jnp.full((1,), len(req.out), jnp.int32),
                    )
                req.out.append(int(tok[0]))
                pos += 1
                steps += 1
        wall = time.time() - t0
        toks = sum(len(r.out) for r in requests)
        return dict(wall_s=wall, ticks=steps, tokens=toks,
                    tok_s=toks / max(wall, 1e-9), decode_dispatches=steps,
                    prefill_dispatches=len(requests))


def serial_generate(model: Model, params, requests: Sequence[Request], *,
                    capacity: int = None, cache_update: str = "mask",
                    unroll: int = 1, sampler: SamplerConfig = None) -> Dict:
    """Convenience wrapper: build a SerialLoop and drive `requests`."""
    return SerialLoop(model, params, capacity=capacity,
                      cache_update=cache_update, unroll=unroll,
                      sampler=sampler).run(requests)
