"""Paged continuous-batching generation engine.

One :class:`GenerationEngine` serves one hosted transformer bundle. It
owns a persistent KV cache and a dedicated worker thread that runs the
device loop — the Orca-style continuous-batching core (Yu et al., OSDI
'22), over **paged block-table storage** (PagedAttention, Kwon et al.
SOSP '23; prefix sharing after RadixAttention). This is the only way
the node generates:

- the cache is a pool of fixed-size KV blocks
  (:class:`~pygrid_tpu.models.decode.PagedKVCache`); a request holds
  only the pages covering its own prompt + ``n_new`` tokens instead of
  a contiguous ``[max_len]`` slab, so short requests stop stranding
  cache memory and the block pool — not the slot count — is what
  admission exhausts;
- identical prompt prefixes (hash-keyed full blocks, e.g. a common
  system prompt) prefill ONCE and are mapped read-only into later
  requests' block tables copy-on-write
  (:class:`~pygrid_tpu.serving.pagedkv.PrefixCache`); refcounted blocks
  free when the last reader completes;
- requests wait in a bounded FIFO queue (admission past the depth limit
  — or block demand past the overcommit bound — answers a typed
  :class:`~pygrid_tpu.utils.exceptions.ServerBusyError`);
- a free slot admits the oldest request via a per-slot dense chunk
  prefill (prompt suffix after the shared prefix, padded to a bucket,
  true length traced) that writes only that request's pages — live
  slots keep decoding undisturbed; when the pool is exhausted the row
  parks at the queue head until completions free blocks;
- every step advances ALL live slots with one jitted block-table decode
  program at the narrowest width bucket covering them, each slot at its
  own position — finished requests leave between steps while the rest
  keep decoding, so short requests never wait for long ones;
- at most ``quantum`` decode steps run between admission checks (the
  fairness cap: a queued request's time-to-first-token is bounded by
  one quantum even when the batch is full of long generations);
- when no admission is pending, the whole quantum runs as ONE compiled
  ``lax.scan`` program (**fused multi-step decode**, default on,
  ``PYGRID_FUSED_DECODE=off``): per-row token budgets freeze rows that
  finish mid-scan (their writes trash-route, their positions park), so
  the host pays one dispatch + one token fetch per quantum instead of
  per step — the dominant cost of small/medium-model decode;
- a family whose forward carries a BLOCK of positions (``BLOCK_LEN`` >
  1: generation by diffusion over blocks) takes the same step loop with
  one program a width, ``paged_block_step``: a row's current block
  (its tokens, which positions are still masked) lives on the device
  beside the cache, a forward reveals nought to ``BLOCK_LEN`` of its
  tokens there, and the forward after the one that made a block whole
  carries that block beside the next one: its K/V stand, the row's
  position moves on, and no forward runs for the commit alone. Rows in
  different phases share a dispatch.

**The loop dispatches ahead of what it has read.** Nothing the host
decides between two forwards depends on the tokens: there is no stop
token, so who is live, who ends and who is admitted at step n+1 follows
from counts. A row therefore carries how many tokens are SCHEDULED for
it (launched programs will make them) apart from how many have ARRIVED
in ``row.out``; the loop plans by the first: a row leaves its slot, and
its pages the pool, when its last token is scheduled. A block family's
counts are fixed by ``denoising_steps``: a block with ``m`` masked
positions takes ``ceil(m / (BLOCK_LEN / denoising_steps))`` denoising
forwards, each revealing that many or what is left, and the first
forward of the block after it commits it (nothing commits a row's last
block), so how many a forward reveals, whether it carries a commit and
what it makes final are known as it is built. The device
runs its programs in order, so a prefill into the same slot or pages
queues behind the step that still writes them. What a forward reads of
the one before stays on the device (``programs``: ``last``, a causal
family's last tokens; a block family's blocks), and what a launched
program will answer the host waits in a FIFO of arrivals: ``_step``
builds and launches step n+1 and only then fetches the arrivals up to
step n, so the device has its next program while the host hands tokens
out. Nothing stays in flight where nobody would collect it: the FIFO is
drained when nothing is live any more (before the loop waits for work),
when the thread leaves, and behind every fused scan.

Every instant of the worker thread belongs to one of six phases of a
:class:`~pygrid_tpu.telemetry.loopclock.LoopClock` — ``idle`` (nothing
queued, nothing live), ``admit`` (slot, pages, prompt padding, keys),
``prefill`` (the prefill program's call, which returns once it is
enqueued), ``build`` (a decode dispatch's inputs and its enqueue),
``fetch`` (blocked on the device for the oldest arrival) and ``emit``
(tokens into rows, finished requests out). Each is seconds on
``serving_loop_seconds_total{phase}`` and an ``engine.<phase>``
annotation in a ``jax.profiler`` trace (docs/OBSERVABILITY.md §4, §6).

Greedy results are bit-identical to single-request
:func:`pygrid_tpu.models.decode.generate` on the CPU at f32 (tested).
On the TPU they are the same tokens except at rounding ties: the same
arithmetic runs in programs of different batch width, and a top-1/top-2
logit margin below ~0.02 can change hands (measured on a v5e: 134 of
160 requests identical, every divergence a tie of ≤ 0.018 in float32
reference logits — PERF.md, PR 21). Sampling is reproducible per
(seed, row) and distribution-identical to the single-request path. The
worker thread is the ONLY thread that touches
the device loop — WS/HTTP handler threads just enqueue and wait on a
future, so heavy generation cannot starve FL report handlers on the
shared executor.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeoutError
from dataclasses import dataclass
from typing import Any

import numpy as np

from pygrid_tpu import telemetry
from pygrid_tpu.serving import pagedkv
from pygrid_tpu.serving.programs import (
    ProgramSet,
    prompt_buckets,
    width_buckets,
)
from pygrid_tpu.utils import exceptions as E

logger = logging.getLogger(__name__)

#: occupancy histogram bucket bounds: one bucket per live-slot count
#: (the seconds ladder the bus defaults to is wrong for small integers)
_OCCUPANCY_BOUNDS = [float(i) for i in range(1, 17)]

#: blocks-per-request histogram bounds: a pages ladder, not seconds
_BLOCKS_BOUNDS = [1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0]


@dataclass(frozen=True)
class EngineConfig:
    """Engine shape knobs. ``slot_buckets`` are decode widths to compile
    (always topped up with ``max_slots``); prompt buckets derive from
    the model's ``max_len`` (see :func:`programs.prompt_buckets`).

    Paged-KV knobs (docs/SERVING.md): ``block_size`` is the KV page
    in tokens (``PYGRID_KV_BLOCK``, default 64, power-of-two-bucketed);
    ``num_blocks`` overrides the pool size directly, else
    ``kv_budget_bytes`` sizes it, else the pool holds ``max_slots``
    full-length requests (``max_slots`` × pages-per-slot + trash);
    ``kv_overcommit`` bounds how far QUEUED worst-case block demand may
    run past the pool before enqueue answers busy — block exhaustion,
    not slot exhaustion, is the admission limit. Per-model admission
    weights for the node-wide device budget live on the
    :class:`~pygrid_tpu.serving.pagedkv.DeviceBudget`
    (``PYGRID_KV_WEIGHTS``), not here — one EngineConfig is shared by
    every hosted model, so a per-model weight cannot ride on it."""

    max_slots: int = 8
    slot_buckets: tuple[int, ...] = (1, 4, 8)
    min_prompt_bucket: int = 16
    max_queue: int = 64
    quantum: int = 8
    default_timeout_s: float = 300.0
    compute_dtype: Any = None
    cache_dtype: Any = None
    block_size: int | None = None
    num_blocks: int | None = None
    kv_budget_bytes: int | None = None
    kv_overcommit: float = 4.0
    #: fused multi-step decode: run ``quantum`` paged decode steps in
    #: ONE lax.scan program when no admission is pending (default on;
    #: ``PYGRID_FUSED_DECODE=off``) — kills per-step host dispatch
    fused: bool | None = None


class _Row:
    """One sequence occupying (or waiting for) one slot — one row of a
    client's [B, P] prompt."""

    __slots__ = (
        "pending", "row", "batch", "prompt", "n_new", "temperature",
        "seed", "keys", "out", "scheduled", "enqueued_at", "admitted_at",
        "pages", "shared_pages", "start", "demand", "denoising_steps",
        "blk_pos", "blk_left", "blk_forward", "blk_tail", "blk_tokens",
        "blk_step", "reveal", "dropped",
    )

    def __init__(
        self, pending, row, batch, prompt, n_new, temperature, seed,
        denoising_steps=None,
    ):
        self.pending = pending
        self.row = row
        self.batch = batch
        self.prompt = prompt  # np int32 [P]
        self.n_new = n_new
        self.temperature = temperature
        self.seed = seed  # resolved (never None when sampling)
        #: np uint32 [n_new, 2] when sampling — derived lazily on the
        #: ENGINE thread at admission (PRNGKey/split are device calls;
        #: they must not run on an enqueueing event-loop thread)
        self.keys = None
        #: tokens that have ARRIVED on the host, and how many launched
        #: programs will have made (``scheduled`` >= ``len(out)``): the
        #: loop plans by the second, a client is answered from the first
        self.out: list[int] = []
        self.scheduled = 0
        self.enqueued_at = time.perf_counter()
        self.admitted_at: float | None = None
        #: paged-KV bookkeeping — the row's block-table pages in page
        #: order (shared prefix first), how many of them are shared,
        #: the block-aligned prefix length, and the worst-case page
        #: demand charged against the pool at enqueue
        self.pages: list[int] | None = None
        self.shared_pages = 0
        self.start = 0
        self.demand = 0
        #: a block family's row: how many denoising forwards reveal a
        #: whole block. What is PLANNED, as ``scheduled`` is: the block
        #: the next forward runs (its first position, the masked
        #: positions the launched forwards leave in it: none once it is
        #: whole, and the next forward then opens the block after it and
        #: carries this one's commit; the next forward's index in it)
        #: and the prompt's tail, which the row's first forward brings.
        #: What has ARRIVED, as ``out`` has: the
        #: block being read (its tokens, the forward that revealed
        #: each) and, beside ``out``, the forward that revealed each
        #: token, with the (token, forward) pairs the last block made
        #: past ``n_new``
        self.denoising_steps = denoising_steps
        self.blk_pos = 0
        self.blk_left = 0
        self.blk_forward = 0
        self.blk_tail = None
        self.blk_tokens = None
        self.blk_step = None
        self.reveal: list[int] = []
        self.dropped: list[tuple[int, int]] = []


class _Pending:
    """One client request: B rows + the future their reassembled
    [B, n_new] tokens resolve. ``request_id`` names the request in
    engine snapshots and flight-recorder crash dumps."""

    def __init__(self, batch: int, n_new: int) -> None:
        import uuid

        self.request_id = uuid.uuid4().hex[:16]
        self.future: Future = Future()
        self.tokens = np.zeros((batch, n_new), np.int32)
        #: what a family answers beside the tokens, a list a name with
        #: one entry a row
        self.extras: dict[str, list] = {}
        self.remaining = batch

    def finish_row(self, row: int, toks: list[int], **extras) -> None:
        """The row's tokens and, where its family names more, each
        further field's row. The future resolves to the tokens alone, or
        to a mapping ``{"tokens": ..., name: [B, ...]}``."""
        self.tokens[row] = toks
        for name, value in extras.items():
            rows = self.extras.setdefault(name, [None] * len(self.tokens))
            rows[row] = value
        self.remaining -= 1
        if self.remaining == 0 and not self.future.done():
            # done() covers both a waiter's cancel AND a racing
            # _fail_all that already set an exception
            self.future.set_result(
                {
                    "tokens": self.tokens,
                    **{k: np.asarray(v, np.int32) for k, v in self.extras.items()},
                }
                if self.extras
                else self.tokens
            )


class _Arrival:
    """What one launched program will answer the host, and what it takes
    to hand that out once it is fetched: ``hand(fetched, seconds)``.
    ``rows`` are the rows that wait for it (``_fail_all`` fails them);
    ``decode`` tells a step or a scan from a prefill."""

    __slots__ = ("answer", "launched", "rows", "decode", "hand")

    def __init__(self, answer, launched, rows, decode, hand) -> None:
        self.answer = answer
        self.launched = launched
        self.rows = rows
        self.decode = decode
        self.hand = hand


class GenerationEngine:
    """Continuous-batching server for one (config, params) bundle."""

    def __init__(
        self,
        cfg,
        params,
        config: EngineConfig | None = None,
        model_id: str = "",
    ) -> None:
        import jax

        from pygrid_tpu.models import decode

        self.cfg = cfg
        self.model_id = model_id
        self.config = config or EngineConfig()
        self.params = params
        #: the module that serves this config's family (its cache, its
        #: prefill and decode step, and what it keeps per slot)
        self._family = decode.family_of(cfg)
        #: a recurrent state beside the K/V pages: observed, not a knob.
        #: It switches off exactly one thing, prefix sharing: a page of
        #: K/V can be mapped into a second request, the state that ran
        #: over the same tokens cannot
        self._recurrent = bool(self._family.RECURRENT)
        #: positions a row's forward carries (1: a token a row a step)
        self._block_len = int(self._family.BLOCK_LEN)
        # the fused scan carries one token a row from step to step; a
        # family that says BLOCK_LEN > 1 has its own step program and no
        # scan over it
        self._fused = (
            pagedkv.fused_enabled(self.config.fused) and self._block_len == 1
        )
        self.programs = ProgramSet(
            cfg,
            compute_dtype=self.config.compute_dtype,
            cache_dtype=self.config.cache_dtype,
            model_id=model_id,
        )
        self._prompt_buckets = prompt_buckets(
            cfg.max_len, self.config.min_prompt_bucket
        )
        self._widths = width_buckets(
            self.config.max_slots, self.config.slot_buckets
        )
        self._kv_dtype = (
            self.config.cache_dtype
            if self.config.cache_dtype is not None
            else (
                self.config.compute_dtype
                if self.config.compute_dtype is not None
                # bf16 on TPU (decode is bandwidth-bound on the cache
                # sweep), f32 elsewhere — the parity tests pin both
                else pagedkv.default_cache_dtype()
            )
        )
        #: bytes a slot holds beside its K/V pages (0: the transformer)
        self._state_per_slot = pagedkv.state_bytes(cfg, 1, self._kv_dtype)
        self._block = pagedkv.resolve_block_size(
            cfg.max_len, self.config.block_size
        )
        #: whether any layer holds K/V pages: observed, as ``_recurrent``
        #: is. A family whose whole cache is per-slot state has a pool of
        #: the trash block alone and a table of one column that nothing
        #: reads: a free slot admits, and no page is counted anywhere
        self._paged = self.block_cost_bytes() > 0
        self._max_pages = -(-cfg.max_len // self._block) if self._paged else 1
        if not self._paged:
            num_blocks = 1
        elif self.config.num_blocks is not None:
            num_blocks = int(self.config.num_blocks)
        elif self.config.kv_budget_bytes is not None:
            # the trash block counts INSIDE the byte budget (same
            # accounting as DeviceBudget.blocks_for): an operator
            # sizing to available HBM must never be overshot. A
            # recurrent family's fixed state comes out of it first
            num_blocks = (
                int(self.config.kv_budget_bytes) - self._state_bytes()
            ) // self.block_cost_bytes()
        else:
            # room for every slot at full length; short requests free
            # what they don't use
            num_blocks = 1 + self.config.max_slots * self._max_pages
        self._num_blocks = max(2, num_blocks) if self._paged else 1
        self._pool = pagedkv.BlockPool(self._num_blocks)
        self._prefix = pagedkv.PrefixCache(
            self._pool, self._block, shareable=not self._recurrent
        )
        #: blocks given back to the device budget by live
        #: re-partitioning (shrink_blocks) — survives _fail_all's
        #: pool rebuild
        self._shrunk_blocks = 0
        #: host mirror of the device block table; rebuilt lazily
        #: (``_table``) after any admission/free edit
        self._table_np = np.zeros(
            (self.config.max_slots, self._max_pages), np.int32
        )
        self._table_dev = None
        self._table_dirty = True
        self._demand_pages = 0
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefix_tokens_saved = 0
        cache = self._family.init_paged_cache(
            cfg, self.config.max_slots, self._num_blocks,
            self._block, dtype=self._kv_dtype,
        )
        # held as separate refs: the jitted programs donate and return
        # them, and the engine swaps in the new buffers every call.
        # ``_state`` is what the family keeps per slot beside k/v/pos
        # (a recurrent state; nothing for the transformer): it rides
        # every program call, donated like the rest
        self._k, self._v, self._pos, *self._state = cache
        #: what a forward reads of the one before, on the device beside
        #: the cache: a causal family's last token a slot, a block
        #: family's block a slot (tokens, masked flags). The programs
        #: read and write it (``programs``), the host never builds a
        #: step's input from tokens it has fetched
        self._last = self._new_last()
        #: what launched programs will answer, oldest first, and when
        #: the newest fetch returned (engine thread only)
        self._arrivals: deque[_Arrival] = deque()
        self._fetched_at = 0.0
        #: rows that have left their slots (engine thread only)
        self._retired = 0
        #: whether decode attention reads live pages in place (the rule
        #: the decode programs themselves apply when they are traced)
        self._kv_kernel = self._family.kv_kernel(self._k, self._max_pages)
        self._fused_scans = 0
        self._fused_steps = 0
        self._fused_wasted = 0
        self._slots: list[_Row | None] = [None] * self.config.max_slots
        self._queue: deque[_Row] = deque()
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._running = True
        self._live = 0
        self._thread: threading.Thread | None = None
        self._requests = 0
        self._tokens_out = 0
        #: blocks withheld from the pool by fault injection
        #: (chaos_hold_blocks) — never visible to admission, always
        #: accounted for by ledger() so a forgotten hold reads as a leak
        self._chaos_blocks: list[int] = []
        #: the worker thread's phases (engine thread only)
        self._clock = telemetry.loopclock.LoopClock(
            "serving_loop_seconds_total", "engine."
        )
        self._weights_bytes = int(
            sum(leaf.nbytes for leaf in jax.tree.leaves(params))
        )
        from pygrid_tpu.utils import jaxenv

        device = jaxenv.device_info()
        #: what the engine's programs run on and in — stamped on every
        #: stats() row so a reader asserts the device and the dtypes
        #: instead of assuming them
        self._runs_on = {
            "platform": device["platform"],
            "device_kind": device["device_kind"],
            "device_count": device["count"],
            "kv_dtype": np.dtype(self._kv_dtype).name,
            # no cast configured = matmuls run in the params' dtype
            "compute_dtype": np.dtype(
                self.config.compute_dtype
                if self.config.compute_dtype is not None
                else jax.tree.leaves(params)[0].dtype
            ).name,
        }

    # ── client surface (any thread) ─────────────────────────────────────

    def enqueue(
        self,
        prompt: np.ndarray,
        n_new: int,
        temperature: float = 0.0,
        seed: int | None = None,
        denoising_steps: int | None = None,
    ) -> Future:
        """Queue a [B, P] int prompt for generation; resolves to int32
        tokens [B, n_new] (a block family: to a mapping that names
        ``reveal_step`` and the last block's dropped tail beside them).
        ``denoising_steps`` is a block family's: how many forwards
        reveal a whole block (a divisor of ``BLOCK_LEN``, which is the
        default); a causal family takes no notice of it. Raises
        :class:`ServerBusyError` when the queue is at depth — callers
        translate it to the typed wire error. Validation (shape, vocab
        range, cache caps, temperature/seed domains) is the caller's
        job: this is the hot path."""
        prompt = np.asarray(prompt, np.int32)
        batch, p_len = prompt.shape
        if self._block_len > 1:
            if denoising_steps is None:
                denoising_steps = self._block_len
            if (
                denoising_steps < 1
                or self._block_len % int(denoising_steps)
                or float(temperature) > 0.0
            ):
                raise E.PyGridError(
                    f"denoising_steps ({denoising_steps}) must divide the "
                    f"block length ({self._block_len}), and a block is "
                    "revealed greedily (temperature 0)"
                )
        if p_len + n_new > self.cfg.max_len:
            raise E.PyGridError(
                f"prompt ({p_len}) + n_new ({n_new}) exceeds max_len "
                f"({self.cfg.max_len})"
            )
        if batch > self.config.max_queue:
            # a batch that can never fit is a client defect, not
            # backpressure — ServerBusyError would invite infinite
            # retries against a permanent condition
            raise E.PyGridError(
                f"prompt batch of {batch} rows exceeds the engine queue "
                f"capacity ({self.config.max_queue})"
            )
        if float(temperature) > 0.0 and seed is None:
            # unseeded sampling must still vary across requests (plain
            # urandom here: key derivation happens on the engine thread)
            import os

            seed = int.from_bytes(os.urandom(4), "big")
        pending = _Pending(batch, n_new)
        rows = [
            _Row(
                pending, b, batch, prompt[b], n_new, float(temperature),
                seed, denoising_steps,
            )
            for b in range(batch)
        ]
        # worst-case page demand per row, credited with the pages the
        # prefix cache ALREADY holds for this prompt (a probe —
        # admission re-matches for real; an eviction in between just
        # parks the row until blocks free)
        pages_per_row = (
            -(-self._span(p_len, n_new) // self._block) if self._paged else 0
        )
        if pages_per_row > self._pool.usable:
            raise E.PyGridError(
                f"request needs {pages_per_row} KV blocks of "
                f"{self._block} tokens but the pool holds "
                f"{self._pool.usable} — prompt + n_new can never "
                "be cached"
            )
        if self._paged:
            for row in rows:
                row.demand = max(
                    1, pages_per_row - self._prefix.probe(row.prompt)
                )
        demand = sum(r.demand for r in rows)
        with self._work:
            if not self._running:
                raise E.PyGridError("generation engine is closed")
            if len(self._queue) + batch > self.config.max_queue:
                telemetry.incr(
                    "serving_requests_total", outcome="busy",
                    model=self.model_id,
                )
                raise E.ServerBusyError(
                    f"generation queue full ({len(self._queue)} rows "
                    f"queued, depth limit {self.config.max_queue}) — "
                    "retry later"
                )
            if self._demand_pages + demand > (
                self.config.kv_overcommit * self._pool.usable
            ):
                telemetry.incr(
                    "serving_requests_total", outcome="busy",
                    model=self.model_id,
                )
                raise E.ServerBusyError(
                    f"KV block pool exhausted ({self._demand_pages} "
                    f"pages of demand outstanding against "
                    f"{self._pool.usable} blocks, overcommit "
                    f"{self.config.kv_overcommit:g}) — retry later"
                )
            self._demand_pages += demand
            self._queue.extend(rows)
            self._requests += 1
            self._ensure_thread()
            self._work.notify()
        return pending.future

    def submit(
        self,
        prompt: np.ndarray,
        n_new: int,
        temperature: float = 0.0,
        seed: int | None = None,
        timeout: float | None = None,
        denoising_steps: int | None = None,
    ) -> np.ndarray:
        """Blocking :meth:`enqueue` — the WS handler's enqueue-and-await
        wrapper (handler threads wait here; the device loop stays on the
        engine thread)."""
        future = self.enqueue(
            prompt, n_new, temperature, seed, denoising_steps
        )
        try:
            return future.result(
                timeout if timeout is not None
                else self.config.default_timeout_s
            )
        except FutureTimeoutError:
            telemetry.incr(
                "serving_requests_total", outcome="timeout",
                model=self.model_id,
            )
            raise E.PyGridError(
                "generation timed out awaiting the batch engine"
            ) from None

    def stats(self) -> dict:
        """Live gauges for /metrics, /telemetry/serving, the dashboard,
        and the flight recorder: aggregate depth/occupancy plus per-slot
        row positions (request id, tokens emitted of n_new) so a crash
        dump names exactly which requests were where."""
        with self._lock:
            slots = [
                {
                    "slot": i,
                    "request_id": r.pending.request_id,
                    "row": r.row,
                    "position": len(r.out),
                    "n_new": r.n_new,
                    "prompt_len": len(r.prompt),
                }
                for i, r in enumerate(self._slots)
                if r is not None
            ]
            # dedup preserving order: a batch's rows share one request
            queued = list(
                dict.fromkeys(r.pending.request_id for r in self._queue)
            )
            live_rows = [r for r in self._slots if r is not None]
            alloc_pages = sum(
                len(r.pages) for r in live_rows if r.pages is not None
            )
            used_tokens = sum(
                len(r.prompt) + r.scheduled for r in live_rows
            )
            out = {
                "model_id": self.model_id,
                "queue_depth": len(self._queue),
                "live_slots": self._live,
                "max_slots": self.config.max_slots,
                "requests_total": self._requests,
                "tokens_total": self._tokens_out,
                "compiles_total": self.programs.compile_count(),
                "slots": slots,
                "queued_requests": queued,
                "fused": self._fused,
                # which decode-attention path the programs took (1: the
                # kernel reads live pages in place; 0: the gather)
                "kv_kernel": int(self._kv_kernel),
                # what the slots hold beside their K/V pages (a
                # recurrent family's fixed state; 0 for the transformer)
                "state_bytes": self._state_bytes(),
                "state_bytes_per_slot": self._state_per_slot,
                # what a decode step reads whatever the batch: the
                # parameters, once
                "weights_bytes": self._weights_bytes,
                **self._runs_on,
                "block_size": self._block,
                "kv_block_bytes": self.block_cost_bytes(),
                "kv_blocks_total": self._pool.usable,
                "kv_blocks_retired": self._pool.retired_count(),
                "kv_blocks_free": self._pool.free_count(),
                "kv_blocks_cached": self._prefix.block_count(),
                # cache-ONLY (reclaimable) blocks; a cached block shared
                # with a live request counts as used in the occupancy
                # gauges, not cached
                "kv_blocks_idle_cached": self._prefix.idle_block_count(),
                "kv_demand_pages": self._demand_pages,
                # internal fragmentation of the LIVE allocation:
                # allocated-but-unwritten token slots (page-tail
                # waste) over allocated token slots
                "kv_fragmentation": round(
                    1.0 - used_tokens / (alloc_pages * self._block), 4
                )
                if alloc_pages
                else 0.0,
                "prefix_hits": self._prefix_hits,
                "prefix_misses": self._prefix_misses,
                "prefix_tokens_saved": self._prefix_tokens_saved,
            }
            if self._fused:
                out.update(
                    {
                        "fused_scans": self._fused_scans,
                        "fused_steps": self._fused_steps,
                        "fused_wasted_steps": self._fused_wasted,
                    }
                )
            return out

    def ledger(self) -> dict:
        """Leak-ledger snapshot: where every usable KV block is right
        now, plus the drain invariant. After traffic drains (no queue,
        no live slots, no chaos holds) every block must be either free
        or parked in the prefix cache — ``free + cached == usable`` —
        or some failure path leaked a reference. This is the dynamic
        twin of the GL603 static discipline; the storm harness asserts
        ``balanced`` after every scenario."""
        with self._lock:
            queue_depth = len(self._queue)
            live = self._live
            pool = self._pool.ledger()
            cached = self._prefix.block_count()
            chaos = len(self._chaos_blocks)
            drained = (
                queue_depth == 0
                and live == 0
                and chaos == 0
                and self._demand_pages == 0
            )
            return {
                "model_id": self.model_id,
                "queue_depth": queue_depth,
                "live_slots": live,
                "demand_pages": self._demand_pages,
                "usable": pool["usable"],
                "free": pool["free"],
                "held": pool["held"],
                "cached": cached,
                "retired": pool["retired"],
                "chaos_held": chaos,
                # fixed per-slot state beside the pool: never allocated
                # or freed with traffic, so it is outside the balance
                "state_bytes": self._state_bytes(),
                "drained": drained,
                # not-drained engines are balanced as long as the pool's
                # own accounting closes; once drained the stronger
                # cache-only invariant must hold too
                "balanced": pool["balanced"]
                and (not drained or pool["free"] + cached == pool["usable"]),
            }

    # ── fault plane (pygrid_tpu/storm) ──────────────────────────────────

    def chaos_hold_blocks(self, n: int | None = None) -> int:
        """FAULT INJECTION: withdraw up to ``n`` free blocks (all of
        them when None) from the pool, starving admission the way a
        burst of long-context requests would. Returns how many are now
        held. Release with :meth:`chaos_release_blocks`; ledger() counts
        the holds so they can never masquerade as a clean drain."""
        grabbed: list[int] = []
        while n is None or len(grabbed) < n:
            got = self._pool.alloc(1)
            if got is None:
                break
            grabbed.extend(got)
        with self._lock:
            self._chaos_blocks.extend(grabbed)
            return len(self._chaos_blocks)

    def chaos_release_blocks(self) -> int:
        """Undo :meth:`chaos_hold_blocks`; returns how many blocks went
        back to the pool."""
        with self._lock:
            held, self._chaos_blocks = self._chaos_blocks, []
        if held:
            self._pool.release(held)
            with self._work:
                self._work.notify_all()
        return len(held)

    def compile_count(self) -> int:
        return self.programs.compile_count()

    def block_cost_bytes(self) -> int:
        """Device bytes one of this engine's KV blocks costs."""
        return pagedkv.block_bytes(self.cfg, self._block, self._kv_dtype)

    def _state_bytes(self) -> int:
        """Device bytes of the per-slot state beside the block pool."""
        return self.config.max_slots * self._state_per_slot

    def shrink_blocks(self, n: int) -> int:
        """Give up to ``n`` KV blocks back to the node's device budget
        — live re-partitioning when another model registers against the
        same ``PYGRID_KV_BUDGET``. Only RECLAIMABLE blocks move: free
        blocks first, then idle-cached prefix entries are evicted to
        free more; a block held by a live request (or a prefix chain a
        live request still reads) is untouchable, so in-flight
        generations never fail. Returns the count actually retired.
        The device arrays stay allocated until the next cache
        reallocation (re-host or failure recovery) — the give-back is
        ADMISSION capacity first, bytes at the next rebuild
        (docs/SERVING.md §Live re-partitioning)."""
        if n <= 0:
            return 0
        retired = self._pool.retire(n)
        while retired < n and self._prefix.evict_one():
            retired += self._pool.retire(n - retired)
        with self._lock:
            self._shrunk_blocks += retired
        return retired

    def warmup(self, prompt_lens: tuple[int, ...] = ()) -> None:
        """Compile AND execute the decode width buckets (and the prompt
        buckets the given lengths land in) ahead of traffic, so the
        first real request pays admission latency, not XLA compiles.
        Must run before serving traffic (it drives the device directly;
        with live slots it backs off to lazy compilation instead of
        racing the engine thread for the donated cache buffers). The
        block table is all zeros here, so every write it makes lands in
        the trash block — invisible to every later request."""
        import jax.numpy as jnp

        with self._lock:
            if self._live > 0 or self._queue:
                return
        if (
            self._paged and not self._kv_kernel
            and self._runs_on["platform"] == "tpu"
        ):
            logger.warning(
                "engine %s: decode attention takes the gather path on "
                "this TPU (pool %s %s does not tile for the paged kernel)",
                self.model_id, self._k.dtype, tuple(self._k.shape),
            )

        zero_key = jnp.zeros((2,), jnp.uint32)
        seen = set()
        for p_len in prompt_lens or (1,):
            bucket = self._prompt_bucket(p_len)
            if bucket in seen:
                continue
            seen.add(bucket)
            self._call(
                self.programs.paged_prefill(bucket), jnp.int32(0),
                jnp.zeros((bucket,), jnp.int32), jnp.int32(0),
                jnp.int32(1), jnp.float32(0.0), zero_key,
            )
        for w in self._widths:
            if self._block_len > 1:
                # a block family's one program a width; with an all-zero
                # table every row writes to trash and nothing advances
                self._call(
                    self.programs.paged_block_step(w),
                    jnp.zeros((w, self._block_len), jnp.int32),
                    jnp.zeros((w,), jnp.int32), jnp.zeros((w,), bool),
                )
                continue
            self._call(
                self.programs.paged_decode(w),
                jnp.zeros((w,), jnp.float32), jnp.zeros((w, 2), jnp.uint32),
            )
            if self._fused:
                # zero budgets: every row frozen, nothing advances
                self._call(
                    self.programs.paged_decode_fused(w, self.config.quantum),
                    jnp.zeros((w,), jnp.int32), jnp.zeros((w,), jnp.float32),
                    jnp.zeros((self.config.quantum, w, 2), jnp.uint32),
                )

    def close(self) -> None:
        """Stop the worker thread, which first collects what is in
        flight (a row whose every token is scheduled is answered, not
        failed); queued/live requests fail typed."""
        with self._work:
            self._running = False
            self._work.notify_all()
            thread = self._thread
        if thread is not None:
            thread.join(timeout=10)
            if thread.is_alive():
                # a jitted call outlasted the join (e.g. a huge lazy
                # compile) — the daemon thread will see _running=False
                # at its next loop check; don't race it for the slots
                logger.warning(
                    "engine %s thread still busy at close; pending "
                    "requests fail typed, thread exits at next step",
                    self.model_id,
                )
        self._fail_all(
            E.PyGridError("generation engine closed"), reset_cache=False
        )

    # ── the device loop (engine thread only) ────────────────────────────

    def _ensure_thread(self) -> None:
        """Under the lock: both callers (enqueue's ``with self._work``
        block) hold the engine lock while (re)spawning the worker."""
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop,
                name=f"pygrid-serving-{self.model_id or 'engine'}",
                daemon=True,
            )
            self._thread.start()

    def _loop(self) -> None:
        clock = self._clock
        while True:
            with self._work:
                while self._running and not self._queue and self._live == 0:
                    clock.enter("idle")
                    self._work.wait()
                running = self._running
            clock.flush()
            try:
                if running:
                    self._pass()
                else:
                    # what is in flight is answered before the thread
                    # leaves: close() fails only what is left
                    self._collect()
            except Exception as err:  # noqa: BLE001 — device-loop boundary
                # a program that raised at its call, or one whose error
                # surfaced when its answer was fetched, a dispatch later
                logger.exception("serving engine step failed")
                self._fail_all(
                    E.PyGridError(f"generation engine error: {err}")
                )
            if not running:
                clock.stop()
                return

    def _pass(self) -> None:
        """One pass of the loop: admissions, then a scan or up to a
        quantum of steps."""
        self._admit()
        if self._fused and self._live and not self._queue:
            # no admission pending: burn the whole quantum in ONE
            # compiled scan — rows finishing mid-scan freeze (wasted
            # steps accepted; zero dispatches saved per step is the
            # whole point)
            self._fused_scan()
        else:
            steps = 0
            while steps < self.config.quantum and self._live:
                freed = self._step()
                steps += 1
                if freed and self._queue:
                    break  # a slot opened and someone's waiting
        if not self._live:
            # nothing left to launch behind what is in flight: an answer
            # never waits for the next request
            self._collect()

    def _admit(self) -> None:
        import jax.numpy as jnp

        clock = self._clock
        while True:
            clock.enter("admit")
            with self._lock:
                if not self._queue:
                    return
                slot = next(
                    (i for i, r in enumerate(self._slots) if r is None),
                    None,
                )
                if slot is None:
                    return
                row = self._queue.popleft()
                self._slots[slot] = row
                self._live += 1
            if self._paged and not self._assign_pages(slot, row):
                # block pool exhausted even after prefix-cache
                # eviction: park the row at the queue HEAD (FIFO order
                # kept) until a completing request frees blocks — the
                # loop keeps stepping the live slots, so progress is
                # guaranteed
                with self._lock:
                    self._slots[slot] = None
                    self._live = max(0, self._live - 1)
                    self._queue.appendleft(row)
                return
            now = time.perf_counter()
            row.admitted_at = now
            telemetry.observe(
                "serving_queue_wait_seconds", now - row.enqueued_at
            )
            telemetry.incr("serving_admitted_total")
            if row.temperature > 0.0 and row.keys is None:
                row.keys = self._row_keys(
                    row.seed, row.row, row.batch, row.n_new
                )
            request_id = row.pending.request_id
            # where the prompt's whole blocks end: the prompt itself for
            # a causal family; a block family's prompt tail opens the
            # first generated block instead of being prefilled
            prompt_end = len(row.prompt) // self._block_len * self._block_len
            chunk_len = prompt_end - row.start
            bucket = self._prompt_bucket(chunk_len)
            padded = np.zeros(bucket, np.int32)
            padded[:chunk_len] = row.prompt[row.start : prompt_end]
            # tokens the prefill program computes over against the
            # prompt's own: the bucket's padding is real device work
            telemetry.incr_many(
                "serving_prefill_tokens_total", "kind",
                {"true": chunk_len, "padded": bucket},
            )
            if self._recurrent:
                # the prefill writes the slot's whole state
                telemetry.incr(
                    "serving_state_bytes_total",
                    self._state_per_slot, kind="written",
                )
            # the program's small arguments go to the device under
            # ``admit``: ``prefill`` begins at the program's call
            args = (
                jnp.int32(slot), jnp.asarray(padded),
                jnp.int32(row.start), jnp.int32(prompt_end),
                jnp.float32(row.temperature), self._key_for(row, 0),
            )
            fn = self.programs.paged_prefill(bucket)
            self._table()  # edited above: rebuilt here, under ``admit``
            clock.enter("prefill", request_id=request_id, bucket=bucket)
            launched = time.perf_counter()
            answer = self._call(fn, *args)
            clock.enter("admit")
            self._arrivals.append(_Arrival(
                answer, launched, [row], False,
                functools.partial(self._hand_prefill, slot, row),
            ))
            if self._paged:
                # publish the full-prompt pages for future prefix hits
                # (first prefill wins; a matched chain is only touched)
                # gridlint: disable-next=GL202 — PrefixCache takes its own lock; only the engine thread mutates it
                self._prefix.insert(row.prompt, row.pages)
            if self._block_len > 1:
                # no token comes of a block family's prefill: the row's
                # first block opens where the prompt's whole blocks end
                self._open_block(row, prompt_end)
            else:
                # the first token is on its way (a row that asked for
                # one leaves its slot to the next in the queue here)
                self._schedule(slot, row, 1)

    def _hand_prefill(self, slot: int, row: _Row, fetched, seconds) -> None:
        """A prefill's answer, fetched: its seconds, and a causal
        family's first token into the row."""
        telemetry.observe("serving_prefill_seconds", seconds)
        self._note_counted("prefill", fetched[1:])
        if self._block_len > 1:
            return
        telemetry.observe(
            "serving_ttft_seconds", time.perf_counter() - row.enqueued_at
        )
        self._emit(slot, row, int(fetched[0]))

    def _assign_pages(self, slot: int, row: _Row) -> bool:
        """Map ``row`` into the block pool: match the longest cached
        prompt prefix (refcounted, read-only — copy-on-write by the
        scatter discipline in ``models/decode.py``), then allocate
        private pages for the rest of prompt + n_new, evicting LRU
        prefix entries under pressure. False = pool exhausted, caller
        parks the row. Engine thread only."""
        total_pages = -(
            -self._span(len(row.prompt), row.n_new) // self._block
        )
        shared = self._prefix.match(row.prompt)
        need = total_pages - len(shared)
        priv = self._pool.alloc(need)
        # eviction only ever targets nodes whose block actually frees
        # (cache-only refs), so live-shared chains survive pressure and
        # every True strictly grows the free list — no drain, no spin
        while priv is None and self._prefix.evict_one():
            priv = self._pool.alloc(need)
        if priv is None:
            if shared:
                self._pool.release(shared)
            return False
        row.pages = shared + priv
        row.shared_pages = len(shared)
        row.start = len(shared) * self._block
        self._table_np[slot, :] = 0
        self._table_np[slot, : len(row.pages)] = row.pages
        self._table_dirty = True
        with self._lock:
            if shared:
                self._prefix_hits += 1
                self._prefix_tokens_saved += row.start
            else:
                self._prefix_misses += 1
        telemetry.incr(
            "serving_prefix_lookups_total",
            outcome="hit" if shared else "miss", model=self.model_id,
        )
        if shared:
            telemetry.incr(
                "serving_prefix_tokens_saved_total", row.start,
                model=self.model_id,
            )
        telemetry.observe(
            "serving_blocks_per_request", float(len(row.pages)),
            bounds=_BLOCKS_BOUNDS,
        )
        return True

    def _table(self):
        """The device block table, rebuilt from the host mirror after
        any admission/free edit. Engine thread only — the table is a
        plain (non-donated) argument, so the same device array serves
        every step between edits without a retrace."""
        if self._table_dirty or self._table_dev is None:
            import jax.numpy as jnp

            # a copy the host never writes again: the transfer may read
            # its source after this returns, and a program in flight
            # reads its table while the mirror is edited for the next
            self._table_dev = jnp.asarray(self._table_np.copy())
            self._table_dirty = False
        return self._table_dev

    def _live_snapshot(self) -> tuple[list[tuple[int, "_Row"]], int]:
        """(live (slot, row) pairs, covering width bucket) for one
        dispatch — shared by the per-step and fused-scan paths.
        Snapshot under the lock and never re-index self._slots after
        releasing it (a close() that outwaited its join could swap the
        list under us). Width 0 means nothing is live."""
        with self._lock:
            live = [
                (i, r) for i, r in enumerate(self._slots) if r is not None
            ]
        if not live:
            return [], 0
        return live, next(w for w in self._widths if w > live[-1][0])

    def _step(self) -> bool:
        """One batched forward over every live slot (a decode step; a
        block family's block step), launched; then the arrivals before
        it, collected. Returns True if any slot freed (a row's last
        token was scheduled and it left the batch)."""
        clock = self._clock
        clock.enter("build")
        live, width = self._live_snapshot()
        if not live:
            return False
        clock.annotate(path="step", width=width, live=len(live), steps=1)
        self._note_dispatch("step", width, live, 1)
        build = (
            self._token_inputs if self._block_len == 1
            else self._block_inputs
        )
        fn, inputs, take, made = build(width, live)
        retired = self._retired
        launched = time.perf_counter()
        # the ONE decode dispatch site of the per-step path, whatever
        # the family
        answer = self._call(fn, *inputs)
        self._arrivals.append(_Arrival(
            answer, launched, [row for _, row in live], True,
            functools.partial(self._hand_step, width, live, take),
        ))
        for (i, row), count in zip(live, made):
            self._schedule(i, row, count)
        # this step stays in flight: what came before it is handed out
        # while the device runs it
        self._collect(keep=1)
        return self._retired > retired

    def _hand_step(self, width: int, live, take, fetched, seconds) -> None:
        """A step's answer, fetched: its seconds on the bus and what it
        yielded each row that was live in it."""
        telemetry.observe(
            "serving_dispatch_seconds", seconds, path="step",
            width=str(width),
        )
        for (i, row), tokens in zip(live, take(live, *fetched)):
            telemetry.observe("serving_token_seconds", seconds)
            self._emit(i, row, tokens)

    def _collect(self, keep: int = 0) -> None:
        """Fetch the oldest arrivals, until ``keep`` are left in flight,
        and hand each out. The device runs its programs in order, so a
        fetch waits for its own program and every one before it. An
        arrival's seconds run from the later of its launch and the fetch
        before it to its own fetch: the program's device time while the
        device is never idle, and over any stretch the seconds of all
        arrivals sum to at most the stretch, however many programs were
        queued at once."""
        clock = self._clock
        while len(self._arrivals) > keep:
            arrival = self._arrivals[0]
            clock.enter("fetch")
            fetched = [np.asarray(x) for x in arrival.answer]
            now = time.perf_counter()
            seconds = now - max(arrival.launched, self._fetched_at)
            self._fetched_at = now
            clock.enter("emit")
            # off the FIFO only once fetched: a fetch that raises leaves
            # the arrival's rows where _fail_all finds them
            self._arrivals.popleft()
            arrival.hand(fetched, seconds)

    def _call(self, fn, *inputs) -> tuple:
        """Launch one program on the engine's cache: the donated buffers
        in (``last`` after them), the returned ones in their place.
        Returns what the program answers before them, not fetched.
        Engine thread only — the cache buffers are single-writer."""
        *result, self._last = fn(
            self.params, self._k, self._v, self._pos, *self._state,
            self._last, self._table(), *inputs,
        )
        n = 3 + len(self._state)
        self._k, self._v, self._pos, *self._state = result[-n:]
        return tuple(result[:-n])

    def _schedule(self, slot: int, row: _Row, count: int) -> None:
        """``count`` more of the row's tokens are on their way (launched
        programs make them). With its last the row is retired: its slot
        freed, its pages released and its table row zeroed, for whatever
        is launched next."""
        row.scheduled += count
        if row.scheduled < row.n_new:
            return
        with self._lock:
            self._slots[slot] = None
            self._live = max(0, self._live - 1)
        self._release_row(slot, row)
        self._retired += 1

    def _token_inputs(self, width: int, live: list[tuple[int, "_Row"]]):
        """A decode step's program, its inputs after the table, how its
        answer reads, and the tokens it makes each live row: one. The
        step's tokens are the device's (``last``); a row's key is the
        one of the token being scheduled."""
        import jax.numpy as jnp

        temps = np.zeros(width, np.float32)
        keys = np.zeros((width, 2), np.uint32)
        for i, row in live:
            temps[i] = row.temperature
            if row.keys is not None:
                keys[i] = row.keys[row.scheduled]

        def take(live, toks, *counted):
            self._note_counted("step", counted)
            return [int(toks[i]) for i, _ in live]

        return (
            self.programs.paged_decode(width),
            (jnp.asarray(temps), jnp.asarray(keys)),
            take,
            [1] * len(live),
        )

    def _block_inputs(self, width: int, live: list[tuple[int, "_Row"]]):
        """A block step's program, its inputs after the table, how its
        answer reads, and the tokens it makes final for each live row.
        All of it from counts, nothing from an answer: every forward is
        a DENOISING forward that reveals ``BLOCK_LEN / denoising_steps``
        of its block's masked positions (or those that are left), and the
        one that reveals the last makes the block's tokens final. A row
        whose block that left whole opens the next block at once: this
        forward is that block's first and carries the COMMIT of the
        block before (its known tokens ride along, their K/V stand, the
        row's position moves on past them). The blocks are the device's
        (``last``); a row's first forward brings the prompt's tail, and
        a free slot inside the width runs a known block of zeros."""
        import jax.numpy as jnp

        L = self._block_len
        tail = np.zeros((width, L), np.int32)
        n_reveal = np.zeros(width, np.int32)
        commit = np.zeros(width, bool)
        plans, made = [], []
        for i, row in live:
            tail[i] = -1 if row.blk_tail is None else row.blk_tail
            row.blk_tail = None
            if row.blk_left == 0:
                commit[i] = True
                row.blk_pos += L
                row.blk_left = L
                row.blk_forward = 0
            n_reveal[i] = n = min(L // row.denoising_steps, row.blk_left)
            row.blk_left -= n
            whole = row.blk_left == 0
            plans.append((row.blk_forward, row.blk_pos, whole))
            row.blk_forward += 1
            first, end = len(row.prompt), len(row.prompt) + row.n_new
            made.append(
                min(row.blk_pos + L, end) - max(row.blk_pos, first)
                if whole else 0
            )
        telemetry.incr(
            "serving_block_forwards_total", len(live), kind="denoise"
        )
        telemetry.incr_many(
            "serving_block_positions_total", "kind",
            {"denoise": L * len(live), "commit": L * int(commit.sum())},
        )

        def take(live, toks, chosen, *counted):
            self._note_counted("step", counted)
            return [
                self._close_forward(row, toks[i], chosen[i], plan)
                for (i, row), plan in zip(live, plans)
            ]

        return (
            self.programs.paged_block_step(width),
            tuple(map(jnp.asarray, (tail, n_reveal, commit))),
            take,
            made,
        )

    @staticmethod
    def _note_counted(path: str, counted) -> None:
        """What a family's program counted of one forward and answered
        beside its tokens (nothing, for a family with neither experts
        nor a chunked recurrence), under the path that ran it: first the
        bytes of expert weights it had to read (touched (layer, expert)
        pairs x one expert's matrices); then, where the family holds a
        share of its experts, the assignments its routers made and those
        that fell on an expert held here. A pair is no expert's: it is
        what a prefill's chunked recurrence held and ran."""
        for counts in counted:
            read, *rows = np.ravel(counts)
            if len(rows) == 1:
                # a PAIR is a chunked recurrence's prefill: the chunks
                # that held the prompt's own positions, the chunks run
                telemetry.incr_many(
                    "serving_retention_chunks_total", "kind",
                    {"true": float(read), "computed": float(rows[0])},
                )
                continue
            telemetry.incr(
                "serving_expert_bytes_total", float(read), kind="read",
                path=path,
            )
            if rows:
                telemetry.incr_many(
                    "serving_expert_rows_total", "kind",
                    {"routed": float(rows[0]), "held": float(rows[1])},
                )

    def _open_block(self, row: _Row, pos: int) -> None:
        """Start the row's first block at position ``pos``: what of it
        is prompt is known (the tail its first forward brings), the rest
        masked. A later block starts wholly masked: each of its
        positions is revealed, token and forward, before it is read, so
        ``blk_tokens`` and ``blk_step`` need no opening again."""
        at = pos + np.arange(self._block_len)
        known = at < len(row.prompt)
        row.blk_pos = pos
        row.blk_left = int((~known).sum())
        row.blk_forward = 0
        row.blk_tail = np.where(
            known, row.prompt[np.minimum(at, len(row.prompt) - 1)], -1
        ).astype(np.int32)
        row.blk_tokens = row.blk_tail.copy()
        row.blk_step = np.full(self._block_len, -1, np.int32)

    def _close_forward(self, row: _Row, toks, chosen, plan) -> tuple:
        """Read one forward's answer into the row's block as the plan it
        was built by says (the forward's index in its block, the block's
        position and whether it reveals the block's last masked
        position); returns the tokens the forward yields the row, in
        position order: the block's own once it is whole (those past
        ``n_new`` go to ``row.dropped``), none before."""
        forward, pos, whole = plan
        row.blk_tokens[chosen] = toks[chosen]
        row.blk_step[chosen] = forward
        if not whole:
            return ()
        at = pos + np.arange(self._block_len)
        end = len(row.prompt) + row.n_new
        past = at >= end
        kept = (at >= len(row.prompt)) & ~past
        row.reveal.extend(row.blk_step[kept].tolist())
        row.dropped.extend(
            zip(row.blk_tokens[past].tolist(), row.blk_step[past].tolist())
        )
        if not row.out:
            telemetry.observe(
                "serving_ttft_seconds", time.perf_counter() - row.enqueued_at
            )
        return tuple(row.blk_tokens[kept].tolist())

    def _fused_scan(self) -> None:
        """Up to ``quantum`` decode steps for every live slot in ONE
        compiled program (``programs.paged_decode_fused``): per-row
        token budgets freeze finished rows inside the scan (their
        writes trash-route, their position parks), the emitted
        [steps, w] matrix drains into pendings afterwards. Host cost
        per quantum: one dispatch + one device→host token fetch,
        instead of ``quantum`` of each. A scan is collected as soon as
        it is launched, behind whatever was in flight before it (an
        admission's first token, a step's). Engine thread only."""
        import jax.numpy as jnp

        clock = self._clock
        clock.enter("build")
        live, width = self._live_snapshot()
        if not live:
            return
        steps = self.config.quantum
        clock.annotate(
            path="fused", width=width, live=len(live), steps=steps
        )
        temps = np.zeros(width, np.float32)
        budget = np.zeros(width, np.int32)
        keys = np.zeros((steps, width, 2), np.uint32)
        counts = []
        for i, row in live:
            temps[i] = row.temperature
            budget[i] = row.n_new - row.scheduled
            take = min(steps, int(budget[i]))
            counts.append(take)
            if row.keys is not None:
                keys[:take, i] = row.keys[row.scheduled : row.scheduled + take]
        launched = time.perf_counter()
        answer = self._call(
            self.programs.paged_decode_fused(width, steps),
            jnp.asarray(budget), jnp.asarray(temps), jnp.asarray(keys),
        )
        self._note_dispatch("fused", width, live, steps)
        self._arrivals.append(_Arrival(
            answer, launched, [row for _, row in live], True,
            functools.partial(self._hand_scan, width, steps, live, counts),
        ))
        for (i, row), count in zip(live, counts):
            self._schedule(i, row, count)
        wasted = steps * len(live) - sum(counts)
        with self._lock:
            self._fused_scans += 1
            self._fused_steps += steps
            self._fused_wasted += wasted
        telemetry.incr("serving_fused_scans_total", model=self.model_id)
        telemetry.incr(
            "serving_fused_steps_total", steps, model=self.model_id
        )
        if wasted:
            telemetry.incr(
                "serving_fused_wasted_steps_total", wasted,
                model=self.model_id,
            )
        self._collect()

    def _hand_scan(
        self, width: int, steps: int, live, counts, fetched, seconds
    ) -> None:
        """A scan's answer, fetched: its seconds on the bus and each
        row's first ``count`` tokens of the [steps, w] matrix (a frozen
        row's tail is its last token repeated)."""
        toks, *counted = fetched
        self._note_counted("fused", counted)
        telemetry.observe(
            "serving_dispatch_seconds", seconds, path="fused",
            width=str(width),
        )
        for (i, row), count in zip(live, counts):
            for j in range(count):
                telemetry.observe("serving_token_seconds", seconds / steps)
                self._emit(i, row, int(toks[j, i]))

    def _note_dispatch(
        self,
        path: str,
        width: int,
        live: list[tuple[int, "_Row"]],
        steps: int,
    ) -> None:
        """One decode dispatch on the bus as it is built, BEFORE its
        tokens are scheduled (the rows still hold the lengths the
        program runs at): whether it goes out ahead of an earlier decode
        dispatch whose tokens the host had not fetched; the rows it
        carries; the row-steps it computes against those that belong to
        an occupied slot; and the KV pages its attention reads against
        the pages its block tables span. Its seconds follow when its
        answer is fetched (``_collect``)."""
        ahead = any(arrival.decode for arrival in self._arrivals)
        telemetry.incr(
            "serving_dispatches_total", ahead="yes" if ahead else "no"
        )
        telemetry.observe(
            "serving_batch_occupancy", float(len(live)),
            bounds=_OCCUPANCY_BOUNDS,
        )
        telemetry.incr_many(
            "serving_dispatch_rowsteps_total", "kind",
            {"live": len(live) * steps, "computed": width * steps},
        )
        if self._paged:
            table = width * self._max_pages * steps
            telemetry.incr_many(
                "serving_kv_pages_total", "kind",
                {
                    "read": (
                        self._kernel_pages(width, live, steps)
                        if self._kv_kernel
                        else table  # the gather reads whole tables
                    ),
                    "table": table,
                },
            )
        if self._recurrent:
            # a live row's state is read and written once a step (rows
            # the width computes beyond the live ones are not counted:
            # they are padding, as in ``rowsteps``)
            moved = len(live) * steps * self._state_per_slot
            telemetry.incr_many(
                "serving_state_bytes_total", "kind",
                {"read": moved, "written": moved},
            )

    def _kernel_pages(
        self, width: int, live: list[tuple[int, "_Row"]], steps: int
    ) -> int:
        """Pages ``paged_attention``'s kernel reads over one dispatch of
        ``steps`` steps, from the host's own counts: a live row's pages
        up to its length at each step (what is scheduled for it so far;
        parked once the row has its tokens, as its position is), one
        trash page for each free slot inside the width."""
        base = np.array([len(r.prompt) + r.scheduled for _, r in live])
        need = np.array([r.n_new - r.scheduled for _, r in live])
        lengths = base[:, None] + np.minimum(np.arange(steps), need[:, None])
        rows = self._max_pages * self._block
        pages = -(-np.minimum(lengths, rows) // self._block)
        return int(pages.sum()) + (width - len(live)) * steps

    def _emit(self, slot: int, row: _Row, token) -> None:
        """What one forward yielded a row, arrived on the host: a causal
        family's one token (an int), or the tuple of nought to
        ``BLOCK_LEN`` tokens a block family's forward made final. With
        its last token the row is answered (it left ``slot`` when that
        token was scheduled: ``_schedule``)."""
        tokens = token if isinstance(token, tuple) else (token,)
        if tokens:
            row.out.extend(tokens)
            with self._lock:
                # stats() reads this counter under the lock from other
                # threads — the engine thread must not += it lock-free
                self._tokens_out += len(tokens)
            telemetry.incr(
                "serving_tokens_total", len(tokens), model=self.model_id
            )
        if len(row.out) < row.n_new:
            return
        # a block family names, beside its tokens, the forward that
        # revealed each and what its last block made past n_new
        extras = {
            "reveal_step": row.reveal,
            "dropped_tokens": [t for t, _ in row.dropped],
            "dropped_reveal_step": [f for _, f in row.dropped],
        } if self._block_len > 1 else {}
        row.pending.finish_row(row.row, row.out, **extras)
        if row.pending.remaining == 0:
            telemetry.incr(
                "serving_requests_total", outcome="ok",
                model=self.model_id,
            )

    def _release_row(self, slot: int, row: _Row) -> None:
        """Return a retired row's pages to the pool (shared pages just
        decref — the prefix cache and other readers keep theirs), zero
        its table row so the freed slot's garbage decode writes land in
        trash instead of a possibly-reallocated block, and refund its
        enqueue-time demand. Engine thread only."""
        if row.pages is not None:
            self._pool.release(row.pages)
            row.pages = None
            self._table_np[slot, :] = 0
            self._table_dirty = True
        with self._lock:
            self._demand_pages = max(0, self._demand_pages - row.demand)
            row.demand = 0

    def _fail_all(self, err: Exception, reset_cache: bool = True) -> None:
        cache = None
        snapshot = None
        if reset_cache:
            # a failure path, not a clean close: capture the engine's
            # last state for the flight recorder BEFORE the slots are
            # wiped (the dump is the only record of who was in flight)
            snapshot = self.stats()
            # the failed program may have CONSUMED the donated cache
            # buffers before raising — reallocate so the engine serves
            # the next request instead of failing forever on deleted
            # arrays (skipped on close: no one decodes again). A live
            # re-partition (shrink_blocks) is REALIZED in bytes here:
            # the fresh arrays are sized to the shrunken pool, so the
            # budget give-back stops being merely logical at the first
            # cache reallocation
            with self._lock:
                if self._paged:
                    self._num_blocks = max(
                        2, self._num_blocks - self._shrunk_blocks
                    )
                self._shrunk_blocks = 0
            cache = self._family.init_paged_cache(
                self.cfg, self.config.max_slots, self._num_blocks,
                self._block, dtype=self._kv_dtype,
            )
            self._last = self._new_last()
        # rows that left their slots with tokens still in flight wait
        # in the FIFO alone: they fail with the rest
        in_flight = [row for arrival in self._arrivals for row in arrival.rows]
        self._arrivals.clear()
        with self._lock:
            rows = [r for r in self._slots if r is not None]
            rows.extend(self._queue)
            rows.extend(in_flight)
            self._queue.clear()
            self._slots = [None] * self.config.max_slots
            self._live = 0
            self._demand_pages = 0
            if cache is not None:
                # the whole cache: a recurrent family's state with it
                self._k, self._v, self._pos, *self._state = cache
        if reset_cache:
            # the device pool was reallocated: every cached prefix
            # block now names stale (zeroed) data — rebuild the
            # allocator and drop the prefix cache wholesale (engine
            # thread only; every request future already failed above)
            # _num_blocks was already rebased above (shrunk blocks
            # realized in the fresh arrays), so the new pool simply
            # matches the new device allocation
            # gridlint: disable-next=GL202 — engine-thread-confined swap, requests already failed
            self._pool = pagedkv.BlockPool(self._num_blocks)
            # gridlint: disable-next=GL202 — engine-thread-confined swap, requests already failed
            self._prefix = pagedkv.PrefixCache(
                self._pool, self._block,
                shareable=not self._recurrent,
            )
            # chaos holds named the OLD pool; releasing those ids
            # against the fresh allocator would be a refcount bug
            # gridlint: disable-next=GL202 — engine-thread-confined swap, requests already failed
            self._chaos_blocks = []
        else:
            # clean close: refcounts must balance exactly (the
            # leak test rides on this) — release each admitted
            # row's pages individually
            for row in rows:
                if row.pages is not None:
                    self._pool.release(row.pages)
                    row.pages = None
        self._table_np[:] = 0
        self._table_dirty = True
        failed: dict[int, str] = {}
        for row in rows:
            if id(row.pending) not in failed:
                failed[id(row.pending)] = row.pending.request_id
                if not row.pending.future.done():
                    row.pending.future.set_exception(err)
        if failed:
            telemetry.incr(
                "serving_requests_total", len(failed), outcome="error",
                model=self.model_id,
            )
        if snapshot is not None:
            snapshot["failed_request_ids"] = sorted(failed.values())
            try:
                telemetry.recorder.note(
                    "engine.fail_all", model=self.model_id, error=str(err),
                    failed=len(failed),
                )
                # the engine thread may write the dump synchronously: it
                # is already off every request path (all futures failed
                # above) — but a recorder failure (unwritable flight
                # dir, full disk) must not kill the worker thread too
                telemetry.recorder.dump(
                    "engine_fail_all", snapshot=snapshot, error=err,
                )
            except Exception:  # noqa: BLE001 — capture is best-effort
                logger.exception("flight-recorder capture failed")

    # ── helpers ─────────────────────────────────────────────────────────

    def _new_last(self):
        """What the programs carry beside the cache (``programs``): a
        causal family's last token a slot; a block family's block a
        slot, its tokens and which positions are masked."""
        import jax.numpy as jnp

        slots = self.config.max_slots
        if self._block_len == 1:
            return jnp.zeros((slots,), jnp.int32)
        block = (slots, self._block_len)
        return jnp.zeros(block, jnp.int32), jnp.zeros(block, bool)

    def _span(self, p_len: int, n_new: int) -> int:
        """Positions a row's pages must cover: prompt and new tokens, up
        to the end of the block the last one falls in."""
        return -(-(p_len + n_new) // self._block_len) * self._block_len

    def _prompt_bucket(self, p_len: int) -> int:
        for b in self._prompt_buckets:
            if p_len <= b:
                return b
        raise E.PyGridError(
            f"prompt length {p_len} exceeds model max_len "
            f"{self.cfg.max_len}"
        )

    @staticmethod
    def _row_keys(seed, row, batch, n_new):
        """Per-row PRNG key schedule matching ``generate()``: split the
        request key into one key per token. Single-row requests use the
        request key itself (the same schedule generate() draws from);
        multi-row prompts fold the row index in, so rows sample
        independently (distribution-identical to the single-request
        path, which shares one key across rows)."""
        import jax

        key = jax.random.PRNGKey(int(seed))
        if batch > 1:
            key = jax.random.fold_in(key, row)
        return np.asarray(jax.random.split(key, n_new))

    def _key_for(self, row: _Row, index: int):
        import jax.numpy as jnp

        if row.keys is None:
            return jnp.zeros((2,), jnp.uint32)
        return jnp.asarray(row.keys[index])
