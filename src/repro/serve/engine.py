"""Continuous-batching serving engine on the MMU's paged KV cache.

The LLM mirror of the paper's multi-threaded AES pipeline (Fig 1/9/10):
token-by-token decode has a strict sequential dependence per request, so a
single stream leaves the pipeline idle — the engine fills the bubbles by
interleaving many concurrent requests (cThread streams) into one batched
decode step.  Admission is credit-based (page budget via the MMU), pages
are allocated on demand and freed at completion, and finished rows are
immediately replaced from the queue (continuous batching).

Hot-path invariants (the Coyote v2 "shell out of the datapath" story):

  * **Device-resident state.**  The KV pools, block tables, row lengths,
    last-sampled tokens, per-row temperatures, and the PRNG key all live
    on device.  Block tables are a cached :class:`DeviceBlockTable` view
    owned by the MMU — the table goes to the device in one transfer
    only when an alloc/extend/free/evict delta changes a sequence's
    mapping (i.e. on page-boundary crossings and slot churn), never on
    a step that changed nothing.
  * **Donation.**  ``decode_step_paged`` donates the pools and the
    decode-state buffers, so KV is updated in place instead of copied.
    ``self.pools`` / ``self.dev_lens`` / ``self.dev_tokens`` /
    ``self.rng`` must be reassigned from the step's return values every
    call — holding a stale reference to a donated buffer is an error.
    The block-table view is NOT donated (the cache reuses it).  The
    slot-state sync (``update_slot_state``) donates all six per-row state
    arrays and takes its delta as one packed transfer.
  * **One (B,) vector per step.**  Sampling (greedy argmax + Gumbel-max
    temperature) is fused inside the jitted step; the (B, vocab) logits
    tensor never leaves the device.  The only per-step host<->device
    traffic is reading back the (B,) int32 token vector.
  * **Batched prefill.**  All requests admitted in one ``_admit()`` pass
    run as a single padded forward (``prefill_shared_paged``), with
    suffix lengths and batch counts bucketed to powers of two to bound
    retraces.  Prompt pages the MMU mapped onto shared prefix pages are
    skipped entirely — only the uncovered suffix is computed.
  * **Non-blocking billing.**  Decode-step I/O is submitted to the shell
    scheduler asynchronously; credits settle at step boundaries
    (``_settle_io``) and ``flush_io()`` drains the tail, so in normal
    operation QoS accounting never stalls the decode loop.  The one
    intended exception is the scheduler's submitter-side back-pressure:
    a tenant whose pending I/O hits its bound stalls *itself* at submit
    (paper §7.2 containment) — that is the QoS design, not a hot-path
    regression.
  * **One compilation.**  ``decode_step_paged`` and ``update_slot_state``
    each trace exactly once per (engine shape, flags) across a run
    regardless of occupancy changes —
    ``repro.serve.paged_model.TRACE_COUNTS`` is the retrace guard.

Bench reproduction: ``PYTHONPATH=src python -m benchmarks.run --only
llm_serving`` (writes ``BENCH_serving.json``), or ``scripts/ci.sh`` for
the tier-1 smoke path plus the quick bench.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.faults import FaultKind
from repro.core.port import PortError
from repro.core.services.mmu import MMU, MMUConfig
from repro.kernels import resolve_use_pallas
from repro.serve.paged_model import (bucket_pages, decode_step_paged,
                                     flat_page_indices, gather_kv_pages,
                                     make_pools, pack_slot_delta,
                                     prefill_chunk_paged,
                                     prefill_shared_paged,
                                     scatter_kv_pages, update_slot_state)


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    temperature: float = 0.0          # 0 = greedy
    top_k: int = 0                    # 0 = disabled
    top_p: float = 1.0                # >= 1 = disabled
    tid: int = 0                      # submitting cThread
    priority: int = 0                 # scheduler priority (higher = sooner)
    deadline_s: Optional[float] = None  # absolute SLO deadline (perf_counter)
    out_tokens: List[int] = field(default_factory=list)
    t_submit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    done: bool = False
    # chunked-prefill cursor: -1 = not chunking; >= 0 = prompt tokens
    # whose KV is already in the pools (the row holds a slot + pages but
    # is NOT bound into the decode batch until its final chunk lands)
    prefill_pos: int = -1


def _bucket(n: int, cap: int) -> int:
    """Round up to a power of two (capped) so padded prefill shapes
    bucket into O(log) distinct compilations."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, mmu: MMU, *,
                 max_batch: int = 8, max_len: int = 1024,
                 use_pallas: Optional[bool] = None,
                 pages_per_block: Optional[int] = None, seed: int = 0,
                 shell=None, slot: int = 0, tenant: Optional[str] = None,
                 rid_base: int = 0, prefill_chunk: Optional[int] = None,
                 admit_window: int = 8, mesh=None, collectives=None):
        assert cfg.ssm is None and len(cfg.block_pattern) == 1, \
            "paged engine serves attention archs (docs/architecture.md)"
        self.cfg = cfg
        self.params = params
        self.mmu = mmu
        self.page = mmu.config.page_size
        self.max_batch = max_batch
        self.max_len = max_len
        self.max_pages = -(-max_len // self.page)
        # decode attention: the compiled Pallas kernel on a TPU, the XLA
        # reference elsewhere, unless the caller says otherwise
        self.use_pallas = use_pallas = resolve_use_pallas(use_pallas)
        self.pages_per_block = pages_per_block
        # chunked/streaming prefill: prompts whose uncovered suffix
        # exceeds ``prefill_chunk`` tokens are prefilled one chunk per
        # step, interleaved with decode, instead of one giant padded
        # forward that stalls every running row.  None = one-shot.
        self.prefill_chunk = prefill_chunk
        # head-of-line fix: how deep past a blocked queue head admission
        # may scan for smaller requests that DO fit the page budget
        # (per-tenant FIFO is always preserved)
        self.admit_window = admit_window
        # step-time EWMAs (SLO admission feasibility inputs): seconds
        # per prefilled prompt token, and seconds per fused decode step.
        # Samples are clamped against the running estimate so a JIT
        # recompile outlier cannot wreck the feasibility math.
        self.ewma_prefill_s_per_tok: Optional[float] = None
        self.ewma_decode_step_s: Optional[float] = None
        self.prefill_obs = 0
        self.decode_obs = 0
        self._ewma_alpha = 0.25
        # gateway hooks: ``admission_hook(engine)`` runs at the top of
        # every step (before ``_admit``) so a frontend can backfill the
        # queue at step granularity; ``token_sink(req, token, done)``
        # fires for every emitted token (prefill first-tokens included)
        self.admission_hook = None
        self.token_sink = None
        # Tensor-parallel serving (docs/sharding.md): a mesh with a
        # model axis > 1 shards weights and KV pools across its devices
        # while everything host-side — MMU, block table, pager, queue,
        # scheduler — stays logically single.  ``collectives`` routes
        # the per-layer partial-sum reductions through the shell's
        # CollectiveService port.
        self.mesh = mesh
        self.tp = None
        if mesh is not None and dict(mesh.shape).get("model", 1) > 1:
            from repro.serve.tp import TPContext
            self.tp = TPContext(cfg, mesh, params, page_size=self.page,
                                use_pallas=use_pallas,
                                pages_per_block=pages_per_block,
                                collectives=collectives)
            self.params = self.tp.params
        if self.tp is not None:
            self._decode_step = self.tp.decode_step
            self._prefill_shared = self.tp.prefill_shared
            self._prefill_chunk = self.tp.prefill_chunk
        else:
            self._decode_step = functools.partial(
                decode_step_paged, cfg=cfg, page_size=self.page,
                use_pallas=use_pallas, pages_per_block=pages_per_block)
            self._prefill_shared = functools.partial(
                prefill_shared_paged, cfg=cfg, page_size=self.page)
            self._prefill_chunk = functools.partial(
                prefill_chunk_paged, cfg=cfg, page_size=self.page)
        self.pools = make_pools(
            cfg, mmu.config.n_pages, self.page,
            kv_sharding=self.tp.kv_sharding if self.tp is not None
            else None)
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.queue: deque[Request] = deque()
        self._rng = np.random.RandomState(seed)     # host sampling oracle
        # request/sequence ids: ``rid_base`` namespaces the id range so
        # a migration destination adopting foreign rids (or shells whose
        # engines use per-tenant MMU instances) never collides in the
        # page tables.  NOTE: two paged engines must NOT share one MMU
        # instance — register_pager(owner=...) enforces it.
        self._rid_next = rid_base + 1
        self.completed: List[Request] = []
        self.steps = 0
        self.tokens_out = 0
        # prefix-sharing accounting: prompt tokens actually run through a
        # prefill forward vs tokens whose KV came from shared pages
        self.prefill_computed = 0
        self.prefill_skipped = 0
        # slot-state deltas pushed to the device (_sync_slot_state calls)
        self.slot_syncs = 0
        # Device-resident decode state: block tables (cached MMU view),
        # row lengths, last tokens, temperatures, PRNG key.
        self.block_table = mmu.block_table_device(
            max_batch, self.max_pages,
            sharding=self.tp.replicated if self.tp is not None else None)
        self.dev_lens = self._place(jnp.zeros((max_batch,), jnp.int32))
        self.dev_tokens = self._place(jnp.zeros((max_batch,), jnp.int32))
        self.dev_temps = self._place(jnp.zeros((max_batch,), jnp.float32))
        self.dev_topk = self._place(jnp.zeros((max_batch,), jnp.int32))
        self.dev_topp = self._place(jnp.ones((max_batch,), jnp.float32))
        # per-slot sequence ids: sampling keys are counter-based
        # fold_in(fold_in(rng, rid), token_index), so a request's
        # sampled stream is invariant to admission order, chunking, and
        # continuous-vs-wave scheduling (see sampler.fold_row_keys)
        self.dev_rids = self._place(jnp.zeros((max_batch,), jnp.int32))
        self.rng = self._place(jax.random.PRNGKey(seed))
        # Optional shell binding: decode-step I/O is then submitted through
        # the slot's unified Port (Port API v2) into the shell scheduler
        # (weighted credits + arbiter) instead of bypassing the shared
        # link — multi-tenant serving engines contend for bandwidth
        # exactly like any other vFPGA traffic.
        self.shell = shell
        self.slot = slot
        self.tenant = tenant
        self.io_bytes = 0
        self.io_failures = 0          # billed-IO futures that failed typed
        self._io_futs: List = []
        self.port = (shell.attach(slot, tenant=tenant)
                     if shell is not None else None)
        if shell is not None:
            shell.engines[slot] = self     # migrate() resolves us by slot
        # evict-with-copy: the MMU pager gathers a page's KV payload off
        # the device before recycling the page and scatters it back on
        # fault-in.  owner=self makes the one-pool-owner-per-MMU rule
        # explicit: a second engine on this MMU is refused at
        # construction, not discovered as silent KV corruption on evict.
        mmu.register_pager(self._pager_gather, self._pager_scatter,
                           owner=self)

    # --------------------------------------------------- TP placement ------
    def _place(self, arr):
        """Device-resident decode state: replicated across the TP mesh
        when sharded, plain single-device array otherwise."""
        if self.tp is not None:
            return jax.device_put(arr, self.tp.replicated)
        return jnp.asarray(arr)

    def _adopt_pools(self, pools):
        """Re-pin KV pools to the TP head-sharded layout after a scatter
        (GSPMD propagation normally preserves it; this makes the decode
        jit's input layout an invariant, not an inference)."""
        if self.tp is not None:
            pools = {s: jax.device_put(p, self.tp.kv_sharding)
                     for s, p in pools.items()}
        return pools

    # ------------------------------------------------- evict-with-copy -----
    def _pager_gather(self, ppage: int) -> Dict[str, np.ndarray]:
        """Copy one physical page's KV (all layers) to host — called by
        the MMU just before it recycles the device page."""
        flat = flat_page_indices([ppage], self.cfg.n_layers,
                                 self.mmu.config.n_pages)
        kv = gather_kv_pages(self.pools, flat)
        return {"k": np.asarray(kv["k"]), "v": np.asarray(kv["v"])}

    def _pager_scatter(self, ppage: int,
                       data: Dict[str, np.ndarray]) -> None:
        """Write a preserved page payload into a freshly mapped device
        page (MMU fault-back-in path)."""
        flat = flat_page_indices([ppage], self.cfg.n_layers,
                                 self.mmu.config.n_pages)
        self.pools = self._adopt_pools(scatter_kv_pages(
            self.pools, flat, {"k": jnp.asarray(data["k"]),
                               "v": jnp.asarray(data["v"])}))

    # -------------------------------------------------------------- API ----
    def submit(self, prompt: List[int], max_new_tokens: int = 16, *,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, tid: int = 0, priority: int = 0,
               deadline_s: Optional[float] = None) -> int:
        if prompt and (min(prompt) < 0 or max(prompt) >= self.cfg.vocab_size):
            # out-of-range ids would embed as NaN (XLA gathers fill OOB
            # reads) and silently poison the KV cache; fail at the door
            raise ValueError(
                f"prompt token out of range for vocab_size="
                f"{self.cfg.vocab_size}")
        health = getattr(self.shell, "health", None)
        if health is not None and health.is_quarantined(self.tenant):
            # graceful degradation: a repeatedly-faulting tenant is
            # rejected fast with a typed error, bystanders keep flowing
            health.record_rejection(self.tenant)
            raise PortError(
                f"tenant {self.tenant!r} is quarantined (repeated faults "
                "within the quarantine window); "
                "shell.health.unquarantine() to lift",
                kind=FaultKind.QUARANTINED, slot=self.slot,
                tenant=self.tenant, retryable=False)
        rid = self._rid_next
        self._rid_next += 1
        self.queue.append(Request(
            rid=rid, prompt=list(prompt), max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p, tid=tid,
            priority=priority, deadline_s=deadline_s,
            t_submit=time.perf_counter()))
        return rid

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def pending(self) -> bool:
        return self.active > 0 or bool(self.queue)

    # -------------------------------------------------------- admission ----
    def _ewma(self, prev: Optional[float], sample: float) -> float:
        """EWMA update with a 10x clamp against the running estimate so
        a one-off JIT-recompile outlier cannot poison feasibility math."""
        if prev is None:
            return sample
        a = self._ewma_alpha
        return (1 - a) * prev + a * min(sample, 10.0 * prev)

    def _admit(self) -> int:
        """Admit queued requests into free slots under the page budget;
        returns how many were admitted.

        The queue head no longer blocks everything behind it: when a
        request does not fit the remaining page credits, admission scans
        up to ``admit_window`` entries deep for smaller requests that DO
        fit, while skipping any request whose tenant (``tid``) already
        has a blocked one ahead of it — per-tenant FIFO order is never
        reordered, only independent tenants leapfrog a stuck head.
        """
        if not self.queue:
            return 0
        free = [i for i in range(self.max_batch) if self.slots[i] is None]
        if not free:
            return 0
        oneshot, taken, blocked = [], set(), set()
        qlist = list(self.queue)
        for qi, req in enumerate(qlist):
            if not free:
                break
            if blocked and qi >= self.admit_window:
                break                  # bounded skip-ahead exhausted
            if req.tid in blocked:
                continue               # preserve per-tenant FIFO
            plen = len(req.prompt)
            need = -(-(plen + req.max_new_tokens) // self.page)
            with TraceAnnotation("repro.mmu.admit", pages=need):
                # prefix-shared pages cost no new capacity: charge
                # admission credits only for the uncovered suffix
                probe = self.mmu.probe_prefix(req.prompt)
                need -= probe // self.page
                fits = need <= self.mmu.config.n_pages - (
                    self.mmu.utilization()["pages_used"])
                if fits:
                    i = free.pop(0)
                    # a row that will chunk-prefill must NOT publish its
                    # prompt pages into the prefix index yet: the pages
                    # exist at admission but their KV lands over later
                    # steps — a sharer admitted in between would read
                    # unwritten KV.  Publication happens when the final
                    # chunk lands (_prefill_chunks).
                    will_chunk = (self.prefill_chunk is not None
                                  and plen - probe > self.prefill_chunk)
                    covered = self.mmu.alloc_seq(req.rid, plen, slot=i,
                                                 prompt_tokens=req.prompt,
                                                 publish=not will_chunk)
            if not fits:
                blocked.add(req.tid)   # page credits exhausted for this
                continue               # size; try smaller ones behind it
            self.slots[i] = req
            taken.add(qi)
            if will_chunk:
                # long uncovered suffix: stream it chunk-by-chunk.  The
                # row holds its slot + pages but stays UNBOUND from the
                # decode batch until the final chunk samples its first
                # token — decode steps keep running at full speed.
                req.prefill_pos = covered
                self.prefill_skipped += covered
            else:
                self.block_table.bind(i, req.rid)
                qstart = covered if covered < plen else plen - 1
                self.prefill_computed += plen - qstart
                self.prefill_skipped += qstart
                oneshot.append((i, req, qstart, covered))
        if taken:
            self.queue = deque(r for qi, r in enumerate(qlist)
                               if qi not in taken)
        if oneshot:
            self._prefill_batch(oneshot)
        return len(taken)

    def _prefill_chunks(self) -> None:
        """Advance every chunk-prefilling row by ONE chunk.

        Intermediate chunks run through ``prefill_chunk_paged`` (KV
        writes only — no logits, no PRNG use), batched into one padded
        forward.  Rows whose remaining suffix now fits a single chunk
        take the normal ``_prefill_batch`` path, which samples their
        first token and binds them into the decode batch — from then on
        they are indistinguishable from one-shot admissions, which is
        why chunked and one-shot token streams match token-for-token.
        """
        rows = [(i, r) for i, r in enumerate(self.slots)
                if r is not None and r.prefill_pos >= 0]
        if not rows:
            return
        inter, finals = [], []
        for i, req in rows:
            if len(req.prompt) - req.prefill_pos <= self.prefill_chunk:
                finals.append((i, req))
            else:
                inter.append((i, req))
        if inter:
            with TraceAnnotation("repro.engine.prefill", kind="chunk",
                                 rows=len(inter),
                                 tokens=len(inter) * self.prefill_chunk):
                self._prefill_chunk_batch(inter)
        if finals:
            batch = []
            for i, req in finals:
                self.block_table.bind(i, req.rid)
                plen = len(req.prompt)
                qstart = req.prefill_pos
                self.prefill_computed += plen - qstart
                # write_from == qstart: every earlier position was
                # written by a previous chunk or a shared prefix page
                batch.append((i, req, qstart, qstart))
                req.prefill_pos = -1
            self._prefill_batch(batch)
            # every prompt position's KV is now resident: the deferred
            # prefix-index publication (alloc_seq publish=False) is safe
            for _, req in finals:
                self.mmu.publish_prefix(req.rid, req.prompt)

    def _prefill_chunk_batch(self, inter) -> None:
        """One padded ``prefill_chunk_paged`` forward that advances each
        of the rows ``inter`` (slot, request) by one full chunk."""
        t0 = time.perf_counter()
        n = len(inter)
        nb = _bucket(n, self.max_batch)
        chunk = self.prefill_chunk
        smax = max(len(r.prompt) for _, r in inter)
        maxp = max(self.max_pages,
                   -(-_bucket(smax, 1 << 30) // self.page))
        tables = np.full((nb, maxp), -1, np.int32)
        tables[:n] = self.mmu.block_table(
            [req.rid for _, req in inter], maxp)
        q_starts = np.zeros((nb,), np.int32)
        q_lens = np.zeros((nb,), np.int32)
        tokens = np.zeros((nb, chunk), np.int32)
        for j, (_, req, ) in enumerate(inter):
            q_starts[j] = req.prefill_pos
            q_lens[j] = chunk
            tokens[j] = req.prompt[req.prefill_pos:
                                   req.prefill_pos + chunk]
        self.pools = self._prefill_chunk(
            self.params, self.pools, jnp.asarray(tokens),
            jnp.asarray(q_lens), jnp.asarray(q_starts),
            jnp.asarray(tables))
        with TraceAnnotation("repro.engine.prefill_wait"):
            jax.block_until_ready(self.pools["k"])
        n_tok = n * chunk
        self.prefill_computed += n_tok
        self.ewma_prefill_s_per_tok = self._ewma(
            self.ewma_prefill_s_per_tok,
            (time.perf_counter() - t0) / n_tok)
        self.prefill_obs += 1
        for _, req in inter:
            # the chunk's KV just landed in pages allocated at
            # admission — dirty them NOW, not at alloc time, so a
            # pre-copy round between alloc and write can't clear
            # the flag before the content exists
            self.mmu.mark_dirty_range(req.rid, req.prefill_pos,
                                      req.prefill_pos + chunk)
            req.prefill_pos += chunk

    def _prefill_batch(self, rows) -> None:
        """One padded forward for a batch of prefill-finishing rows.

        ``rows`` are (slot, request, qstart, write_from): row j computes
        queries for ``prompt[qstart:]`` and scatters KV only at
        positions >= ``write_from`` (shared prefix pages and
        already-chunked positions are never rewritten).  One-shot
        admissions pass qstart = coverage (or len-1 when fully covered);
        final chunks pass qstart = write_from = their chunk cursor.
        Using ONE kernel for shared, unshared, and chunked rows is what
        makes the parity bit-exact — a row's ops depend only on its own
        tokens, absolute positions, and page bytes, so identical rows
        produce identical tokens whatever the rest of the wave skipped.
        Prefill accounting (prefill_computed/skipped) is the CALLER's
        job — chunked rows bill incrementally as chunks land.
        """
        with TraceAnnotation("repro.engine.prefill", kind="shared",
                             rows=len(rows)) as span:
            t0 = time.perf_counter()
            n = len(rows)
            nb = _bucket(n, self.max_batch)
            smax = max(len(r.prompt) for _, r, _, _ in rows)
            # prompts may exceed max_len (such requests finish right after
            # prefill): size the prefill tables for the longest prompt
            maxp = max(self.max_pages,
                       -(-_bucket(smax, 1 << 30) // self.page))
            temps = np.zeros((nb,), np.float32)
            topks = np.zeros((nb,), np.int32)
            topps = np.ones((nb,), np.float32)
            tables = np.full((nb, maxp), -1, np.int32)
            tables[:n] = self.mmu.block_table(
                [req.rid for _, req, _, _ in rows], maxp)
            q_starts = np.zeros((nb,), np.int32)
            q_lens = np.zeros((nb,), np.int32)
            write_from = np.zeros((nb,), np.int32)
            for j, (_, req, qstart, wfrom) in enumerate(rows):
                temps[j] = req.temperature
                topks[j] = req.top_k
                topps[j] = req.top_p
                q_starts[j] = qstart
                q_lens[j] = len(req.prompt) - qstart
                write_from[j] = wfrom
            span.set_metadata(tokens=int(q_lens.sum()))
            sb = _bucket(int(q_lens.max()), 1 << 30)
            tokens = np.zeros((nb, sb), np.int32)
            for j, (_, req, qstart, _) in enumerate(rows):
                tokens[j, :q_lens[j]] = req.prompt[qstart:]
            seq_ids = np.zeros((nb,), np.int32)
            for j, (_, req, _, _) in enumerate(rows):
                seq_ids[j] = req.rid
            first, self.pools, self.rng = self._prefill_shared(
                self.params, self.pools, jnp.asarray(tokens),
                jnp.asarray(q_lens), jnp.asarray(q_starts),
                jnp.asarray(write_from), jnp.asarray(tables), self.rng,
                jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(topps),
                jnp.asarray(seq_ids))
            with TraceAnnotation("repro.engine.prefill_wait"):
                first = np.asarray(first)
            now = time.perf_counter()
            for _, req, _, wfrom in rows:
                # prefill KV for [write_from, plen) just landed (pre-copy
                # dirty tracking; see _prefill_chunks)
                self.mmu.mark_dirty_range(req.rid, wfrom, len(req.prompt))
            self.ewma_prefill_s_per_tok = self._ewma(
                self.ewma_prefill_s_per_tok,
                (now - t0) / max(int(q_lens.sum()), 1))
            self.prefill_obs += 1
            slots_i, srows = [], []
            for j, (i, req, _, _) in enumerate(rows):
                tok = int(first[j])
                req.out_tokens.append(tok)
                req.t_first_token = now
                self.mmu.extend_seq(req.rid, 1, slot=i)
                self.tokens_out += 1
                if len(req.prompt) + 1 >= self.max_len:
                    # no decode budget left: complete straight from
                    # prefill
                    req.done = True
                    req.t_done = now
                    self.mmu.free_seq(req.rid)
                    self.block_table.unbind(i)
                    self.completed.append(req)
                    self.slots[i] = None
                    if self.token_sink is not None:
                        self.token_sink(req, tok, True)
                    continue
                if self.token_sink is not None:
                    self.token_sink(req, tok, False)
                slots_i.append(i)
                # write position of the NEXT decode step's token
                srows.append((len(req.prompt), tok, req.temperature,
                              req.top_k, req.top_p, req.rid))
            if slots_i:
                self._sync_slot_state(slots_i, srows)

    def _sync_slot_state(self, slots_i, rows) -> None:
        """Push slot-transition deltas into the device-resident state
        (admissions, frees, rows whose mapping changed, migration).
        ``rows`` is a list of (len, token, temperature, top_k, top_p,
        rid).  The delta crosses as one packed array into one compiled
        program, whatever the number of rows."""
        with TraceAnnotation("repro.engine.slot_sync", rows=len(slots_i)):
            packed = pack_slot_delta(self.max_batch, slots_i, rows)
            (self.dev_rids, self.dev_lens, self.dev_tokens, self.dev_temps,
             self.dev_topk, self.dev_topp) = update_slot_state(
                self.dev_rids, self.dev_lens, self.dev_tokens,
                self.dev_temps, self.dev_topk, self.dev_topp,
                self._place(packed))
            self.slot_syncs += 1

    def _sample(self, logits: np.ndarray, temperature: float,
                top_k: int = 0, top_p: float = 1.0) -> np.ndarray:
        """Host-side sampling oracle for the fused on-device sampler:
        vectorized Gumbel-max with the same top-k -> top-p filter rule
        (greedy at temperature <= 0)."""
        logits = logits[..., :self.cfg.vocab_size]
        if temperature <= 0:
            return np.argmax(logits, axis=-1)
        z = logits.astype(np.float64) / temperature
        v = z.shape[-1]
        if 0 < top_k < v:
            kth = np.sort(z, axis=-1)[..., -top_k][..., None]
            z = np.where(z < kth, -np.inf, z)
        if top_p < 1.0:
            srt = np.sort(z, axis=-1)[..., ::-1]
            ez = np.exp(srt - srt[..., :1])
            cum = np.cumsum(ez / ez.sum(axis=-1, keepdims=True), axis=-1)
            idx = np.minimum((cum < top_p).sum(axis=-1), v - 1)
            cutoff = np.take_along_axis(srt, idx[..., None], axis=-1)
            z = np.where(z < cutoff, -np.inf, z)
        u = np.clip(self._rng.random_sample(z.shape), 1e-12, 1 - 1e-12)
        g = -np.log(-np.log(u))
        return np.argmax(np.where(np.isfinite(z), z + g, -np.inf), axis=-1)

    # ------------------------------------------------------------ decode ----
    def step(self) -> int:
        """One continuous-batching engine step; returns tokens emitted.

        Each part of the step's host work runs inside a profiler span
        (``repro.*``, ``docs/tracing.md``); a span costs about a
        microsecond when no profiler is running."""
        with TraceAnnotation("repro.engine.step") as span:
            with TraceAnnotation("repro.health.beat"):
                if self.shell is not None:
                    health = getattr(self.shell, "health", None)
                    if health is not None:
                        health.beat(self.slot)  # watchdog: slot decoding
                self._settle_io()
            if self.admission_hook is not None:
                self.admission_hook(self)
            with TraceAnnotation("repro.engine.admit") as admit:
                admit.set_metadata(admitted=self._admit())
            self._prefill_chunks()
            # decode runs over BOUND rows only: chunk-prefilling rows hold
            # a slot + pages but emit nothing until their final chunk lands
            held = [i for i, r in enumerate(self.slots) if r is not None]
            live = [i for i in held if self.slots[i].prefill_pos < 0]
            emitted = self._decode(live) if live else 0
            span.set_metadata(live=len(held), emitted=emitted)
        return emitted

    def _decode(self, live: List[int]) -> int:
        """One fused decode step over the bound rows ``live``, then the
        host bookkeeping of its tokens; returns tokens emitted."""
        t0 = time.perf_counter()
        tables = self.block_table.device_view()
        # rows whose mapping changed (page crossing, eviction, fault-back)
        # re-sync lens/tokens from host truth, so device state can never
        # drift from the MMU even when a live row loses a page under
        # pressure.  Steady-state steps see no updated rows and skip this.
        upd = [i for i in self.block_table.last_updated_rows
               if self.slots[i] is not None
               and self.slots[i].prefill_pos < 0]
        if upd:
            self._sync_slot_state(
                upd,
                [(len(self.slots[i].prompt)
                  + len(self.slots[i].out_tokens) - 1,
                  self.slots[i].out_tokens[-1],
                  self.slots[i].temperature,
                  self.slots[i].top_k,
                  self.slots[i].top_p,
                  self.slots[i].rid) for i in upd])
        with TraceAnnotation("repro.engine.decode_pages") as span:
            # pages the kernel reads per layer (each row's context with
            # the token being appended) against the block table's width
            span.set_metadata(
                live_pages=sum(-(-(len(self.slots[i].prompt)
                                   + len(self.slots[i].out_tokens))
                                 // self.page) for i in live),
                table_pages=self.max_batch * self.max_pages)
        with TraceAnnotation("repro.engine.decode_dispatch"):
            next_toks, self.pools, self.dev_lens, self.rng = \
                self._decode_step(
                    self.params, self.pools, tables, self.dev_lens,
                    self.dev_tokens, self.rng, self.dev_temps,
                    self.dev_topk, self.dev_topp, self.dev_rids)
        self.dev_tokens = next_toks
        # the ONLY per-step device->host sync: the (B,) int32 token vector
        with TraceAnnotation("repro.engine.decode_wait"):
            toks = np.asarray(next_toks)
        self.ewma_decode_step_s = self._ewma(
            self.ewma_decode_step_s, time.perf_counter() - t0)
        self.decode_obs += 1
        self.steps += 1
        self._submit_step_io(n_live=len(live))

        # MMU and row state first, in one pass; then the tokens go out in
        # the same row order
        out: List[Tuple[Request, int]] = []
        freed = []
        with TraceAnnotation("repro.mmu.extend") as span:
            for i in live:
                req = self.slots[i]
                tok = int(toks[i])
                req.out_tokens.append(tok)
                self.mmu.extend_seq(req.rid, 1, slot=i)
                total = len(req.prompt) + len(req.out_tokens)
                if (len(req.out_tokens) >= req.max_new_tokens
                        or total >= self.max_len):
                    req.done = True
                    req.t_done = time.perf_counter()
                    self.mmu.free_seq(req.rid)
                    self.block_table.unbind(i)
                    self.completed.append(req)
                    self.slots[i] = None
                    freed.append(i)
                out.append((req, tok))
            span.set_metadata(rows=len(live), freed=len(freed))
        if self.token_sink is not None:
            for req, tok in out:
                self.token_sink(req, tok, req.done)
        if freed:
            self._sync_slot_state(freed, [(0, 0, 0.0, 0, 1.0, 0)] * len(freed))
        self.tokens_out += len(out)
        return len(out)

    # ---------------------------------------------------------- billing ----
    def _submit_step_io(self, n_live: int) -> None:
        """Bill this decode step's host I/O — one int32 token per live
        row is all that crosses the link — to our tenant through the
        slot's unified Port (``port.submit`` -> shell scheduler).
        Submission is async: the future is collected and settled at the
        next step boundary.  Only the scheduler's submitter back-pressure
        (tenant pending bound) can block here, which is the intended
        self-containment of an over-subscribed tenant."""
        if self.port is None or n_live == 0:
            return
        from repro.core.port import Invocation
        nbytes = n_live * 4
        self.io_bytes += nbytes
        fut = self.port.submit(Invocation.io(
            nbytes, tag="decode_io", tenant=self.tenant))
        self._io_futs.append(fut)

    def _settle_io(self) -> None:
        """Drop completed I/O futures (non-blocking settle)."""
        if self._io_futs:
            self._io_futs = [f for f in self._io_futs if not f.done()]

    def flush_io(self, timeout: float = 30.0, *,
                 strict: bool = False) -> bool:
        """Wait (bounded by one shared deadline) for outstanding billed
        I/O to clear the link.

        A future that FAILED with a typed ``PortError`` is settled — the
        error was already delivered and health-recorded by the port
        layer — and counted in ``io_failures``.  Futures that neither
        complete nor fail stay queued so accounting is never silently
        dropped.  Returns True when fully drained; a timeout is recorded
        as an ``io_flush_timeout`` health event when shell-bound, and
        ``strict=True`` raises it as a typed ``PortError`` instead of
        returning False."""
        deadline = time.perf_counter() + timeout
        remaining = []
        for fut in self._io_futs:
            left = deadline - time.perf_counter()
            try:
                comp = fut.completion(timeout=max(left, 0.0))
            except BaseException:  # noqa: BLE001 — typed failure: the
                self.io_failures += 1  # IO never cleared but is settled
                continue
            if comp is None and not fut.done():
                remaining.append(fut)
        self._io_futs = [f for f in remaining if not f.done()]
        if not self._io_futs:
            return True
        health = getattr(self.shell, "health", None)
        msg = (f"{len(self._io_futs)} decode-IO future(s) still pending "
               f"after {timeout}s on slot {self.slot}")
        if health is not None:
            health.record_fault(FaultKind.IO_FLUSH_TIMEOUT,
                                slot=self.slot, tenant=self.tenant,
                                site="engine.flush_io", strike=False,
                                msg=msg)
        if strict:
            raise PortError(msg, kind=FaultKind.IO_FLUSH_TIMEOUT,
                            slot=self.slot, tenant=self.tenant,
                            retryable=True)
        return False

    # ------------------------------------------- migration state (v2) ------
    @staticmethod
    def _req_to_dict(req: Request) -> Dict:
        return {"rid": req.rid, "prompt": list(req.prompt),
                "max_new_tokens": req.max_new_tokens,
                "temperature": float(req.temperature),
                "top_k": int(req.top_k), "top_p": float(req.top_p),
                "tid": req.tid, "priority": int(req.priority),
                "deadline_s": (None if req.deadline_s is None
                               else float(req.deadline_s)),
                "out_tokens": list(req.out_tokens),
                "t_submit": float(req.t_submit),
                "t_first_token": float(req.t_first_token)}

    @staticmethod
    def _req_from_dict(d: Dict) -> Request:
        dl = d.get("deadline_s")
        return Request(rid=int(d["rid"]), prompt=list(d["prompt"]),
                       max_new_tokens=int(d["max_new_tokens"]),
                       temperature=float(d["temperature"]),
                       top_k=int(d["top_k"]), top_p=float(d["top_p"]),
                       tid=int(d["tid"]),
                       priority=int(d.get("priority", 0)),
                       deadline_s=None if dl is None else float(dl),
                       out_tokens=list(d["out_tokens"]),
                       t_submit=float(d["t_submit"]),
                       t_first_token=float(d["t_first_token"]))

    def geometry(self) -> Dict[str, int]:
        """The shape contract a migration peer must match byte-for-byte:
        page geometry and the KV head layout of the pools."""
        return {"page_size": self.page,
                "n_layers": self.cfg.n_layers,
                "n_kv_heads": self.cfg.n_kv_heads,
                "head_dim": self.cfg.resolved_head_dim,
                "vocab_size": self.cfg.vocab_size}

    def snapshot_state(self, *, only_pages=None) -> Tuple[Dict, Dict]:
        """Freeze this engine's paged tenant state for migration.

        ``only_pages`` (a set of MMU share keys — ``("d", ppage)`` /
        ``("h", hslot)``) restricts the shipped PAYLOADS to that subset:
        pre-copy migrations pass the final dirty delta so the freeze
        gathers O(delta) pages instead of the whole KV footprint.  The
        header (page tables, requests, queue, PRNG) is always complete.

        Returns ``(header, arrays)``: a JSON-safe header (in-flight and
        queued requests, the MMU page-table snapshot, the gather order of
        the live pages, geometry) and an array pytree (the PRNG key, the
        device-side compact KV gather of every live page, preserved
        host-evicted page payloads).  The engine must be quiesced: no
        concurrent ``step()``.  Nothing here is pickled — the pair feeds
        ``repro.core.bitstream.encode("migration", ...)`` directly.
        """
        # rows still mid-chunk-prefill (no sampled token yet) are demoted
        # back to the queue: their partial KV is cheap to recompute and
        # carries no sampled state, so the destination just re-prefills —
        # token streams are unaffected (prefill is deterministic and the
        # PRNG is untouched until the first sample)
        reqs = [{"slot": i, **self._req_to_dict(r)}
                for i, r in enumerate(self.slots)
                if r is not None and r.prefill_pos < 0]
        demoted = [r for r in self.slots
                   if r is not None and r.prefill_pos >= 0]
        seq_ids = [r["rid"] for r in reqs]
        mmu_snap = self.mmu.snapshot_seqs(seq_ids)
        # dedupe: each physical page (device ppage / host slot) ships
        # ONCE however many sequences share it — restore_seqs rebuilds
        # the sharing from the per-seq page tables in ``mmu_snap``
        pages, host_pages = [], {}
        seen_pp = set()
        for sd in mmu_snap["seqs"]:
            for p in sd["pages"]:
                if p["on_host"]:
                    hs = int(p.get("host_slot", -1))
                    if (only_pages is not None and hs >= 0
                            and ("h", hs) not in only_pages):
                        continue
                    key = (f"h:{hs}" if hs >= 0
                           else f"u:{sd['seq_id']}:{p['vpage']}")
                    if key in host_pages:
                        continue
                    data = self.mmu.host_page_data(sd["seq_id"],
                                                   p["vpage"])
                    if data is not None:
                        host_pages[key] = {
                            "k": np.asarray(data["k"]),
                            "v": np.asarray(data["v"])}
                elif p["ppage"] not in seen_pp:
                    seen_pp.add(p["ppage"])
                    if (only_pages is not None
                            and ("d", p["ppage"]) not in only_pages):
                        continue
                    pages.append({"ppage": p["ppage"]})
        header = {
            "geometry": self.geometry(),
            "requests": reqs,
            "queue": [self._req_to_dict(r)
                      for r in list(demoted) + list(self.queue)],
            "mmu": mmu_snap,
            "pages": pages,          # gather order of kv_k/kv_v rows
        }
        arrays: Dict = {"rng": np.asarray(self.rng)}
        if pages:
            pps = [p["ppage"] for p in pages]
            L = self.cfg.n_layers
            if only_pages is not None:
                # latency-critical freeze window (pre-copy delta): pad
                # the gather to a power-of-two bucket so freezes with
                # slightly different delta sizes hit one compiled
                # gather instead of retracing inside the downtime gap;
                # the shipped arrays are trimmed back to the real count
                nb = bucket_pages(len(pps))
                flat = flat_page_indices(pps + [pps[-1]] * (nb - len(pps)),
                                         L, self.mmu.config.n_pages)
                kv = gather_kv_pages(self.pools, flat)

                def _trim(x):
                    x = np.asarray(x).reshape(L, nb, *x.shape[1:])
                    return np.ascontiguousarray(
                        x[:, :len(pps)]).reshape(L * len(pps),
                                                 *x.shape[2:])
                arrays["kv_k"] = _trim(kv["k"])
                arrays["kv_v"] = _trim(kv["v"])
            else:
                flat = flat_page_indices(pps, L, self.mmu.config.n_pages)
                kv = gather_kv_pages(self.pools, flat)
                arrays["kv_k"] = np.asarray(kv["k"])
                arrays["kv_v"] = np.asarray(kv["v"])
        if host_pages:
            arrays["host_pages"] = host_pages
        return header, arrays

    def restore_state(self, header: Dict, arrays: Dict, *,
                      staged=None) -> Dict[str, int]:
        """Adopt a migrated tenant: fresh page allocation on OUR MMU,
        block-table rebuild (dirty rows upload on the next view), KV
        payload scattered to the new physical pages, decode state synced,
        PRNG stream adopted.  In-flight requests land on their original
        slot index when free (keeps the sampled noise stream aligned
        row-for-row), else the first free slot.

        ``staged`` (pre-copy): ``{source share key: our ppage}`` of
        pages already filled by warm rounds — forwarded to
        ``MMU.restore_seqs`` so those mappings adopt the staged pages;
        the delta payloads in ``arrays`` then overwrite exactly the
        pages that changed after their last warm copy."""
        g = header["geometry"]
        mine = self.geometry()
        if g != mine:
            raise ValueError(
                f"migration geometry mismatch: snapshot {g} vs "
                f"destination {mine} — KV pages are not byte-compatible")
        reqs = header["requests"]
        free = [i for i in range(self.max_batch)
                if self.slots[i] is None]
        if len(reqs) > len(free):
            raise ValueError(
                f"destination engine has {len(free)} free slots for "
                f"{len(reqs)} in-flight migrated requests")
        mapping = self.mmu.restore_seqs(header["mmu"], slot=self.slot,
                                        staged=staged)
        # shared source pages restored to ONE destination page each:
        # index the new ppage by old device ppage / host slot so every
        # shipped payload (deduped at snapshot) scatters exactly once
        by_old, by_hslot, by_sv = {}, {}, {}
        for sid, pl in mapping.items():
            for p in pl:
                if p["was_host"]:
                    if p["host_slot"] >= 0:
                        by_hslot[p["host_slot"]] = p["new_ppage"]
                    by_sv[(sid, p["vpage"])] = p["new_ppage"]
                else:
                    by_old[p["old_ppage"]] = p["new_ppage"]
        n_pages = self.mmu.config.n_pages
        if header["pages"]:
            new_pps = [by_old[p["ppage"]] for p in header["pages"]]
            kk = np.asarray(arrays["kv_k"])
            vv = np.asarray(arrays["kv_v"])
            if staged is not None:
                # pre-copy delta restore runs inside the freeze window:
                # pad to the same power-of-two bucket as the snapshot
                # gather (pad = last real page repeated; duplicate
                # indices carry identical rows, so the extra scatter
                # writes are no-ops) to avoid a per-delta-size retrace
                L = self.cfg.n_layers
                nb = bucket_pages(len(new_pps))
                pad = nb - len(new_pps)
                if pad:
                    def _pad(x):
                        x = x.reshape(L, -1, *x.shape[1:])
                        x = np.concatenate(
                            [x, np.repeat(x[:, -1:], pad, axis=1)],
                            axis=1)
                        return x.reshape(L * nb, *x.shape[2:])
                    kk, vv = _pad(kk), _pad(vv)
                    new_pps = new_pps + [new_pps[-1]] * pad
            flat = flat_page_indices(new_pps, self.cfg.n_layers, n_pages)
            self.pools = self._adopt_pools(scatter_kv_pages(
                self.pools, flat, {"k": jnp.asarray(kk),
                                   "v": jnp.asarray(vv)}))
        for key, data in (arrays.get("host_pages") or {}).items():
            if key.startswith("h:"):
                new_pp = by_hslot[int(key[2:])]
            else:                       # "u:<sid>:<vpage>" legacy pages
                _, sid, vpage = key.split(":")
                new_pp = by_sv[(int(sid), int(vpage))]
            flat = flat_page_indices([new_pp], self.cfg.n_layers, n_pages)
            self.pools = self._adopt_pools(scatter_kv_pages(
                self.pools, flat, {"k": jnp.asarray(data["k"]),
                                   "v": jnp.asarray(data["v"])}))
        slots_i, rows = [], []
        for rd in reqs:
            req = self._req_from_dict(rd)
            want = int(rd.get("slot", -1))
            i = want if (0 <= want < self.max_batch
                         and self.slots[want] is None) else free[0]
            free.remove(i)
            self.slots[i] = req
            self.block_table.bind(i, req.rid)
            assert req.out_tokens, "in-flight request without prefill"
            slots_i.append(i)
            rows.append((len(req.prompt) + len(req.out_tokens) - 1,
                         req.out_tokens[-1], req.temperature,
                         req.top_k, req.top_p, req.rid))
        if slots_i:
            self._sync_slot_state(slots_i, rows)
        for rd in header["queue"]:
            self.queue.append(self._req_from_dict(rd))
        self.rng = self._place(jnp.asarray(arrays["rng"]))
        adopted = ([r["rid"] for r in reqs]
                   + [r["rid"] for r in header["queue"]])
        if adopted:
            self._rid_next = max(self._rid_next, max(adopted) + 1)
        return {"requests": len(reqs), "queued": len(header["queue"]),
                "pages": len(header["pages"])
                + len(arrays.get("host_pages") or {})}

    def reset_decode_state(self) -> None:
        """Cold-reset the engine's device-side soft state — the local
        analogue of restarting the slot's logic after a crash: a fresh
        block-table view, zeroed lens/tokens/sampling params, dropped
        billed-IO futures, full TLB flush.  KV pool *contents* are not
        touched: :meth:`restore_state` scatters the preserved page
        payloads back in right after, which is what makes a recovery
        KV-intact instead of a re-prefill."""
        self.block_table = self.mmu.block_table_device(
            self.max_batch, self.max_pages,
            sharding=self.tp.replicated if self.tp is not None else None)
        self.dev_lens = self._place(jnp.zeros((self.max_batch,), jnp.int32))
        self.dev_tokens = self._place(
            jnp.zeros((self.max_batch,), jnp.int32))
        self.dev_temps = self._place(
            jnp.zeros((self.max_batch,), jnp.float32))
        self.dev_topk = self._place(jnp.zeros((self.max_batch,), jnp.int32))
        self.dev_topp = self._place(jnp.ones((self.max_batch,), jnp.float32))
        self.dev_rids = self._place(jnp.zeros((self.max_batch,), jnp.int32))
        self._io_futs = []
        self.mmu.tlb.invalidate()

    def evacuate(self) -> Dict[str, int]:
        """Release the tenant's paged state AFTER a successful snapshot
        restore elsewhere: free every sequence on our MMU (returning the
        pages to the shared pool), unbind block-table rows, clear the
        run queue.  The engine stays usable for new work."""
        freed, n_seqs = [], 0
        for i, req in enumerate(self.slots):
            if req is not None:
                self.mmu.free_seq(req.rid)
                self.block_table.unbind(i)
                self.slots[i] = None
                freed.append(i)
                n_seqs += 1
        if freed:
            self._sync_slot_state(freed, [(0, 0, 0.0, 0, 1.0, 0)] * len(freed))
        n_q = len(self.queue)
        self.queue.clear()
        return {"seqs": n_seqs, "queued": n_q}

    def latency_stats(self) -> Dict[str, float]:
        """TTFT/TPOT percentiles over completed requests (milliseconds).

        TTFT = first sampled token's wall time minus ``t_submit``;
        TPOT = mean seconds per decode token after the first.  Both were
        always recorded per request (``t_submit``/``t_first_token``/
        ``t_done``) — this aggregates them into the p50/p99 view every
        serving paper quotes.
        """
        ttfts, tpots = [], []
        for r in self.completed:
            if r.t_first_token > 0 and r.t_submit > 0:
                ttfts.append(r.t_first_token - r.t_submit)
            n_dec = len(r.out_tokens) - 1
            if r.t_done > 0 and r.t_first_token > 0 and n_dec > 0:
                tpots.append((r.t_done - r.t_first_token) / n_dec)
        out: Dict[str, float] = {}
        if ttfts:
            out["ttft_p50_ms"] = float(np.percentile(ttfts, 50) * 1e3)
            out["ttft_p99_ms"] = float(np.percentile(ttfts, 99) * 1e3)
        if tpots:
            out["tpot_p50_ms"] = float(np.percentile(tpots, 50) * 1e3)
            out["tpot_p99_ms"] = float(np.percentile(tpots, 99) * 1e3)
        return out

    def run(self, max_steps: int = 10_000) -> Dict[str, float]:
        t0 = time.perf_counter()
        while self.pending() and self.steps < max_steps:
            self.step()
            # decode-step preemption checkpoint: when this loop is the
            # body of a long-running port invocation on a lane, yield to
            # higher-priority granted work between steps (no-op off-lane)
            if self.shell is not None:
                self.shell.scheduler.checkpoint(self.slot)
        drained = self.flush_io()
        dt = time.perf_counter() - t0
        stats = {"wall_s": dt, "engine_steps": self.steps,
                 "tokens": self.tokens_out,
                 "tokens_per_s": self.tokens_out / max(dt, 1e-9),
                 "completed": len(self.completed),
                 "prefill_computed": self.prefill_computed,
                 "prefill_skipped": self.prefill_skipped,
                 "slot_syncs": self.slot_syncs}
        stats.update(self.latency_stats())
        if self.shell is not None and self.tenant is not None:
            stats["io_drained"] = drained
            stats["io_pending"] = self.shell.scheduler.tenant_pending(
                self.tenant)
        return stats
