"""Generation engines.

`ContinuousEngine` is the request-centric serving core: one global
block-table KV page pool ([L, num_pages, page_size, G, dh] — int8 values
+ per-page scale planes for `kv_quant` configs) with a per-slot int32
page-table row mapping each slot's logical positions onto pool pages,
`submit()`/`step()` lifecycle, admission of a queued prompt into any slot
the step after its occupant hits EOS, and prefill of admitted prompts
chunked into the running decode loop so a long prompt never stalls other
slots for more than one chunk. Pages are refcounted (serving/pager.py):
a prompt whose prefix is already cached maps the shared pages READ-ONLY
into its table row and skips their prefill chunks entirely; a partially
matching page is COPY-ON-WRITE forked (one page copy) and prefill
resumes at the first divergent token. At prefix share 0 the gathered
logical buffer is element-identical to the old slot-contiguous cache, so
paged output stays bit-identical to the wave path. Both greedy and
sampled requests run here: each sampled request draws from its own PRNG
stream `fold_in(PRNGKey(seed), request_id)` advanced by a per-request
draw counter, so its tokens are bit-identical regardless of co-residents
(DESIGN.md §10).

`Engine` keeps the legacy wave surface: `generate()` is now a thin
compatibility wrapper that routes requests through a shared
`ContinuousEngine` whenever the config supports the paged path
(`model.supports_paged`: the dense and moe text families, including
sliding-window and int8-KV — greedy token output is identical to the
wave path, see tests/test_serving.py and tests/test_paged_families.py),
and falls back to fixed length-bucketed waves (`generate_wave`) for the
families without paged KV (M-RoPE, encdec, recurrent state).
`generate(..., continuous=False)` forces the legacy wave path, which
remains the parity baseline every serving bench compares against; wave
sampling draws from the same per-request `fold_in(PRNGKey(seed), rid)`
streams as the paged path (one shared split-per-step key historically
made wave draws depend on batch composition), so sampled output is also
path-identical.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import model, moe
from repro.serving.pager import PagePool, PoolStats, PrefixCache
from repro.serving.trace import NO_SPAN, TraceSink

# monotone engine-instance counter: the `src` tag on trace records, so
# replicas sharing one TraceSink never collide on request ids
_ENGINE_SEQ = [0]


@dataclass
class GenResult:
    """One finished generation: decoded token ids (including the EOS, if
    hit), the prompt length, and measured prefill / decode wall time
    attributed to this request. On the paged path `prefill_s` runs from
    the request's admission to its first token (the chunks of other
    requests and the decode steps between its own chunks included)."""
    tokens: List[int]
    prompt_len: int
    prefill_s: float = 0.0
    decode_s: float = 0.0

    @property
    def ttft_s(self) -> float:
        """Time to first token == the measured prefill time (the first
        token is drawn from the prefill logits)."""
        return self.prefill_s


@dataclass
class EngineEvent:
    """One request-visible state change from a `ContinuousEngine.step()`:
    kind is "admitted" (slot assigned, prefill starting), "token" (one new
    token id in `token`), "done" (`result` carries the GenResult), or
    "shed" (terminal refusal — `reason` says why, e.g. "oversize" for a
    request that cannot fit its page budget; no tokens were produced and
    none will be)."""
    rid: int
    kind: str
    token: Optional[int] = None
    result: Optional[GenResult] = None
    reason: Optional[str] = None


@dataclass
class _Request:
    """Engine-internal per-request state: prompt, prefill/decode
    progress, the occupied slot and mapped pages, timing, and the
    sampling mode/stream."""
    rid: int
    prompt: np.ndarray
    max_new: int
    submitted_s: float
    tokens: List[int] = field(default_factory=list)
    filled: int = 0                  # prefill progress (incl. matched skip)
    matched: int = 0                 # prefix tokens reused from the cache
    slot: int = -1
    pages: List[int] = field(default_factory=list)
    admitted_s: float = 0.0          # on the trace sink's clock
    prefill_s: float = 0.0           # admission -> first token
    decode_s: float = 0.0
    greedy: bool = True
    # sampled requests only: this request's own PRNG stream root,
    # fold_in(PRNGKey(seed), rid); draw t folds in t = len(tokens)
    key: Optional[object] = None


@jax.jit
def _sample_rows(logits, keys, ts, greedy):
    """One next-token draw per row, all rows in one jitted call.

    logits [B, V]; keys [B, 2] uint32 per-request stream roots; ts [B]
    per-request draw counters; greedy [B] bool. Greedy rows take argmax,
    sampled rows draw categorical under fold_in(key, t) — exactly the
    draw the engine's scalar path computes, row by row (logits upcast to
    f32 first, matching the host-side draw), so batching the draws
    changes nothing bitwise while collapsing the per-slot Python loop
    into a single device call that transfers B ints instead of the full
    [B, V] logits."""
    def one(row, key, t, g):
        row = row.astype(jnp.float32)
        samp = jax.random.categorical(jax.random.fold_in(key, t), row)
        return jnp.where(g, jnp.argmax(row), samp).astype(jnp.int32)
    return jax.vmap(one)(logits, keys, ts, greedy)


class ContinuousEngine:
    """Continuous (slot-level) batching over a block-table paged KV pool.

    The cache is one global page pool [L, num_pages, page_size, G, dh]
    (int8 values with [L, num_pages, page_size, G] scale planes for
    `kv_quant` configs); each slot maps an ordered list of pages through
    its [W] page-table row, so a slot's logical position p lives at pool
    page `table[p // page_size]`, in-page offset `p % page_size`. Decode
    steps run all slots at once through `model.decode_step_paged`;
    admission prefill runs one `prefill_chunk` slice of one prompt per
    slot per step through `model.prefill_chunk_paged`, interleaved with
    decode, so the running requests keep streaming while a new prompt
    fills its pages. A slot freed by EOS (or max_new) admits the next
    queued request on the following step.

    Prefix reuse (non-sliding-window configs): completed prompts register
    their pages in a token-keyed trie (serving/pager.py). Admission
    matches the longest cached prefix, maps its full pages read-only
    (refcounted — zero copies), copy-on-write forks at most one partially
    matching page, and starts prefill at the first unmatched token; the
    skipped chunks are the TTFT win `benchmarks/bench_serving.py
    --prefix` measures. Shared pages are never written: every store lands
    at logical position >= the request's matched length, which sits in
    slot-private pages. Sliding-window configs keep per-slot ring pages
    (cursor `pos % ring_len`) with sharing disabled — a ring's contents
    depend on its own wrap history, so its pages are never
    prefix-reusable.

    Oversize admission: a prompt needing more than the slot's table width
    in pages (prompt + max_new tokens) is refused with a terminal "shed"
    event (reason "oversize") — never silently truncated; anything
    smaller can borrow transiently free pool pages and waits in queue
    while they are held by live slots.

    Sampling: `submit(..., greedy=False, seed=s)` gives the request its
    own PRNG stream `fold_in(PRNGKey(s), rid)`; draw t folds in the
    number of tokens already emitted. Because paged decode rows are
    independent and the stream depends only on (seed, rid), a request's
    sampled tokens are bit-identical whatever else is co-resident.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 512, eos_id: int = 2,
                 prefill_chunk: int = 32, page_size: int = 32,
                 oversize_pages: int = 2,
                 trace: Optional[TraceSink] = None):
        """Allocate the page pool (`slots` table-widths of `page_size`
        pages; sliding-window configs get `min(window, chunk-rounded
        max_len)` ring positions per slot) and jit the paged decode /
        chunk-prefill executables. `oversize_pages` widens every table
        row beyond the ceil(max_len / page_size) baseline so a request
        slightly over budget can still be admitted from transiently free
        pages instead of shed. `params` is held as
        `model.serving_params` casts it, and float32 masters passed in
        are not kept. Raises ValueError for configs without
        slot-paged support (`model.supports_paged`)."""
        if not model.supports_paged(cfg):
            raise ValueError(
                f"{cfg.name}: family/config without slot-paged KV support "
                "(use Engine's wave path)")
        self.cfg = cfg
        self.params = model.serving_params(cfg, params)
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.prefill_chunk = prefill_chunk
        self.page_size = page_size
        self.oversize_pages = oversize_pages
        # observability: every request-visible state change is mirrored
        # into the sink (serving/trace.py); tracing is pure host-side
        # bookkeeping and never touches device state, so tokens are
        # bit-identical with or without a sink attached
        self.trace = trace
        self.trace_src = f"e{_ENGINE_SEQ[0]}"
        _ENGINE_SEQ[0] += 1
        ps = page_size
        # absolute-position scratch length for chunked prefill: rounded
        # UP to whole chunks so a final ragged chunk's dynamic slice
        # never clamps backwards over earlier positions
        self.abs_len = -(-max_len // prefill_chunk) * prefill_chunk
        if cfg.sliding_window:
            # per-slot ring over the window (same modulus the wave path
            # bakes into its rolled layout); prefix sharing disabled
            self.ring_len = min(cfg.sliding_window, self.abs_len)
            self.table_width = -(-self.ring_len // ps)
        else:
            self.ring_len = 0
            self.table_width = -(-max_len // ps) + oversize_pages
        self.num_pages = self.slots * self.table_width
        self.cache = model.init_page_pool(cfg, self.num_pages, ps,
                                          dtype=model.compute_dtype(cfg))
        self.pool = PagePool(self.num_pages)
        self.prefix: Optional[PrefixCache] = (
            None if self.ring_len else PrefixCache(self.pool, ps))
        # host page table + lazily refreshed device mirror
        self._tbl = np.zeros((slots, self.table_width), np.int32)
        self._tbl_dev = None
        self._decode = jax.jit(
            lambda p, c, t, pos, act, tbl: model.decode_step_paged(
                cfg, p, c, t, pos, act, tbl, page_size=ps,
                ring_len=self.ring_len),
            donate_argnums=(1,))
        self._chunk = jax.jit(
            lambda p, c, t, row, off, lim: model.prefill_chunk_paged(
                cfg, p, c, t, row, off, lim, page_size=ps,
                ring_len=self.ring_len, abs_len=self.abs_len),
            donate_argnums=(1,))

        def _copy_page(c, src, dst):
            out = dict(c)
            for k in out:
                out[k] = out[k].at[:, dst].set(out[k][:, src])
            return out
        self._copy = jax.jit(_copy_page, donate_argnums=(0,))
        # host-side slot state
        self.pos = np.zeros(slots, np.int32)
        self.last_tok = np.zeros(slots, np.int32)
        self.active = np.zeros(slots, bool)      # decoding (prefill done)
        self._occupant: List[Optional[_Request]] = [None] * slots
        self.queue: Deque[_Request] = deque()
        self._inflight: Dict[int, _Request] = {}
        self._next_rid = 0
        # utilisation / pager counters (decode steps only)
        self.steps = 0
        self.active_slot_steps = 0
        self.cancelled = 0
        self.shed = 0
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0

    def clone(self, *, slots: Optional[int] = None) -> "ContinuousEngine":
        """An independent replica: same params/config, its own page pool
        and slot state (the SlotScheduler's unit of failover)."""
        return ContinuousEngine(
            self.cfg, self.params, slots=slots or self.slots,
            max_len=self.max_len, eos_id=self.eos_id,
            prefill_chunk=self.prefill_chunk, page_size=self.page_size,
            oversize_pages=self.oversize_pages, trace=self.trace)

    def _table_dev(self):
        if self._tbl_dev is None:
            self._tbl_dev = jnp.asarray(self._tbl)
        return self._tbl_dev

    # ------------------------------------------------------------ tracing

    def _emit(self, name: str, rid: int = -1, *, comp: str = "engine",
              **attrs):
        """One trace record from this engine; None without a sink."""
        if self.trace is not None:
            return self.trace.emit(comp, name, rid, src=self.trace_src,
                                   **attrs)
        return None

    def _span(self, name: str, rid: int = -1, **attrs):
        """An `engine/<name>` span (serving/trace.py); a shared no-op
        context without a sink."""
        if self.trace is None:
            return NO_SPAN
        return self.trace.span("engine", name, rid, src=self.trace_src,
                               **attrs)

    def _moe_rows(self, n_rows: int, n_real: int) -> dict:
        """Span attrs of a MoE engine's call over `n_rows` rows holding
        `n_real` real tokens: the expert-rows routed and computed
        (`moe.serving_rows`), summed over layers."""
        r = moe.serving_rows(self.cfg, n_rows, n_real)
        layers = self.cfg.num_layers
        return {"moe_rows_routed": r.routed * layers,
                "moe_rows_computed": r.computed * layers}

    def _now(self, rec) -> float:
        """A record's timestamp, or the sink's clock source when there
        is no sink (and so no record)."""
        return rec.ts if rec is not None else time.perf_counter()

    def _trace_page_stats(self) -> None:
        """Snapshot pool accounting into the trace: tools/trace_check.py
        reconciles the last snapshot of a drained engine against the
        only-the-trie-holds-refs invariant."""
        if self.trace is not None:
            st = self.page_stats()
            self.trace.emit("pager", "page_stats", src=self.trace_src,
                            total=st.total, free=st.free,
                            mapped_refs=st.mapped_refs,
                            retained=st.retained,
                            inflight=len(self._inflight))

    # ------------------------------------------------------------- intake

    def submit(self, prompt: np.ndarray, max_new: int = 32,
               rid: Optional[int] = None, *, greedy: bool = True,
               seed: int = 0, parent_src: Optional[str] = None,
               parent_rid: Optional[int] = None) -> int:
        """Queue one request; returns its rid. A prompt whose pages
        (prompt + max_new tokens) exceed the slot table width is shed
        with a terminal "shed" event at admission — never silently
        truncated. `greedy=False` samples from this request's own PRNG
        stream `fold_in(PRNGKey(seed), rid)` — pass an explicit `rid` to
        make a sampled request's draws reproducible across engines/runs
        regardless of what else is co-resident. `parent_src` /
        `parent_rid` name the caller's request that caused this one (a
        session request); the `queued` record carries them."""
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid) + 1
        p = np.asarray(prompt, np.int32).reshape(-1)
        req = _Request(rid, p, max_new, time.perf_counter(),
                       greedy=greedy)
        if not greedy:
            req.key = jax.random.fold_in(jax.random.PRNGKey(seed), rid)
        self.queue.append(req)
        self._inflight[rid] = req
        link = ({} if parent_src is None
                else {"parent_src": parent_src, "parent_rid": parent_rid})
        self._emit("queued", rid, prompt_len=len(p), max_new=max_new,
                   greedy=greedy, **link)
        return rid

    def _draw(self, req: _Request, row: np.ndarray) -> int:
        """Next token for `req` from its logits row [V]. Greedy: argmax.
        Sampled: categorical under fold_in(req.key, t) where t is the
        number of tokens already emitted — the draw depends only on
        (seed, rid, t, row), never on co-residents."""
        if req.greedy:
            return int(np.argmax(row))
        key = jax.random.fold_in(req.key, len(req.tokens))
        return int(jax.random.categorical(key, jnp.asarray(row)))

    @property
    def pending(self) -> int:
        """Requests still in flight (queued, prefilling or decoding)."""
        return len(self._inflight)

    def free_slots(self) -> int:
        """Slots with no occupant (neither decoding nor admitting)."""
        return sum(1 for r in self._occupant if r is None)

    def available_slots(self) -> int:
        """Admission capacity: free slots minus already-queued requests
        (what a scheduler should look at, not raw free_slots)."""
        return self.free_slots() - len(self.queue)

    def page_stats(self) -> PoolStats:
        """Pool occupancy snapshot: total/free pages, the sum of live
        references (slot mappings + prefix-cache retentions), and how
        many retentions the prefix cache holds."""
        retained = self.prefix.retained_count() if self.prefix else 0
        return PoolStats(self.pool.num_pages, self.pool.free_count,
                         int(self.pool.refs.sum()), retained)

    def drop_prefix_cache(self) -> int:
        """Release every prefix-cache page retention (pages still mapped
        by live slots survive until those slots free them); returns the
        number of entries dropped."""
        return self.prefix.drop() if self.prefix else 0

    def cancel(self, rid: int) -> bool:
        """Abandon one in-flight request (deadline expiry, hedged copy
        superseded, scheduler failover): its slot and page references are
        freed immediately — the next `step()` can admit a queued prompt
        into them — and no further events are emitted for the rid.
        Returns False when the rid is unknown or already finished."""
        req = self._inflight.pop(rid, None)
        if req is None:
            return False
        try:
            self.queue.remove(req)
        except ValueError:
            pass
        self._release_pages(req)
        s = req.slot
        if s >= 0 and self._occupant[s] is req:
            self._occupant[s] = None
            self.active[s] = False
        self.cancelled += 1
        self._emit("cancelled", rid, slot=s, n_tokens=len(req.tokens))
        self._trace_page_stats()
        return True

    # ------------------------------------------------------------- stepping

    def _release_pages(self, req: _Request) -> None:
        """Drop this request's page references (shared prefix pages
        survive while the trie or other slots still hold them) and clear
        its table row."""
        for pid in req.pages:
            self.pool.decref(pid)
        req.pages = []
        if req.slot >= 0:
            self._tbl[req.slot, :] = 0
            self._tbl_dev = None

    def _finish(self, req: _Request, events: List[EngineEvent]) -> None:
        """Free the request's slot + pages and emit its terminal "done"
        event."""
        s = req.slot
        self.active[s] = False
        self._occupant[s] = None
        self._inflight.pop(req.rid, None)
        self._release_pages(req)
        events.append(EngineEvent(req.rid, "done", result=GenResult(
            req.tokens, len(req.prompt), req.prefill_s, req.decode_s)))
        self._emit("done", req.rid, n_tokens=len(req.tokens),
                   prefill_s=req.prefill_s, decode_s=req.decode_s)
        self._trace_page_stats()

    def _emit_token(self, req: _Request, tok: int,
                    events: List[EngineEvent]) -> None:
        """Record one emitted token; finish the request on EOS/max_new."""
        req.tokens.append(tok)
        events.append(EngineEvent(req.rid, "token", token=tok))
        if len(req.tokens) == 1:
            rec = self._emit("first_token", req.rid, token=tok)
            req.prefill_s = self._now(rec) - req.admitted_s
        else:
            self._emit("token", req.rid, token=tok)
        if tok == self.eos_id or len(req.tokens) >= req.max_new:
            self._finish(req, events)

    def _map_request(self, req: _Request, s: int) -> str:
        """Try to map `req`'s pages into slot `s`'s table row. Returns
        "ok" (mapped; prefill resumes at the matched prefix length),
        "shed" (can never fit: more pages than the table width, or the
        pool can't cover it even with the engine otherwise idle and the
        prefix cache fully evicted), or "wait" (transient shortage —
        pages will free when a live slot finishes)."""
        plen = len(req.prompt)
        ps = self.page_size
        if plen == 0:
            return "shed"
        if self.ring_len:
            # rings wrap, so only the prefill scratch bounds the prompt;
            # every slot maps a full table width of private pages
            if plen > self.abs_len:
                return "shed"
            full: List[int] = []
            cow = None
            matched = 0
            need_total = self.table_width
        else:
            need_total = -(-(plen + req.max_new) // ps)
            if need_total > self.table_width:
                return "shed"
            m = self.prefix.match(req.prompt)
            full, cow, matched = m.full, m.cow, m.matched
        # hold the matched pages across eviction/alloc: evicting a leaf
        # we are about to share must not free it back into the pool
        for pid in full:
            self.pool.incref(pid)
        if cow:
            self.pool.incref(cow[0])
        fresh = self.pool.alloc(need_total - len(full))
        while fresh is None and self.prefix and self.prefix.evict_one():
            fresh = self.pool.alloc(need_total - len(full))
        if fresh is None:
            for pid in full:
                self.pool.decref(pid)
            if cow:
                self.pool.decref(cow[0])
            # live slots will free pages; with the engine idle and the
            # trie fully evicted the pool cannot ever cover this request
            if any(r is not None for r in self._occupant):
                return "wait"
            return "shed"
        if cow:
            # fork the partially matching page: one page copy, then the
            # resumed prefill overwrites everything past the match point
            self.cache = self._copy(self.cache, jnp.int32(cow[0]),
                                    jnp.int32(fresh[0]))
            self.pool.decref(cow[0])
            self._emit("cow_fork", req.rid, comp="pager", src_page=cow[0],
                       dst_page=fresh[0], copy_len=cow[1])
        req.pages = full + fresh
        req.matched = req.filled = matched
        self._tbl[s, :len(req.pages)] = req.pages
        self._tbl[s, len(req.pages):] = 0
        self._tbl_dev = None
        if matched:
            self.prefix_hits += 1
            self.prefix_tokens_reused += matched
            self._emit("prefix_hit", req.rid, comp="pager",
                       matched=matched, full_pages=len(full))
        return "ok"

    def _admit(self, events: List[EngineEvent]) -> None:
        """Assign queued requests to free slots (prefill starts on the
        same step, via `_prefill_step`), inside an `engine/admit` span
        whose `admitted` attr counts them (no span when nothing is
        queued). Oversize requests shed loudly; a transient page
        shortage leaves the queue intact until live slots free their
        pages."""
        if not self.queue:
            return
        with self._span("admit") as b:
            n = self._admit_queued(events)
            if b is not None:
                b.attrs["admitted"] = n

    def _admit_queued(self, events: List[EngineEvent]) -> int:
        """`_admit`'s body; returns how many requests it admitted."""
        n = 0
        for s in range(self.slots):
            while self._occupant[s] is None and self.queue:
                req = self.queue.popleft()
                st = self._map_request(req, s)
                if st == "wait":
                    self.queue.appendleft(req)
                    return n
                if st == "shed":
                    self._inflight.pop(req.rid, None)
                    self.shed += 1
                    events.append(EngineEvent(req.rid, "shed",
                                              reason="oversize"))
                    self._emit("shed", req.rid, reason="oversize",
                               prompt_len=len(req.prompt))
                    self._trace_page_stats()
                    continue
                req.slot = s
                self._occupant[s] = req
                self.active[s] = False
                events.append(EngineEvent(req.rid, "admitted"))
                rec = self._emit("admitted", req.rid, slot=s,
                                 matched=req.matched, pages=len(req.pages))
                req.admitted_s = self._now(rec)
                n += 1
        return n

    def _prefill_step(self, events: List[EngineEvent]) -> None:
        """Advance every admitting slot by one prompt chunk. A request
        resuming past a matched prefix takes a short first chunk up to
        the next chunk boundary, so all later chunks land on the same
        grid a cold prefill uses — that alignment (plus identical shared
        page contents) is what keeps a prefix hit bit-identical to a
        cold run. Each chunk is traced as an `engine/prefill_chunk` span
        (for MoE carrying the expert-rows routed and computed)."""
        c = self.prefill_chunk
        for s in range(self.slots):
            req = self._occupant[s]
            if req is None or self.active[s]:
                continue
            end = min(len(req.prompt), (req.filled // c + 1) * c)
            chunk = req.prompt[req.filled:end]
            real = len(chunk)
            if real < c:
                chunk = np.concatenate([chunk, np.zeros(c - real, np.int32)])
            rows = (self._moe_rows(c, real) if self.trace is not None
                    and self.cfg.family == "moe" else {})
            with self._span("prefill_chunk", req.rid, slot=s,
                            start=req.filled, n=real, **rows):
                logits, self.cache = self._chunk(
                    self.params, self.cache, jnp.asarray(chunk[None]),
                    jnp.asarray(self._tbl[s]), jnp.int32(req.filled),
                    jnp.int32(req.filled + real))
                req.filled += real
            if req.filled >= len(req.prompt):
                plen = len(req.prompt)
                if self.prefix is not None:
                    self.prefix.register(req.prompt,
                                         req.pages[:-(-plen // self.page_size)])
                with self._span("prefill_readback", req.rid):
                    row = np.asarray(logits, np.float32)[0, real - 1]
                    tok = self._draw(req, row)
                self.pos[s] = plen
                self.last_tok[s] = tok
                self.active[s] = True
                self._emit_token(req, tok, events)

    def _page_use(self) -> tuple:
        """(pages reserved, pages holding K/V) over the decoding slots:
        the pages mapped into their table rows, and ceil(pos / page_size)
        of them (at most the row's pages: a ring wraps)."""
        ps = self.page_size
        reserved = live = 0
        for s in np.flatnonzero(self.active):
            n = len(self._occupant[s].pages)
            reserved += n
            live += min(n, -(-int(self.pos[s]) // ps))
        return reserved, live

    def _decode_step(self, events: List[EngineEvent]) -> None:
        """One `decode_step_paged` over every active slot, then one
        batched `_sample_rows` draw (greedy argmax rows and per-request
        PRNG-stream rows in the same jitted call — only [slots] ints ever
        reach the host). Traced as an `engine/decode_step` span carrying
        the KV pages reserved and in use (and, for MoE, the expert-rows
        routed and computed), with the wait for the drawn tokens as an
        `engine/decode_readback` span inside it."""
        if not self.active.any():
            return
        t0 = time.perf_counter()
        if self.trace is None:
            step = NO_SPAN
        else:
            reserved, live = self._page_use()
            n = int(self.active.sum())
            rows = (self._moe_rows(self.slots, n)
                    if self.cfg.family == "moe" else {})
            step = self._span("decode_step", active=n,
                              pages_reserved=reserved, pages_live=live,
                              **rows)
        with step:
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(self.last_tok[:, None]),
                jnp.asarray(self.pos), jnp.asarray(self.active),
                self._table_dev())
            keys = np.zeros((self.slots, 2), np.uint32)
            ts = np.zeros(self.slots, np.int32)
            gr = np.ones(self.slots, bool)
            for s in range(self.slots):
                req = self._occupant[s]
                if self.active[s] and not req.greedy:
                    keys[s] = np.asarray(req.key)
                    ts[s] = len(req.tokens)
                    gr[s] = False
            with self._span("decode_readback"):
                nxt = np.asarray(_sample_rows(logits, jnp.asarray(keys),
                                              jnp.asarray(ts),
                                              jnp.asarray(gr)))
            dt = time.perf_counter() - t0
        self.steps += 1
        self.active_slot_steps += int(self.active.sum())
        for s in range(self.slots):
            if not self.active[s]:
                continue
            req = self._occupant[s]
            req.decode_s += dt
            self.pos[s] += 1
            tok = int(nxt[s])
            self.last_tok[s] = tok
            self._emit_token(req, tok, events)

    def step(self) -> List[EngineEvent]:
        """One engine step: admit queued prompts into freed slots, advance
        each admitting slot by one prefill chunk, then run one decode step
        over all active slots. Returns the request events it produced."""
        events: List[EngineEvent] = []
        self._admit(events)
        self._prefill_step(events)
        self._decode_step(events)
        return events

    def utilisation(self) -> float:
        """Mean fraction of slots doing useful decode work per step."""
        return self.active_slot_steps / max(self.steps * self.slots, 1)

    # ----------------------------------------------------------- draining

    def warmup(self) -> None:
        """Compile the chunk-prefill and paged-decode executables off the
        measured path (shapes are fixed, so one tiny request covers it)."""
        self.generate([np.arange(2, dtype=np.int32)], max_new=2)
        self.steps = self.active_slot_steps = 0

    def generate(self, prompts: List[np.ndarray], max_new: int = 32,
                 greedy: bool = True, seed: int = 0) -> List[GenResult]:
        """Batch convenience: submit everything, step until drained.
        `greedy=False` samples each request from its own
        fold_in(PRNGKey(seed), rid) stream; rids are pinned to the batch
        index so the same (prompts, seed) call draws the same tokens no
        matter what the engine served before. Raises RuntimeError if a
        request is shed (oversize) — callers of the batch API expect
        every prompt to produce tokens."""
        assert not self._inflight, "generate() on a busy engine"
        rids = [self.submit(p, max_new, rid=i, greedy=greedy, seed=seed)
                for i, p in enumerate(prompts)]
        results: Dict[int, GenResult] = {}
        while self._inflight:
            for ev in self.step():
                if ev.kind == "done":
                    results[ev.rid] = ev.result
                elif ev.kind == "shed":
                    raise RuntimeError(
                        f"request {ev.rid} shed: {ev.reason} "
                        f"(prompt + max_new exceed the page budget)")
        return [results[r] for r in rids]


class Engine:
    """Serving engine over one model: `generate()` auto-routes through a
    shared slot-paged `ContinuousEngine` for paged-capable configs and
    falls back to the legacy length-bucketed wave path
    (`generate_wave`) for the rest (M-RoPE, encdec, recurrent state) or
    when forced with `continuous=False`."""

    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 512,
                 eos_id: int = 2, prefill_chunk: Optional[int] = None,
                 slots: int = 4, page_size: int = 32):
        """`max_len`: KV budget per request (prompt + generation);
        `slots`: default concurrent-request count of the shared
        ContinuousEngine; `prefill_chunk`: tokens per admission prefill
        chunk; `page_size`: positions per KV pool page (both continuous
        path only). `params` is held as `model.serving_params` casts
        it, and shared with the continuous engines as it is."""
        self.cfg = cfg
        self.params = model.serving_params(cfg, params)
        self.max_len = max_len
        self.eos_id = eos_id
        self.slots = slots
        self.prefill_chunk = prefill_chunk or 32
        self.page_size = page_size
        self._prefill = jax.jit(
            lambda p, b: model.prefill(cfg, p, b))
        self._decode = jax.jit(
            lambda p, c, t, pos: model.decode_step(cfg, p, c, t, pos),
            donate_argnums=(1,))
        self._cont: Dict[int, ContinuousEngine] = {}

    def continuous(self, slots: Optional[int] = None) -> ContinuousEngine:
        """The shared slot-paged engine over the same params/KV budget
        (one per slot count — the decode jit keys on it)."""
        n = slots or self.slots
        if n not in self._cont:
            self._cont[n] = ContinuousEngine(
                self.cfg, self.params, slots=n, max_len=self.max_len,
                eos_id=self.eos_id, prefill_chunk=self.prefill_chunk,
                page_size=self.page_size)
        return self._cont[n]

    def _grow_cache(self, cache, b: int):
        """Caches come back sized to the prompt; decode needs max_len —
        capped at the sliding window for SWA configs: growing a ring past
        its window would change the `pos % len` cursor modulus that the
        prefill roll already baked into the layout."""
        target = self.max_len
        if self.cfg.family in ("dense", "moe") and self.cfg.sliding_window:
            target = min(target, self.cfg.sliding_window)

        def grow(x):
            if x.ndim in (4, 5) and x.shape[2] < target:
                pad = target - x.shape[2]
                z = jnp.zeros(x.shape[:2] + (pad,) + x.shape[3:], x.dtype)
                return jnp.concatenate([x, z], axis=2)
            return x
        if self.cfg.family in ("dense", "moe", "encdec"):
            grown = dict(cache)
            for k in ("k", "v", "k_s", "v_s"):
                if k in grown and not k.startswith("cross"):
                    grown[k] = grow(grown[k])
            return grown
        return cache  # state caches (mamba2/rglru) are fixed-size

    def generate(self, prompts: List[np.ndarray], max_new: int = 32,
                 greedy: bool = True, seed: int = 0,
                 continuous: Optional[bool] = None) -> List[GenResult]:
        """Compatibility wrapper. `continuous=None` auto-routes requests
        through the slot-paged ContinuousEngine when the config supports
        it. Both paths draw each request's sampled tokens from its own
        fold_in(PRNGKey(seed), rid) stream with rid pinned to the prompt
        index, so greedy AND sampled output are token-identical between
        the paged path and the legacy length-bucketed waves
        (`continuous=False`, kept as the pre-paged parity baseline)."""
        if continuous is None:
            continuous = model.supports_paged(self.cfg)
        if continuous:
            return self.continuous().generate(prompts, max_new=max_new,
                                              greedy=greedy, seed=seed)
        buckets: dict[int, List[int]] = {}
        for i, p in enumerate(prompts):
            buckets.setdefault(len(p), []).append(i)
        results: List[Optional[GenResult]] = [None] * len(prompts)
        for plen, idxs in sorted(buckets.items()):
            wave = [prompts[i] for i in idxs]
            for i, r in zip(idxs, self.generate_wave(wave, max_new,
                                                     greedy, seed,
                                                     rids=idxs)):
                results[i] = r
        return results

    def generate_wave(self, prompts: List[np.ndarray], max_new: int = 32,
                      greedy: bool = True, seed: int = 0,
                      rids: Optional[List[int]] = None) -> List[GenResult]:
        """prompts: list of 1-D int32 token arrays of EQUAL length.

        Sampled draws come from per-request streams
        fold_in(fold_in(PRNGKey(seed), rid), step) — the same computation
        the continuous engine's `_sample_rows` performs — so a request's
        tokens depend only on (seed, rid, its own logits), never on the
        wave's composition. `rids` defaults to the batch index."""
        b = len(prompts)
        plen = max(len(p) for p in prompts)
        assert all(len(p) == plen for p in prompts), \
            "generate_wave requires equal-length prompts (use generate())"
        toks = np.stack([np.asarray(p, np.int32) for p in prompts])
        batch = {"tokens": jnp.asarray(toks)}
        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, batch)
        logits.block_until_ready()
        t_prefill = time.perf_counter() - t0
        cache = self._grow_cache(cache, b)

        outs = [[] for _ in range(b)]
        done = np.zeros(b, bool)
        if not greedy:
            if rids is None:
                rids = list(range(b))
            root = jax.random.PRNGKey(seed)
            keys = jnp.asarray(np.stack([
                np.asarray(jax.random.fold_in(root, r)) for r in rids]))
            gflags = jnp.zeros(b, bool)
        t1 = time.perf_counter()
        for step in range(max_new):
            if greedy:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            else:
                tok = _sample_rows(logits, keys,
                                   jnp.full((b,), step, jnp.int32),
                                   gflags)[:, None]
            tok_np = np.asarray(tok)[:, 0]
            for i in range(b):
                if not done[i]:
                    outs[i].append(int(tok_np[i]))
                    if tok_np[i] == self.eos_id:
                        done[i] = True
            if done.all():
                break
            pos = jnp.int32(min(plen + step, self.max_len - 1))
            logits, cache = self._decode(self.params, cache, tok, pos)
        t_decode = time.perf_counter() - t1
        return [GenResult(outs[i], len(prompts[i]), t_prefill, t_decode)
                for i in range(b)]
