"""Paged posit-KV serving runtime: shared block-table cache, batched
chunked prefill, prefix sharing, page reclamation, continuous batching.

The engine is a slot scheduler over jit'd model entry points, all with
fixed shapes (no per-request recompilation):

  * `prefill_chunk_batched` — prompts are decomposed into chunks drawn from
    a small bucket table (e.g. 64/16/4/1 tokens, composed exactly — no
    padding), and all slots whose next chunk has the same bucket size run
    as ONE `[batch_slots, chunk]` program: serving a mixed-length queue
    compiles O(#buckets) prefill programs and issues one device call per
    (step, bucket) regardless of how many slots are filling.  Each chunk
    writes its KV straight into the slot's cache rows/pages — there is no
    whole-prompt prefill and no cache-splice over the full cache.
  * `decode_step` — one token for all slots per iteration.

**Shared paged KV cache** (the default for attention families): the KV
cache is a pool of fixed-size pages `[n_pages, page_size, Hkv*Dh]` stored
at the QuantPolicy's `kv_cache` posit code width, plus a per-slot block
table (models/paged.py).  The host-side allocator refcounts every page, so
one physical page may appear in many block tables:

  * a **prefix index** maps the hash of each prompt-token prefix that
    exactly fills a page to the physical page holding its KV.  A request
    whose prompt shares that prefix maps the donor's pages into its block
    table (refcount++) and only prefills the unshared tail — repeated-
    system-prompt traffic costs O(unique prefix) prefill compute and KV
    pages instead of O(requests x prompt).  Sharing stops at boundaries
    aligned with the request's own chunk decomposition, so shared serving
    is bit-identical to unshared serving.  For recurrent families
    (hybrid), index entries carry the donor's conv/SSM state snapshot at
    the boundary; entries without one are chain links only.
  * shared pages are **copy-on-write**: a page is immutable below its
    frozen prefix (the positions sharers trust).  A slot about to write
    below it first forks the page into a private copy (swapping its
    block-table entry); a donor appending decode tokens past every
    sharer's trusted range writes in place.  Admission pre-reserves each
    request's fork page, so a COW fork never allocates mid-flight — pages
    promised to admitted requests are accounted up front rather than per
    request in isolation.

**Sharded pools** (`mesh=...`): on a mesh with a >1 kv_pages axis the
pool's page dimension splits into contiguous per-device ranges with one
page budget per device; block tables keep *global* page ids (the id
contract lives in models/paged.py), every entry point runs under a
fully-manual shard_map, and decode log-sum-exp-merges per-device
streaming-softmax partials exactly — a multi-device engine is
token-identical to the 1-device engine over the same pool.

Pages reclaim at retirement (refcount--, recycled at zero, prefix-index
entries evicted) and are reused without zeroing: every position is written
before any attention may read it, so stale keys cannot leak.  The decode
hot path runs the Pallas paged-attention kernel
(kernels/paged_attention.py): block-table gather, in-kernel posit decode
next to the q·k dot, streaming softmax — the PDPU fused-decode idea
applied to attention.  `paged=False` (or an SSM family, whose recurrent
state is already O(1)) serves the dense cache as a special case of the
same scheduler.

**Sampling**: greedy argmax by default; `greedy=False` enables temperature/
top-k sampling with a per-request seed (`Request.seed`, default the rid)
folded with the token index — reproducible across runs and independent of
batch composition, paged/dense layout, or prefix sharing.

Weights may equally be posit-coded: `from_checkpoint` restores a packed
checkpoint (models/packing.py) and the GEMM dispatch layer routes it
through the fused Pallas kernels (`execution='fused'`), including grouped
MoE expert stacks and activation-coded policies — see
`execution_summary()` for the datapath and the kv_bytes/metadata_bytes
storage split an engine is actually running.

**Counters** (`engine.stats`, plain ints and floats, host values only):
prefix sharing (`pages_shared`, `shared_admissions`, `cow_forks`);
prefill (`prefill_chunks`, `prefill_device_programs`,
`prefill_batch_sizes` {live rows: programs}, `prefill_live_tokens` /
`prefill_row_tokens`: chunk tokens of live rows / rows x chunk size of
every chunk program); decode (`decode_steps`, `decode_device_programs`,
`decode_rows`: live rows summed over decode steps); scheduling
(`admissions`, `queue_wait_s`: summed (re)queueing -> admission,
`first_tokens`, `admit_to_first_token_s`: summed admission -> first token
sampled, both on `time.perf_counter`; `preemptions`); speculation
(`spec_rounds`, `spec_draft_tokens`, `spec_accepted_tokens`,
`spec_committed_tokens`).  `execution_summary()` turns the scheduling
ones into means and use ratios.

**Spans** (serve/tracing.py, only while a profiler collects): `serve.step`
around each `step()`, holding `serve.admit`, `serve.prefill_chunk` (host
preparation and dispatch of one chunk program), `serve.first_token_wait`,
then `serve.refresh_meta`, `serve.decode_dispatch`, `serve.token_wait`
(the blocking token read) and `serve.retire`; a speculative round nests
the same under `serve.spec_round`.  Every jitted program has a stable
name (`jit_serve_decode_sample`, `jit_serve_prefill_chunk_batched`, ...).
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api
from repro.models import paged as paged_mod
from repro.models.config import ModelConfig
from repro.models.paged import (PagedLayout, PageShard, fork_page,
                                fused_prefill_span_ok)
from repro.parallel import sharding
from repro.serve.tracing import span


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # [S] int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    seed: Optional[int] = None   # sampling stream (defaults to rid)
    out_tokens: Optional[list] = None
    # time.perf_counter() at the last (re)queueing and admission: the
    # engine's queue-wait and admit-to-first-token counters
    queued_at: Optional[float] = dataclasses.field(
        default=None, repr=False, compare=False)
    admitted_at: Optional[float] = dataclasses.field(
        default=None, repr=False, compare=False)


class PageAllocator:
    """Host-side refcounted free-list over the (possibly sharded) KV pool.

    Block tables address *global* page ids throughout (models/paged.py).
    With n_shards=1 this is the single-pool allocator: page 0 is the trash
    page and is never handed out.  With n_shards>1 the pool's page dim is
    split over the kv_pages mesh axis into contiguous per-shard ranges and
    the allocator keeps one free list — one *page budget* — per device:
    every shard's local page 0 (global ids ≡ 0 mod pages_per_shard) is that
    shard's trash page, so capacity is n_pages - n_shards.

    `alloc(n, prefer_shard=...)` grants fresh pages at refcount 1, with
    *slot affinity*: all n pages come from one shard when any single shard
    can serve them (prefer_shard first — a prefix donor's shard, so shared
    chains stay device-local — else the least-loaded shard), and spill
    deterministically across shards (most-free first, ties by shard index)
    only when no single budget fits.  Cross-shard slots stay correct via
    the log-sum-exp partial merge; single-shard slots decode bitwise
    identically to an unsharded pool.

    `share` maps an already-live page into another block table
    (refcount++); `free` drops one reference per page and recycles a page
    onto its own shard's free list only when its last reference goes —
    freeing a page that holds no reference raises (double-free)."""

    def __init__(self, n_pages: int, n_shards: int = 1):
        if n_pages % n_shards:
            raise ValueError(f"n_pages={n_pages} not divisible by "
                             f"n_shards={n_shards}")
        self.n_shards = n_shards
        self.pages_per_shard = n_pages // n_shards
        if self.pages_per_shard < 2:
            raise ValueError(f"need >= 2 pages per shard (trash + 1), got "
                             f"{self.pages_per_shard}")
        self.capacity = n_pages - n_shards
        self.peak_in_use = 0
        self.total_allocs = 0   # fresh grants ever (shares not counted)
        # per-shard free lists of global ids; pop() -> low local ids first;
        # local page 0 of every shard is its trash page, never listed
        self._free = [list(range((s + 1) * self.pages_per_shard - 1,
                                 s * self.pages_per_shard, -1))
                      for s in range(n_shards)]
        self._refs: Dict[int, int] = {}

    @property
    def pages_free(self) -> int:
        return sum(len(f) for f in self._free)

    @property
    def pages_in_use(self) -> int:
        return self.capacity - self.pages_free

    @property
    def pages_free_by_shard(self) -> List[int]:
        return [len(f) for f in self._free]

    @property
    def pages_in_use_by_shard(self) -> List[int]:
        per = self.pages_per_shard - 1  # usable pages per device
        return [per - len(f) for f in self._free]

    def shard_of(self, page: int) -> int:
        return page // self.pages_per_shard

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def alloc(self, n: int,
              prefer_shard: Optional[int] = None) -> Optional[List[int]]:
        if n > self.pages_free:
            return None
        if n == 0:
            return []
        if prefer_shard is not None and len(self._free[prefer_shard]) >= n:
            order = [prefer_shard]
        else:
            fits = [s for s in range(self.n_shards)
                    if len(self._free[s]) >= n]
            if fits:
                # single-shard fit: least-loaded (most free), ties by index
                order = [max(fits, key=lambda s: (len(self._free[s]), -s))]
            else:
                # deterministic spill: most-free first, ties by index
                order = sorted(range(self.n_shards),
                               key=lambda s: (-len(self._free[s]), s))
        out: List[int] = []
        for s in order:
            while len(out) < n and self._free[s]:
                out.append(self._free[s].pop())
        for p in out:
            self._refs[p] = 1
        self.total_allocs += len(out)
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        return out

    def share(self, pages: List[int]):
        """Take one extra reference per page (prefix sharing)."""
        for p in pages:
            if self._refs.get(p, 0) < 1:
                raise ValueError(f"cannot share free page {p}")
            self._refs[p] += 1

    def free(self, pages: List[int]) -> List[int]:
        """Drop one reference per page; returns the pages actually
        recycled (refcount reached zero) so callers can evict metadata."""
        recycled = []
        for p in pages:
            rc = self._refs.get(p, 0)
            if rc < 1:
                raise ValueError(f"double free of page {p}")
            if rc == 1:
                del self._refs[p]
                self._free[self.shard_of(p)].append(p)
                recycled.append(p)
            else:
                self._refs[p] = rc - 1
        return recycled


def _jit_named(name: str, fn, **jit_kw):
    """jax.jit under a stable program name: the device trace's "XLA
    Modules" line reads `jit_<name>` for it, not `jit__lambda`."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kw)


def _build_sampler(greedy: bool, top_k: int):
    """jit'd token sampler: logits [B, V] + per-row keys -> [B] int32."""

    def sample(logits, keys, temperature):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        l = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
        if top_k > 0 and top_k < l.shape[-1]:
            kth = jnp.sort(l, axis=-1)[..., -top_k][..., None]
            l = jnp.where(l >= kth, l, -1e30)
        return jax.vmap(jax.random.categorical)(keys, l).astype(jnp.int32)

    return _jit_named("serve_sample", sample)


_FREE, _PREFILL, _DECODE = 0, 1, 2
_META = ("k", "v", "length", "block_table")


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, batch_slots: int,
                 max_seq: int, greedy: bool = True, *,
                 temperature: float = 1.0, top_k: int = 0,
                 base_seed: int = 0, paged: bool = True,
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 prefill_buckets=(64, 16, 4, 1),
                 prefill_chunks_per_step: int = 0,
                 prefix_sharing: Optional[bool] = None,
                 batched_prefill: Optional[bool] = None,
                 fused_prefill: Optional[bool] = None,
                 fused_decode: Optional[bool] = None,
                 speculate_k: int = 0,
                 draft_quant=None,
                 draft_params=None,
                 mesh=None):
        """batch_slots decode slots over a max_seq position budget per slot.

        paged=True (default) serves attention families from a posit-coded
        page pool; page_size defaults to cfg.quant.kv_page_size and n_pages
        to full capacity (batch_slots * pages_per_slot + trash pages) —
        pass a smaller n_pages to oversubscribe (admission then waits for
        reclaimed pages).  prefill_chunks_per_step=0 completes a prompt's
        chunks at admission; k>0 interleaves at most k chunks per request
        per engine step with ongoing decode (chunked prefill inside the
        decode loop).  prefix_sharing / batched_prefill default to the
        QuantPolicy knobs (both on); sharing applies to paged engines only.
        fused_prefill overrides QuantPolicy.fused_prefill per instance
        (rewriting cfg.quant before tracing): paged prefill chunks run
        attention + KV encode + page scatter as ONE device program instead
        of three, bit-identically, for arbitrary history spans (history
        beyond one flash chunk streams through the kernel's running flash
        softmax) — the per-chunk program counts are reported by
        execution_summary().  fused_decode likewise overrides
        QuantPolicy.fused_decode: each paged decode step runs attention +
        logits head + sampling as ONE device dispatch
        (api.decode_and_sample) instead of a decode program followed by a
        sampler program, with bit-identical tokens; bit_exact execution
        keeps the decomposed pair.

        mesh: optional jax Mesh.  When the mesh has a >1-sized axis that the
        sharding rules map `kv_pages` onto (the 'model' axis by default),
        the page pool's page dimension is sharded over it: each device owns
        a contiguous global-page-id range and one per-device page budget
        (see PageAllocator), n_pages must divide by the shard count, and
        every entry point runs under a fully-manual shard_map — paged
        attention merges per-device softmax partials exactly, so tokens are
        identical to a 1-device engine over the same pool.  All other state
        (weights, metadata, SSM/conv rows) stays replicated; extra >1 mesh
        axes are rejected.  The host scheduler is unchanged: block tables
        keep global page ids, and allocation prefers single-shard slots
        (prefix donors' shards for shared chains) before spilling.
        Dense-cache and SSM-family engines ignore the mesh.

        speculate_k >= 2 turns on posit-native speculative decoding: a
        cheap draft policy (`draft_quant`, default
        `cfg.quant.with_draft()`; `draft_params` defaults to the serve
        weights) proposes up to k-1 tokens per round, all verified in ONE
        batched multi-query `ops.paged_attention` dispatch against the
        serve policy.  Draft and verify read/write the *same* posit-coded
        KV pages (with_draft pins kv_cache + kv_page_size), and the verify
        pass re-encodes every proposed position with the serve policy's
        codes before attending, so the accepted token stream is bitwise
        identical to plain decode over the same seeds — speculation only
        changes how many device programs that stream costs.  Paged
        single-shard attention families only.
        """
        if fused_prefill is not None:
            cfg = dataclasses.replace(
                cfg, quant=dataclasses.replace(
                    cfg.quant, fused_prefill=bool(fused_prefill)))
        if fused_decode is not None:
            cfg = dataclasses.replace(
                cfg, quant=dataclasses.replace(
                    cfg.quant, fused_decode=bool(fused_decode)))
        self.cfg = cfg
        self.params = params
        self.B = batch_slots
        self.S = max_seq
        self.greedy = greedy
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.prefill_chunks_per_step = int(prefill_chunks_per_step)
        self.mesh = None
        self._shard_axis = None
        n_shards = 1
        if mesh is not None and paged:
            axes = [a for a in sharding.mesh_axes_for("kv_pages", mesh)
                    if mesh.shape[a] > 1]
            extra = [a for a in mesh.axis_names
                     if mesh.shape[a] > 1 and a not in axes]
            if extra:
                raise ValueError(
                    f"serving mesh has >1-sized axes {extra} that kv_pages "
                    f"does not shard over; the engine only shards the page "
                    f"pool — use a mesh whose non-trivial axis is the "
                    f"kv_pages one (default: 'model')")
            if len(axes) > 1:
                raise ValueError(
                    f"kv_pages maps onto multiple >1 mesh axes {axes}; "
                    f"shard the page pool over a single axis")
            if axes:
                self.mesh = mesh
                self._shard_axis = axes[0]
                n_shards = mesh.shape[axes[0]]
        self.prefill_buckets = self._valid_buckets(prefill_buckets)
        self.layout = None
        if paged:
            ps = self._resolve_page_size(page_size, max_seq)
            self.layout = PagedLayout.for_slots(batch_slots, max_seq, ps,
                                                n_pages, n_shards=n_shards)
        self.cache = api.init_cache(cfg, batch_slots, max_seq, self.layout)
        self.paged = "block_table" in self.cache  # SSM families: no pages
        if not self.paged:
            self.layout = None
            self.mesh = None      # SSM recurrent state is O(1): nothing to
            self._shard_axis = None  # shard; serve replicated
        self.n_shards = self.layout.n_shards if self.paged else 1
        self.allocator = (PageAllocator(self.layout.n_pages,
                                        self.layout.n_shards)
                          if self.paged else None)
        self.max_pages_per_slot = (self.cache["block_table"].shape[1]
                                   if self.paged else 0)
        q = cfg.quant
        self.prefix_sharing = self.paged and bool(
            q.prefix_sharing if prefix_sharing is None else prefix_sharing)
        if batched_prefill is None:
            # routed-MoE capacity is computed over the whole [B, C] batch:
            # unless the capacity factor is drop-proof (capacity >= tokens
            # even if routing concentrates), padding rows of a batched
            # chunk could displace active tokens and make outputs depend
            # on batch composition — fall back to per-slot prefill there.
            # An explicit batched_prefill=True overrides.
            droppy_moe = (cfg.n_experts > 0 and
                          cfg.capacity_factor * cfg.top_k < cfg.n_experts)
            self.batched_prefill = bool(q.batched_prefill) and not droppy_moe
        else:
            self.batched_prefill = bool(batched_prefill)

        # fused one-program decode: attention + logits head + sampler in a
        # single device dispatch.  Paged engines only (the structural
        # launch-pair residual this removes lives in the serving decode
        # loop); bit_exact has no fused head replay.
        self.fused_decode = (self.paged and bool(q.fused_decode)
                             and q.execution != "bit_exact")
        if self.n_shards > 1:
            self._install_sharded_fns()
        else:
            self._page_shard = None
            self._decode = _jit_named(
                "serve_decode", lambda p, t, c: api.decode_step(p, t, c, cfg))
            if self.fused_decode:
                gd, tk, V = greedy, self.top_k, cfg.vocab_size
                self._decode_sample = _jit_named(
                    "serve_decode_sample",
                    lambda p, t, c, keys, temp: api.decode_and_sample(
                        p, t, c, cfg,
                        None if gd else api.sample_noise(keys, V),
                        temp, greedy=gd, top_k=tk))
            # chunk programs donate the cache (arg 2) and write the page
            # pool in place: queued back to back, as a long prompt's are,
            # they would otherwise each hold a copy of the pool
            self._chunk = _jit_named(
                "serve_prefill_chunk",
                lambda p, t, c, s: api.prefill_chunk(p, t, c, s, cfg),
                donate_argnums=2)
            self._chunk_batched = _jit_named(
                "serve_prefill_chunk_batched",
                lambda p, t, c, a: api.prefill_chunk_batched(p, t, c, a,
                                                             cfg),
                donate_argnums=2)
            # COW page duplication; dst/src are traced so one compile
            # covers every fork
            self._fork_fn = _jit_named(
                "serve_fork_page", lambda kv, d, s: fork_page(kv, d, s))
        # whole-prompt prefill, kept as a reference/debug probe only — the
        # serving path never calls it (chunked prefill replaces it)
        self._prefill = _jit_named(
            "serve_prefill",
            lambda p, b: api.prefill(p, b, cfg, max_seq=max_seq))
        self._sampler = _build_sampler(greedy, self.top_k)
        self._base_key = jax.random.key(base_seed)
        self._dummy_keys = jax.random.split(self._base_key, batch_slots)

        # host-owned scheduler state (device copies are refreshed per call)
        self.lengths = np.zeros(batch_slots, np.int32)
        self.block_tables = np.zeros(
            (batch_slots, max(self.max_pages_per_slot, 1)), np.int32)
        self.slot_phase = np.full(batch_slots, _FREE, np.int8)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.slot_pages: List[List[int]] = [[] for _ in range(batch_slots)]
        self.slot_reserve: List[Optional[int]] = [None] * batch_slots
        self.slot_cursor = np.zeros(batch_slots, np.int64)  # prompt progress
        self.slot_remaining = np.zeros(batch_slots, np.int64)
        self.next_token = np.zeros(batch_slots, np.int32)
        self._slot_keys = [None] * batch_slots
        self._slot_sampled = np.zeros(batch_slots, np.int64)
        self._slot_registered = np.zeros(batch_slots, np.int64)
        self.queue: List[Request] = []
        self.done: List[Request] = []

        # prefix index: digest(prompt token prefix) -> (page, state or
        # None); _page_keys/_frozen support eviction and COW decisions;
        # _held are pages a retiring request left behind because a queued
        # request's prefix still matches them (the engine owns their last
        # reference until that request admits or leaves the queue)
        self.prefix_index: Dict[bytes, tuple] = {}
        self._page_keys: Dict[int, set] = {}
        self._frozen: Dict[int, int] = {}
        self._held: set = set()
        self.stats = {"pages_shared": 0, "shared_admissions": 0,
                      "cow_forks": 0, "prefill_batch_sizes": {},
                      "prefill_chunks": 0, "prefill_device_programs": 0,
                      "decode_steps": 0, "decode_device_programs": 0,
                      "preemptions": 0, "spec_rounds": 0,
                      "spec_draft_tokens": 0, "spec_accepted_tokens": 0,
                      "spec_committed_tokens": 0,
                      "admissions": 0, "queue_wait_s": 0.0,
                      "first_tokens": 0, "admit_to_first_token_s": 0.0,
                      "decode_rows": 0, "prefill_live_tokens": 0,
                      "prefill_row_tokens": 0}

        # batch-dim index per cache leaf, for restoring rows of slots that
        # were mid-prefill during a decode call (page pools have no batch
        # dim — zeroed block-table rows protect them instead)
        specs = api.cache_specs(cfg, batch_slots, max_seq, self.layout)
        self._state_bdim = {
            name: (s.logical_axes.index("batch")
                   if "batch" in s.logical_axes else None)
            for name, s in specs.items()}
        # recurrent families (hybrid) carry per-slot conv/SSM state that
        # prefix sharing must snapshot/restore at the shared boundary
        self._recurrent = any(name not in _META for name in self.cache)

        # ---- speculative decoding (draft-propose / batched-verify) ----
        self.speculate_k = int(speculate_k)
        self.draft_quant = None
        self._spec_dummy_keys: Dict[int, object] = {}
        if self.speculate_k:
            if self.speculate_k < 2:
                raise ValueError("speculate_k must be >= 2 (k=1 is plain "
                                 "decode); pass 0 to disable speculation")
            if not self.paged:
                raise ValueError("speculative decoding requires the paged "
                                 "KV cache (draft and verify must address "
                                 "the same posit-coded pages)")
            if self._recurrent:
                raise ValueError(
                    "speculative decoding is limited to pure-attention "
                    "paged families: recurrent (conv/SSM) state cannot be "
                    "rolled back when a draft token is rejected")
            if self.n_shards > 1:
                raise ValueError("speculative decoding is not implemented "
                                 "for sharded page pools yet")
            if not hasattr(api._mod(cfg), "decode_verify"):
                raise ValueError(f"family {cfg.family!r} has no k-token "
                                 f"verify step")
            dq = draft_quant if draft_quant is not None else \
                cfg.quant.with_draft()
            if (dq.kv_cache != cfg.quant.kv_cache
                    or dq.kv_page_size != cfg.quant.kv_page_size):
                raise ValueError(
                    "draft policy must keep the serve policy's kv_cache "
                    "format and kv_page_size — draft and target decode "
                    "the same posit-coded pages, which is what makes "
                    "speculative acceptance exact (QuantPolicy.with_draft "
                    "preserves both)")
            self.draft_quant = dq
            draft_cfg = dataclasses.replace(cfg, quant=dq)
            self.draft_params = params if draft_params is None else \
                draft_params
            self._draft_decode = _jit_named(
                "serve_draft_decode",
                lambda p, t, c: api.decode_step(p, t, c, draft_cfg))
            gd, tk, V = greedy, self.top_k, cfg.vocab_size
            self._verify = _jit_named(
                "serve_verify",
                lambda p, t, c, keys, temp: api.decode_verify(
                    p, t, c, cfg,
                    None if gd else api.sample_noise(keys, V),
                    temp, greedy=gd, top_k=tk))

    def _valid_buckets(self, buckets):
        """Descending chunk sizes; 1 is always included (exact prompt
        decomposition), and sizes incompatible with the SSD chunk length
        are dropped (ssd_forward needs C % min(ssm_chunk, C) == 0)."""
        out = set(int(b) for b in buckets if b >= 1) | {1}
        if self.cfg.family in ("ssm", "hybrid"):
            q = self.cfg.ssm_chunk
            out = {b for b in out if b <= q or b % q == 0}
        return tuple(sorted(out, reverse=True))

    def _resolve_page_size(self, requested: Optional[int],
                           max_seq: int) -> int:
        """Page size the paged layout is actually built with.

        With fused prefill on, a page size that neither tiles
        paged.FLASH_CHUNK nor keeps every possible prefill span inside one
        flash chunk would silently drop every chunk onto the 3-program
        decomposed path (fused_prefill_span_ok) — the exact quiet fallback
        the ROADMAP carried as a residual.  An explicitly requested size
        that cannot hold the one-program gate raises; the policy default
        (page_size=None) auto-picks the largest FLASH_CHUNK divisor not
        above cfg.quant.kv_page_size instead, so the fused path is never
        lost to a configuration accident."""
        q = self.cfg.quant
        ps = int(q.kv_page_size if requested is None else requested)
        if ps < 1:
            raise ValueError(f"page_size must be >= 1, got {ps}")
        if not q.fused_prefill or self.cfg.family == "ssm":
            return ps  # no paged attention prefill to keep fused
        chunk = paged_mod.FLASH_CHUNK  # read live: tests/CI retune it
        per = -(-max_seq // ps)
        if fused_prefill_span_ok(per, ps, max(self.prefill_buckets)):
            return ps
        if requested is not None:
            raise ValueError(
                f"page_size={ps} cannot tile FLASH_CHUNK={chunk} and the "
                f"slot span ({per} pages x {ps} + a "
                f"{max(self.prefill_buckets)}-token chunk) exceeds one "
                f"flash chunk: every prefill chunk would silently fall "
                f"back to the 3-program decomposed path.  Pass a divisor "
                f"of {chunk} (or page_size=None to auto-pick one), or "
                f"construct with fused_prefill=False to accept the "
                f"decomposed path explicitly")
        return max(d for d in range(1, ps + 1) if chunk % d == 0)

    def _install_sharded_fns(self):
        """Wrap the serving entry points in a fully-manual shard_map over
        the kv_pages mesh axis.  Only the page pools' page dim is sharded
        (each device holds its contiguous global-id range, re-indexed
        locally by models/paged.py); params, metadata, and any recurrent
        conv/SSM state stay replicated.  PartitionSpecs are built from the
        cache leaves' logical axes directly rather than through the global
        rule table: on a serving mesh the kv_pages axis must not drag
        heads/experts/SSM channels along with it (the table maps those onto
        'model' too, for training-time tensor parallelism)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = self.cfg
        ax = self._shard_axis
        sctx = PageShard(axis=ax, n_shards=self.n_shards)
        self._page_shard = sctx
        specs = api.cache_specs(cfg, self.B, self.S, self.layout)
        cspec = {name: P(*[ax if la == "kv_pages" else None
                           for la in s.logical_axes])
                 for name, s in specs.items()}
        rep = P()
        prep = jax.tree.map(lambda _: rep, self.params)
        sm, mesh = sharding.shard_map, self.mesh
        self._decode = _jit_named("serve_decode", sm(
            lambda p, t, c: api.decode_step(p, t, c, cfg, shard=sctx),
            mesh, in_specs=(prep, rep, cspec), out_specs=(rep, cspec)))
        if self.fused_decode:
            gd, tk, V = self.greedy, self.top_k, cfg.vocab_size
            if gd:
                inner = sm(
                    lambda p, t, c, temp: api.decode_and_sample(
                        p, t, c, cfg, None, temp, greedy=True, top_k=tk,
                        shard=sctx),
                    mesh, in_specs=(prep, rep, cspec, rep),
                    out_specs=(rep, cspec))
                self._decode_sample = _jit_named(
                    "serve_decode_sample",
                    lambda p, t, c, keys, temp: inner(p, t, c, temp))
            else:
                # gumbel noise is drawn once outside the shard_map (it only
                # depends on the replicated per-slot keys) and enters
                # replicated, so every shard samples from identical rows
                inner = sm(
                    lambda p, t, c, n, temp: api.decode_and_sample(
                        p, t, c, cfg, n, temp, greedy=False, top_k=tk,
                        shard=sctx),
                    mesh, in_specs=(prep, rep, cspec, rep, rep),
                    out_specs=(rep, cspec))
                self._decode_sample = _jit_named(
                    "serve_decode_sample",
                    lambda p, t, c, keys, temp: inner(
                        p, t, c, api.sample_noise(keys, V), temp))
        self._chunk = _jit_named("serve_prefill_chunk", sm(
            lambda p, t, c, s: api.prefill_chunk(p, t, c, s, cfg,
                                                 shard=sctx),
            mesh, in_specs=(prep, rep, cspec, rep),
            out_specs=(rep, cspec)), donate_argnums=2)
        self._chunk_batched = _jit_named("serve_prefill_chunk_batched", sm(
            lambda p, t, c, a: api.prefill_chunk_batched(p, t, c, a, cfg,
                                                         shard=sctx),
            mesh, in_specs=(prep, rep, cspec, rep),
            out_specs=(rep, cspec)), donate_argnums=2)
        pool = cspec["k"]
        self._fork_fn = _jit_named("serve_fork_page", sm(
            lambda kv, d, s: fork_page(kv, d, s, shard=sctx),
            mesh, in_specs=(pool, rep, rep), out_specs=pool))
        # place params and the freshly-zeroed cache on the mesh up front:
        # params left on one device would be copied to every other device
        # on every entry-point call (a transient weight replica per call)
        self.params = jax.device_put(self.params, NamedSharding(mesh, rep))
        self.cache = {
            name: jax.device_put(leaf, NamedSharding(mesh, cspec[name]))
            for name, leaf in self.cache.items()}

    # ------------------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, cfg: ModelConfig, directory: str,
                        batch_slots: int, max_seq: int,
                        step: Optional[int] = None, **kw) -> "ServingEngine":
        """Restore params (float or posit-packed) and build an engine.

        The checkpoint manifest's `extra` metadata (models.packing.
        pack_manifest) decides the restore dtypes: packed checkpoints come
        back as int8/int16 code arrays that the dispatch layer consumes
        directly — no float materialization of the weights.
        """
        from repro.checkpoint import CheckpointManager
        from repro.models import packing
        from repro.models.module import abstract_params

        mgr = CheckpointManager(directory)
        if step is None:
            step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoints in {directory}")
        extra = mgr.read_manifest(step).get("extra") or {}
        if extra.get("packed_weights"):
            from repro.core.formats import PositFormat
            fmt = PositFormat(extra["weights_n"], extra["weights_es"])
            if cfg.quant.weights != fmt:
                # the dispatch layer decodes codes with cfg.quant.weights —
                # a silent mismatch would serve garbage values
                raise ValueError(
                    f"checkpoint packed as {fmt} but cfg.quant.weights is "
                    f"{cfg.quant.weights}; align the serving QuantPolicy "
                    f"with the pack format")
            specs = packing.packed_param_specs(cfg, fmt)
        else:
            specs = api.param_specs(cfg)
        params = mgr.restore(step, abstract_params(specs))
        return cls(cfg, params, batch_slots, max_seq, **kw)

    # ------------------------------------------------------------------
    # storage accounting
    # ------------------------------------------------------------------

    def weight_bytes(self) -> int:
        """Resident weight-storage bytes (int codes count at container width)."""
        from repro.models.packing import weight_bytes
        return weight_bytes(self.params)

    def kv_cache_summary(self) -> dict:
        """Decode-state storage split: `kv_bytes` is the K/V payload (pages
        or dense rows, plus SSM/conv state — at code width when posit-
        coded); `metadata_bytes` is positions + block tables.  The bench
        storage comparisons use kv_bytes — metadata must not dilute the
        coded-page win."""
        kv = meta = 0
        for name, leaf in self.cache.items():
            if name in ("length", "block_table"):
                meta += int(leaf.nbytes)
            else:
                kv += int(leaf.nbytes)
        out = {"kv_bytes": kv, "metadata_bytes": meta,
               "total_bytes": kv + meta}
        if self.paged:
            # bytes actually backing tokens in flight: what a pool sized to
            # the workload would allocate (decode memory scales with pages
            # in use at code width, not batch_slots x max_seq at f32)
            page_b = int(self.cache["k"].nbytes + self.cache["v"].nbytes) \
                // self.layout.n_pages
            out["kv_bytes_in_use"] = self.pages_in_use * page_b
            out["kv_bytes_peak"] = self.allocator.peak_in_use * page_b
            if self.n_shards > 1:
                out["pages_in_use_by_shard"] = \
                    self.allocator.pages_in_use_by_shard
        return out

    def kv_cache_bytes(self) -> int:
        """Total allocated decode-state bytes (payload + metadata); see
        kv_cache_summary() for the split."""
        return self.kv_cache_summary()["total_bytes"]

    @property
    def slot_free(self) -> List[bool]:
        """Per-slot availability (compat view over the phase array)."""
        return [bool(p == _FREE) for p in self.slot_phase]

    @property
    def pages_in_use(self) -> int:
        return self.allocator.pages_in_use if self.allocator else 0

    @property
    def pages_free(self) -> int:
        return self.allocator.pages_free if self.allocator else 0

    @property
    def pages_promised(self) -> int:
        """Diagnostic: worst-case pages the queued-but-unscheduled
        requests will draw (each counted unshared — sharing is
        opportunistic and may evaporate if donors retire first).  The
        engine does not gate submission on this sum: joint oversubscription
        is served by waiting for reclamation, and what admission accounts
        up front is each admitted request's full private demand including
        its copy-on-write fork reserve (see _admit), so an admitted
        request never allocates mid-flight."""
        if not self.paged:
            return 0
        return sum(self._pages_needed(r) for r in self.queue)

    @property
    def pages_shared_mapped(self) -> int:
        """Extra block-table references onto live pages beyond the first
        (how many page-loads prefix sharing is currently deduplicating)."""
        if not self.paged:
            return 0
        return sum(rc - 1 for rc in self.allocator._refs.values())

    def execution_summary(self) -> dict:
        """Which datapath this engine serves on, its storage terms, and the
        scheduling counters as means and use ratios (None before the first
        event): queue wait and admit-to-first-token in ms, the share of
        chunk-program tokens that carry a prompt, and the share of
        decode-step rows that carry a live request."""
        q = self.cfg.quant
        kv = self.kv_cache_summary()
        st = self.stats

        def ratio(num, den, scale=1.0):
            return scale * st[num] / den if den else None
        return {
            "execution": q.execution,
            "weights": str(q.weights) if q.weights else None,
            "activations": str(q.activations) if q.activations else None,
            "kv_cache": str(q.kv_cache) if q.kv_cache else None,
            "activation_coded": q.execution == "fused"
                                and q.activations is not None,
            "weight_bytes": self.weight_bytes(),
            "kv_cache_bytes": kv["total_bytes"],
            "kv_bytes": kv["kv_bytes"],
            "metadata_bytes": kv["metadata_bytes"],
            "paged": self.paged,
            "page_size": self.layout.page_size if self.paged else None,
            "kv_shards": self.n_shards,
            "pages_in_use": self.pages_in_use,
            "pages_free": self.pages_free,
            "pages_in_use_by_shard": (self.allocator.pages_in_use_by_shard
                                      if self.paged else None),
            "prefix_sharing": self.prefix_sharing,
            "batched_prefill": self.batched_prefill,
            "fused_prefill": self.paged and bool(q.fused_prefill),
            "fused_decode": self.fused_decode,
            "prefill_chunks": self.stats["prefill_chunks"],
            "prefill_device_programs": self.stats["prefill_device_programs"],
            "decode_steps": self.stats["decode_steps"],
            "decode_device_programs": self.stats["decode_device_programs"],
            "pages_shared_mapped": self.pages_shared_mapped,
            "cow_forks": self.stats["cow_forks"],
            "preemptions": self.stats["preemptions"],
            "speculative": bool(self.speculate_k),
            "speculate_k": self.speculate_k or None,
            "speculation_rounds": self.stats["spec_rounds"],
            "speculation_committed_tokens":
                self.stats["spec_committed_tokens"],
            "speculation_accept_rate": (
                self.stats["spec_accepted_tokens"]
                / self.stats["spec_draft_tokens"]
                if self.stats["spec_draft_tokens"] else None),
            "admissions": st["admissions"],
            "queue_wait_mean_ms": ratio("queue_wait_s", st["admissions"],
                                        1e3),
            "admit_to_first_token_mean_ms": ratio(
                "admit_to_first_token_s", st["first_tokens"], 1e3),
            "prefill_row_use": ratio("prefill_live_tokens",
                                     st["prefill_row_tokens"]),
            "decode_slot_use": ratio("decode_rows",
                                     st["decode_steps"] * self.B),
        }

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------

    def submit(self, req: Request):
        n = len(req.prompt)
        if n < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: max_new_tokens must be >= 1")
        # every written position must fit the slot's budget: positions
        # 0 .. n + max_new_tokens - 2 < max_seq.  Past-the-end writes would
        # silently wrap into the slot's last page (insert_tokens clips the
        # page index) / be silently dropped (dense scatter), corrupting or
        # losing KV — reject at submission instead.
        if n + req.max_new_tokens - 1 > self.S:
            raise ValueError(
                f"request {req.rid}: prompt ({n}) + max_new_tokens "
                f"({req.max_new_tokens}) needs {n + req.max_new_tokens - 1} "
                f"positions but max_seq is {self.S}")
        if self.paged and self._pages_needed(req) > self.allocator.capacity:
            budgets = ("" if self.allocator.n_shards == 1 else
                       f" ({self.allocator.n_shards} per-device budgets of "
                       f"{self.allocator.pages_per_shard - 1} pages)")
            raise ValueError(
                f"request {req.rid} needs {self._pages_needed(req)} pages "
                f"but the pool only has {self.allocator.capacity}{budgets}; "
                f"raise n_pages or shorten prompt/max_new_tokens")
        req.out_tokens = []
        req.queued_at = time.perf_counter()
        self.queue.append(req)

    def _pages_needed(self, req: Request) -> int:
        last_pos = len(req.prompt) + req.max_new_tokens - 2  # final write
        return min(last_pos // self.layout.page_size + 1,
                   self.max_pages_per_slot)

    def _chunk_sizes(self, n: int):
        """Exact greedy decomposition of n into bucket sizes (1 included).
        The decomposition has the suffix property — the tail after any
        chunk boundary equals the greedy decomposition of the remainder —
        which is what makes prefix-shared prefill bit-identical to
        unshared prefill when sharing stops at a boundary."""
        out = []
        for b in self.prefill_buckets:
            while n >= b:
                out.append(b)
                n -= b
        return out

    def _next_chunk(self, slot: int) -> int:
        remaining = len(self.slot_req[slot].prompt) \
            - int(self.slot_cursor[slot])
        return self._chunk_sizes(remaining)[0]

    def _refresh_meta(self, cache, mask=None, lengths=None):
        """Push host-owned lengths/block tables into the device cache.
        mask zeroes rows of slots that must not touch real state during a
        batched call (free / mid-prefill slots in decode, non-group slots
        in batched prefill).  lengths overrides the host array (the
        speculative draft loop advances a transient per-slot position
        without committing it)."""
        lengths = (self.lengths if lengths is None else lengths).copy()
        if mask is not None:
            lengths[~mask] = 0
        cache = dict(cache)
        cache["length"] = jnp.asarray(lengths)
        if self.paged:
            bts = self.block_tables.copy()
            if mask is not None:
                bts[~mask] = 0
            cache["block_table"] = jnp.asarray(bts)
        return cache

    def _reset_slot_state(self, slot: int):
        """Zero a slot's recurrent/dense state rows before reuse (SSM and
        conv states are *seeded* by prefill — stale values would leak)."""
        new = {}
        for name, leaf in self.cache.items():
            bdim = self._state_bdim.get(name)
            if name in ("length", "block_table") or bdim is None:
                new[name] = leaf
                continue
            idx = (slice(None),) * bdim + (slot,)
            new[name] = leaf.at[idx].set(0)
        self.cache = new

    def _slot_key(self, req: Request):
        seed = req.seed if req.seed is not None else req.rid
        return jax.random.fold_in(self._base_key, seed)

    def _sample_keys(self, slots, live=None):
        """Per-row sampling keys for `slots` (dummy rows for non-live
        slots), advancing each live slot's draw counter — shared by the
        decomposed sampler and the fused decode-and-sample dispatch so
        both consume the identical key stream."""
        if self.greedy:  # argmax never reads keys: skip building them
            return self._dummy_keys[:len(slots)]
        keys = jnp.stack([
            jax.random.fold_in(self._slot_keys[s],
                               int(self._slot_sampled[s]))
            if (live is None or live[s]) else self._dummy_keys[0]
            for s in slots])
        for s in slots:
            if live is None or live[s]:
                self._slot_sampled[s] += 1
        return keys

    def _sample(self, logits_rows, slots, live=None):
        """Sample one token per row of logits_rows [n, V] for `slots`.
        `live` masks slots whose draw is discarded (dummy keys, counter
        not advanced) — lets batched paths sample a fixed [B, V] batch."""
        keys = self._sample_keys(slots, live=live)
        toks = self._sampler(logits_rows, keys,
                             jnp.float32(self.temperature))
        return np.asarray(toks, np.int32)

    # ------------------------------------------------------------------
    # prefix index: registration, lookup, eviction
    # ------------------------------------------------------------------

    @staticmethod
    def _digest(tokens) -> bytes:
        return hashlib.blake2b(
            np.ascontiguousarray(tokens, np.int32).tobytes(),
            digest_size=16).digest()

    def _prompt_digests(self, req: Request):
        """Per-request digest cache: ([digest per full-page boundary],
        full-prompt digest).  Admission walks these every pass for every
        queued request (lookup, deferral, holds) — hashing each prefix
        once per request instead of once per pass keeps that host work
        O(prompt/page) lookups."""
        ps = self.layout.page_size
        cached = getattr(req, "_prefix_digests", None)
        if cached is not None and cached[0] == ps:
            return cached[1], cached[2]
        prompt = np.ascontiguousarray(req.prompt, np.int32)
        h = hashlib.blake2b(digest_size=16)
        full = []
        for i in range(len(prompt) // ps):
            h.update(prompt[i * ps:(i + 1) * ps].tobytes())
            full.append(h.copy().digest())
        req._prefix_digests = (ps, full, self._digest(prompt))
        return full, req._prefix_digests[2]

    def _put_index(self, key: bytes, page: int, frozen: int, state=None):
        """Register a page for the token prefix hashed by `key`; `frozen`
        is the first position holders may still write (everything below is
        trusted by sharers and must copy-on-write)."""
        if key in self.prefix_index:
            return  # first registration wins; duplicates are identical KV
        self.prefix_index[key] = (page, state)
        self._page_keys.setdefault(page, set()).add(key)
        self._frozen[page] = max(self._frozen.get(page, 0), frozen)

    def _evict(self, recycled: List[int]):
        """Drop index entries whose page went back to the free list (its
        content is about to be reused — the hash no longer describes it)."""
        for p in recycled:
            for key in self._page_keys.pop(p, ()):
                self.prefix_index.pop(key, None)
            self._frozen.pop(p, None)

    def _snapshot_state(self, slot: int):
        """Host copy of the slot's recurrent (conv/SSM) rows, or None for
        pure-attention families."""
        out = {}
        for name, leaf in self.cache.items():
            bdim = self._state_bdim.get(name)
            if name in _META or bdim is None:
                continue
            idx = (slice(None),) * bdim + (slot,)
            out[name] = np.asarray(leaf[idx])
        return out or None

    def _restore_state(self, slot: int, state: dict):
        new = dict(self.cache)
        for name, arr in state.items():
            bdim = self._state_bdim[name]
            idx = (slice(None),) * bdim + (slot,)
            new[name] = new[name].at[idx].set(jnp.asarray(arr))
        self.cache = new

    def _register_pages(self, slot: int):
        """Publish the slot's freshly prompt-filled pages to the prefix
        index (called after every prefill chunk, while cursor <= prompt
        length — so every registered page holds prompt KV only).  For
        recurrent families a conv/SSM snapshot rides along when the chunk
        end lands exactly on the page boundary; boundary-misaligned pages
        become stateless chain links."""
        if not self.prefix_sharing:
            return
        req = self.slot_req[slot]
        ps = self.layout.page_size
        cur = int(self.slot_cursor[slot])
        full = cur // ps
        digests, full_digest = self._prompt_digests(req)
        snap = (self._snapshot_state(slot)
                if self._recurrent and cur % ps == 0 and full else None)
        for i in range(int(self._slot_registered[slot]), full):
            self._put_index(digests[i], int(self.block_tables[slot, i]),
                            (i + 1) * ps,
                            snap if (i + 1) * ps == cur else None)
        self._slot_registered[slot] = full
        if cur == len(req.prompt) and cur % ps and not self._recurrent:
            # the partially-filled tail page: exact-duplicate prompts map
            # it too and copy-on-write their divergence
            self._put_index(full_digest,
                            int(self.block_tables[slot, full]), cur, None)

    def _tail_shareable(self, n: int) -> bool:
        """May a request of prompt length n map an exact-duplicate donor's
        partially-filled tail page?  Requires a divergence point the
        request can actually prefill bit-identically: position n-1 must be
        a boundary of its own chunk decomposition (the same condition
        _lookup_prefix enforces — holds and deferral must not wait for a
        share admission would refuse), and recurrent families need a state
        snapshot partial pages never carry."""
        ps = self.layout.page_size
        return (not self._recurrent and n % ps != 0
                and (n - 1) in {int(t)
                                for t in np.cumsum(self._chunk_sizes(n))})

    def _chain_pages(self, req: Request) -> set:
        """Pages the index currently offers for this request's prefix
        (full-page chain plus the exact-duplicate tail page)."""
        ps = self.layout.page_size
        n = len(req.prompt)
        digests, full_digest = self._prompt_digests(req)
        out = set()
        for i in range((n - 1) // ps):
            ent = self.prefix_index.get(digests[i])
            if ent is None:
                break
            out.add(ent[0])
        else:
            if self._tail_shareable(n):
                ent = self.prefix_index.get(full_digest)
                if ent is not None:
                    out.add(ent[0])
        return out

    def _wanted_by_queue(self) -> set:
        """Index pages some queued-but-unscheduled request would map."""
        wanted = set()
        for req in self.queue:
            wanted |= self._chain_pages(req)
        return wanted

    def _prune_holds(self):
        """Free held pages no queued request's prefix matches anymore.
        Once the queue drains this releases every hold — the pool always
        reclaims completely."""
        if not self._held:
            return
        wanted = self._wanted_by_queue()
        for p in list(self._held):
            if p not in wanted:
                self._held.discard(p)
                self._evict(self.allocator.free([p]))

    def _drop_all_holds(self):
        """Release every held page (liveness over sharing: when admission
        cannot proceed and nothing in flight will ever reclaim, the cached
        prefixes must yield their pages)."""
        for p in list(self._held):
            self._held.discard(p)
            self._evict(self.allocator.free([p]))

    def _lookup_prefix(self, req: Request):
        """Longest shareable prompt prefix for `req` from the index.

        Returns (shared pages, n_shared_tokens, state, partial).  Sharing
        stops at a boundary of the request's own chunk decomposition (the
        greedy suffix property then makes the tail's chunking — and hence
        every logit — bit-identical to an unshared run), leaves at least
        one prompt token to prefill (the engine samples from its logits),
        and for recurrent families requires a state snapshot at the
        boundary."""
        if not self.prefix_sharing:
            return [], 0, None, False
        n = len(req.prompt)
        ps = self.layout.page_size
        digests, full_digest = self._prompt_digests(req)
        bounds = set(int(t) for t in np.cumsum(self._chunk_sizes(n)))
        chain = []
        for i in range((n - 1) // ps):
            ent = self.prefix_index.get(digests[i])
            if ent is None:
                break
            chain.append(ent)
        best = ([], 0, None, False)
        for i, (page, state) in enumerate(chain):
            t = (i + 1) * ps
            if t in bounds and (state is not None or not self._recurrent):
                best = ([p for p, _ in chain[:i + 1]], t, state, False)
        if self._tail_shareable(n) and len(chain) == n // ps:
            ent = self.prefix_index.get(full_digest)
            if ent is not None:
                best = ([p for p, _ in chain] + [ent[0]], n - 1, None, True)
        return best

    # ------------------------------------------------------------------
    # copy-on-write
    # ------------------------------------------------------------------

    def _ensure_writable(self, slot: int, lo: int, hi: int):
        """Fork any shared page the slot is about to write below its
        frozen prefix.  Writes at/after the frozen position (a donor
        appending decode tokens past every sharer's trusted range) stay in
        place — sharers never read there."""
        ps = self.layout.page_size
        for idx in range(lo // ps, (hi - 1) // ps + 1):
            if idx >= self.max_pages_per_slot:
                break
            p = int(self.block_tables[slot, idx])
            if p == 0 or self.allocator.refcount(p) <= 1:
                continue
            if max(lo, idx * ps) >= self._frozen.get(p, 1 << 30):
                continue
            self._fork_slot_page(slot, idx, p)

    def _fork_slot_page(self, slot: int, idx: int, src: int):
        dst = self.slot_reserve[slot]
        self.slot_reserve[slot] = None
        if dst is None:
            # fork copies run device-local when the source's shard has a
            # free page (fork_page broadcasts across shards otherwise)
            got = self.allocator.alloc(
                1, prefer_shard=self.allocator.shard_of(src))
            if got is None:
                raise RuntimeError(
                    f"page pool exhausted during copy-on-write fork for "
                    f"slot {slot}: admission must reserve fork pages up "
                    f"front")
            dst = got[0]
            self.slot_pages[slot].append(dst)
        cache = dict(self.cache)
        cache["k"] = self._fork_fn(cache["k"], dst, src)
        cache["v"] = self._fork_fn(cache["v"], dst, src)
        self.cache = cache
        self.block_tables[slot, idx] = dst
        self.slot_pages[slot].remove(src)
        self._evict(self.allocator.free([src]))
        self.stats["cow_forks"] += 1

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------

    def _next_admissible(self) -> Optional[int]:
        """Queue index of the first request to admit.  A request whose
        prompt would share a longer prefix with a still-prefilling slot
        than the index currently offers is deferred (it admits next pass,
        after the donor registers its pages) — later distinct requests may
        jump ahead so slots keep filling."""
        for qi, req in enumerate(self.queue):
            if self.prefix_sharing and self._should_defer(req):
                continue
            return qi
        return None

    def _should_defer(self, req: Request) -> bool:
        n = len(req.prompt)
        ps = self.layout.page_size
        max_full = (n - 1) // ps
        if max_full == 0 and (self._recurrent or n % ps == 0):
            return False
        digests, full_digest = self._prompt_digests(req)
        # tokens the index can hand us right now
        have = 0
        for i in range(max_full):
            if digests[i] in self.prefix_index:
                have = (i + 1) * ps
            else:
                break
        tail_ok = self._tail_shareable(n)
        if (tail_ok and have == (n // ps) * ps
                and full_digest in self.prefix_index):
            have = n - 1
        # tokens a still-prefilling donor will register once it finishes
        for s in range(self.B):
            if self.slot_phase[s] != _PREFILL:
                continue
            o_digests, o_full = self._prompt_digests(self.slot_req[s])
            k = 0
            for i in range(min(max_full, len(o_digests))):
                if digests[i] == o_digests[i]:
                    k = (i + 1) * ps
                else:
                    break
            if tail_ok and k == (n // ps) * ps and o_full == full_digest:
                k = n - 1  # exact duplicate: the tail page will share too
            if k > have:
                return True
        return False

    def _admit(self) -> bool:
        """Move queued requests into free slots.  Paged admission is
        atomic per request and accounts the full private demand up front —
        shared prefix pages are mapped by reference and a copy-on-write
        fork page is pre-reserved, so an admitted request never allocates
        mid-flight.  Returns True if any request was admitted."""
        with span("serve.admit") as sp:
            rids = self._admit_queued()
            if sp and rids:
                sp.set_metadata(rid=" ".join(map(str, rids)))
        return bool(rids)

    def _admit_queued(self) -> List[int]:
        """_admit's work; returns the rids admitted, in slot order."""
        admitted: List[int] = []
        for slot in range(self.B):
            if self.slot_phase[slot] != _FREE or not self.queue:
                continue
            qi = self._next_admissible()
            if qi is None:
                break
            req = self.queue[qi]
            n_shared, state = 0, None
            if self.paged:
                # capacity was validated at submit(); a transient shortfall
                # here just waits for another request's pages to reclaim
                shared, n_shared, state, partial = self._lookup_prefix(req)
                k_full = len(shared) - (1 if partial else 0)
                # shard affinity: extend a shared chain on its donor's
                # shard so the whole slot stays device-local when it fits
                prefer = (self.allocator.shard_of(shared[0])
                          if shared else None)
                pages = self.allocator.alloc(self._pages_needed(req) - k_full,
                                             prefer_shard=prefer)
                if pages is None and self._held \
                        and not (self.slot_phase != _FREE).any():
                    # nothing in flight will ever reclaim: held prefix
                    # pages must yield so the head of the queue can run
                    # (its demand may not overlap what the holds cache)
                    self._drop_all_holds()
                    shared, n_shared, state, partial = \
                        self._lookup_prefix(req)
                    k_full = len(shared) - (1 if partial else 0)
                    prefer = (self.allocator.shard_of(shared[0])
                              if shared else None)
                    pages = self.allocator.alloc(
                        self._pages_needed(req) - k_full,
                        prefer_shard=prefer)
                if pages is None:
                    return admitted  # wait for reclamation
                self.allocator.share(shared)
                reserve = pages.pop() if partial else None
                row = shared + pages
                self.slot_pages[slot] = list(row) + (
                    [reserve] if reserve is not None else [])
                self.slot_reserve[slot] = reserve
                self.block_tables[slot] = 0
                self.block_tables[slot, :len(row)] = row
                self._slot_registered[slot] = n_shared \
                    // self.layout.page_size
                if shared:
                    self.stats["pages_shared"] += len(shared)
                    self.stats["shared_admissions"] += 1
            self.queue.pop(qi)
            if self.paged:
                self._prune_holds()
            req.admitted_at = time.perf_counter()
            self.stats["admissions"] += 1
            self.stats["queue_wait_s"] += req.admitted_at - req.queued_at
            self.slot_req[slot] = req
            self.slot_phase[slot] = _PREFILL
            self.slot_cursor[slot] = n_shared
            self.lengths[slot] = n_shared
            self._slot_keys[slot] = self._slot_key(req)
            self._slot_sampled[slot] = 0
            self._reset_slot_state(slot)
            if state is not None:
                self._restore_state(slot, state)
            admitted.append(req.rid)
        return admitted

    def _release(self, slot: int):
        if self.paged:
            pages = self.slot_pages[slot]
            if self.prefix_sharing and self.queue:
                # keep prefix pages a queued request still matches alive:
                # the slot's reference becomes an engine hold, released by
                # _prune_holds once nothing in the queue wants the page
                wanted = self._wanted_by_queue()
                keep = {p for p in pages
                        if p in wanted and p not in self._held
                        and self.allocator.refcount(p) == 1}
                self._held.update(keep)
                pages = [p for p in pages if p not in keep]
            self._evict(self.allocator.free(pages))
            self.slot_pages[slot] = []
            self.slot_reserve[slot] = None
            self.block_tables[slot] = 0
        self.lengths[slot] = 0
        self.slot_phase[slot] = _FREE
        self.slot_req[slot] = None

    def _retire(self, slot: int):
        self.done.append(self.slot_req[slot])
        self._release(slot)

    def preempt(self, slot: int) -> Optional[Request]:
        """Evict a mid-flight slot and requeue its request at the head of
        the queue; returns the requeued request (None for a free slot).

        The request is requeued BEFORE the slot releases so _release sees
        its own registered prompt pages as wanted-by-queue and turns them
        into engine holds instead of recycling them — on re-admission the
        prefix lookup maps those pages straight back and only the unshared
        tail re-prefills.  Emitted tokens are discarded and replayed: the
        sampling keys derive from (seed, draw index), so the rerun emits
        the identical stream regardless of when the preemption landed.
        Front ends that streamed tokens out already must dedup by count."""
        if self.slot_phase[slot] == _FREE:
            return None
        req = self.slot_req[slot]
        req.out_tokens = []
        req.queued_at = time.perf_counter()
        self.queue.insert(0, req)
        self._release(slot)
        self.stats["preemptions"] += 1
        return req

    def cancel(self, rid: int) -> bool:
        """Drop a queued (not yet admitted) request.  Holds that only this
        request's prefix was keeping alive are pruned immediately — a
        cancelled request must not pin pages."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                self.queue.pop(i)
                if self.paged:
                    self._prune_holds()
                return True
        return False

    # ------------------------------------------------------------------
    # prefill progression
    # ------------------------------------------------------------------

    def _finish_prompt(self, slot: int, tok: int):
        """Prompt complete: record the sampled first token, retire at
        prefill (eos / single-token budget) or move to decode."""
        req = self.slot_req[slot]
        req.out_tokens.append(tok)
        self.stats["first_tokens"] += 1
        self.stats["admit_to_first_token_s"] += \
            time.perf_counter() - req.admitted_at
        if req.max_new_tokens <= 1 or (
                req.eos_id is not None and tok == req.eos_id):
            self._retire(slot)  # finished at prefill: reclaim pages now
        else:
            self.next_token[slot] = tok
            self.slot_remaining[slot] = req.max_new_tokens - 1
            self.slot_phase[slot] = _DECODE

    def _prefill_programs_per_chunk(self, size: int) -> int:
        """Device programs the paged-attention stage of one prefill chunk
        issues per layer: 1 when the fused kernel applies (attention + KV
        encode + page scatter collapsed into a single Pallas program),
        else 3 (flash_attention, kv_encode, insert_chunk)."""
        if (self.paged and self.cfg.quant.fused_prefill
                and fused_prefill_span_ok(self.max_pages_per_slot,
                                          self.layout.page_size, size)):
            return 1
        return 3

    def _advance_prefill(self, slot: int):
        """Run one prompt chunk for a prefilling slot (per-slot path,
        batched_prefill=False)."""
        req = self.slot_req[slot]
        prompt = np.asarray(req.prompt, np.int32)
        lo = int(self.slot_cursor[slot])
        size = self._next_chunk(slot)
        with span("serve.prefill_chunk") as sp:
            if sp:
                sp.set_metadata(size=size, rows=1)
            if self.paged:
                self._ensure_writable(slot, lo, lo + size)
            tokens = jnp.asarray(prompt[None, lo:lo + size])
            cache = self._refresh_meta(self.cache)
            logits, self.cache = self._chunk(self.params, tokens, cache,
                                             jnp.int32(slot))
            self._count_chunk(1, 1, size)
            self.slot_cursor[slot] += size
            self.lengths[slot] += size
            self._register_pages(slot)
        if int(self.slot_cursor[slot]) >= len(prompt):
            with span("serve.first_token_wait"):
                tok = int(self._sample(logits[:, -1], [slot])[0])
            self._finish_prompt(slot, tok)

    def _count_chunk(self, live_rows: int, rows: int, size: int):
        """Counters of one chunk program of `rows` x `size` tokens, of which
        `live_rows` rows carry a prompt."""
        sizes = self.stats["prefill_batch_sizes"]
        sizes[live_rows] = sizes.get(live_rows, 0) + 1
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_device_programs"] += \
            self._prefill_programs_per_chunk(size)
        self.stats["prefill_live_tokens"] += live_rows * size
        self.stats["prefill_row_tokens"] += rows * size

    def _run_chunk_group(self, slots: List[int], size: int):
        """Advance every slot in `slots` by one chunk of `size` tokens in
        a single [batch_slots, size] program (cross-slot batched prefill).
        Non-group rows are masked: their length/block-table metadata is
        zeroed (paged writes land on the trash page) and the model reverts
        their batch-dim state rows against the input cache."""
        with span("serve.prefill_chunk") as sp:
            if sp:
                sp.set_metadata(size=size, rows=len(slots))
            tokens = np.zeros((self.B, size), np.int32)
            for s in slots:
                lo = int(self.slot_cursor[s])
                tokens[s] = np.asarray(self.slot_req[s].prompt,
                                       np.int32)[lo:lo + size]
                if self.paged:
                    self._ensure_writable(s, lo, lo + size)
            active = np.zeros(self.B, bool)
            active[slots] = True
            cache_in = self._refresh_meta(self.cache, active)
            logits, self.cache = self._chunk_batched(
                self.params, jnp.asarray(tokens), cache_in,
                jnp.asarray(active))
            self._count_chunk(len(slots), self.B, size)
            for s in slots:
                self.slot_cursor[s] += size
                self.lengths[s] += size
                self._register_pages(s)
        done = [s for s in slots if int(self.slot_cursor[s])
                >= len(self.slot_req[s].prompt)]
        if done:
            # sample over the fixed [B, V] batch (same trace as decode);
            # rows of unfinished slots draw from dummy keys, discarded
            live = np.zeros(self.B, bool)
            live[done] = True
            with span("serve.first_token_wait"):
                toks = self._sample(logits, list(range(self.B)), live=live)
            for s in done:
                self._finish_prompt(s, int(toks[s]))

    def _fill_slots(self) -> bool:
        """Admission + prefill progression for one engine step.  The
        per-step chunk budget applies per request: a request retiring at
        prefill frees its slot for the next queued one within the same
        step (so eos-at-prefill bursts never burn decode iterations).
        With batched_prefill, all slots whose next chunk shares a bucket
        size advance in one program per pass."""
        budget = self.prefill_chunks_per_step or None
        ran = False
        used: Dict[int, int] = {}  # chunks run per request this step
        while True:
            admitted = self._admit()
            todo = [s for s in range(self.B)
                    if self.slot_phase[s] == _PREFILL
                    and (budget is None
                         or used.get(id(self.slot_req[s]), 0) < budget)]
            if not todo:
                if not admitted:
                    break
                continue
            for s in todo:
                used[id(self.slot_req[s])] = \
                    used.get(id(self.slot_req[s]), 0) + 1
            if self.batched_prefill:
                groups: Dict[int, List[int]] = {}
                for s in todo:
                    groups.setdefault(self._next_chunk(s), []).append(s)
                for size in sorted(groups, reverse=True):
                    self._run_chunk_group(groups[size], size)
            else:
                for s in todo:
                    self._advance_prefill(s)
            ran = True
        return ran

    def step(self) -> bool:
        """One engine iteration: admit/prefill, then one decode step for
        every decoding slot.  Returns False when the engine is idle: no
        slot is decoding and no prefill remains in flight."""
        with span("serve.step"):
            self._fill_slots()
            decode_mask = self.slot_phase == _DECODE
            if not decode_mask.any():
                return bool((self.slot_phase == _PREFILL).any())
            if self.speculate_k:
                T = self._spec_span(decode_mask)
                if T >= 2:
                    with span("serve.spec_round"):
                        self._spec_round(decode_mask, T)
                    return True
            self._decode_round(decode_mask)
            return True

    def _decode_round(self, decode_mask):
        """One decode step for every slot in decode_mask."""
        with span("serve.refresh_meta"):
            if self.paged:
                for s in np.nonzero(decode_mask)[0]:
                    pos = int(self.lengths[s])
                    self._ensure_writable(int(s), pos, pos + 1)
            cache_in = self._refresh_meta(self.cache, decode_mask)
        with span("serve.decode_dispatch"):
            if self.fused_decode:
                # one device program per decode step: attention + logits
                # head + sampler fused; keys are built (and counters
                # advanced) exactly as the decomposed path would before its
                # sampler dispatch
                keys = self._sample_keys(list(range(self.B)),
                                         live=decode_mask)
                toks_all, new_cache = self._decode_sample(
                    self.params, jnp.asarray(self.next_token), cache_in,
                    keys, jnp.float32(self.temperature))
            else:
                logits, new_cache = self._decode(
                    self.params, jnp.asarray(self.next_token), cache_in)
            if (self.slot_phase == _PREFILL).any():
                # slots mid-prefill (interleaved mode) must not have their
                # recurrent/dense state rows advanced by this decode call
                mask = jnp.asarray(decode_mask)
                for name, leaf in new_cache.items():
                    bdim = self._state_bdim.get(name)
                    if name in ("length", "block_table") or bdim is None:
                        continue
                    shape = [1] * leaf.ndim
                    shape[bdim] = self.B
                    m = mask.reshape(shape)
                    new_cache[name] = jnp.where(m, leaf, self.cache[name])
            self.cache = new_cache
        # sample over the full fixed [B, V] batch (rows of non-decoding
        # slots draw from dummy keys and are discarded) so the jitted
        # sampler never retraces as slots retire
        slots = [s for s in range(self.B) if decode_mask[s]]
        self.stats["decode_steps"] += 1
        self.stats["decode_device_programs"] += 1 if self.fused_decode else 2
        self.stats["decode_rows"] += len(slots)
        with span("serve.token_wait"):
            if self.fused_decode:
                toks = np.asarray(toks_all, np.int32)[np.asarray(slots)]
            else:
                toks = self._sample(logits, list(range(self.B)),
                                    live=decode_mask)[np.asarray(slots)]
        with span("serve.retire"):
            for tok, slot in zip(toks, slots):
                req = self.slot_req[slot]
                req.out_tokens.append(int(tok))
                self.next_token[slot] = tok
                self.lengths[slot] += 1
                self.slot_remaining[slot] -= 1
                if self.slot_remaining[slot] <= 0 or (
                        req.eos_id is not None and int(tok) == req.eos_id):
                    self._retire(slot)

    # ------------------------------------------------------------------
    # speculative decoding
    # ------------------------------------------------------------------

    def _spec_span(self, decode_mask) -> int:
        """Tokens per speculative round this iteration.  Capped by the
        addressable tail of every live slot (a write past the block-table
        row would clip-wrap onto the slot's last page — insert_tokens/
        insert_chunk_batched clamp the page index) and by the longest
        remaining budget (drafting past every slot's budget is wasted
        work).  A span < 2 falls back to plain decode."""
        cap = self.max_pages_per_slot * self.layout.page_size
        slots = np.nonzero(decode_mask)[0]
        head = min(cap - int(self.lengths[s]) for s in slots)
        rem = max(int(self.slot_remaining[s]) for s in slots)
        return min(self.speculate_k, head, rem)

    def _spec_verify_keys(self, decode_mask, T: int, base):
        """Row keys for the verify dispatch, b-major to match the verify
        head's [B*T] row order: row (s, j) samples target token t_j with
        the key the plain decode loop would use for that very draw
        (fold_in(slot key, base + j)) — parity of the committed stream
        follows key-for-key."""
        if self.greedy:
            keys = self._spec_dummy_keys.get(T)
            if keys is None:
                keys = jax.random.split(self._base_key, self.B * T)
                self._spec_dummy_keys[T] = keys
            return keys
        return jnp.stack([
            jax.random.fold_in(self._slot_keys[s], base[s] + j)
            if decode_mask[s] else self._dummy_keys[0]
            for s in range(self.B) for j in range(T)])

    def _spec_round(self, decode_mask, T: int):
        """One speculative round over every decoding slot: T-1 draft
        proposals (cheap draft policy, plain decode steps) followed by ONE
        batched multi-query verify under the serve policy.  The verify
        re-encodes all T positions with the serve policy's KV codes before
        attending, so a committed token stream is bitwise identical to
        plain decode — only draws that commit advance the per-slot key
        counter, and lengths roll forward by exactly the committed count.
        """
        slots = [int(s) for s in np.nonzero(decode_mask)[0]]
        base = {s: int(self._slot_sampled[s]) for s in slots}
        inputs = np.zeros((self.B, T), np.int32)
        inputs[:, 0] = self.next_token
        with span("serve.refresh_meta"):
            for s in slots:
                pos = int(self.lengths[s])
                self._ensure_writable(s, pos, pos + T)
            cache = self._refresh_meta(self.cache, decode_mask)
        # ---- draft: propose d_1 .. d_{T-1}.  d_j guesses the target's
        # j-th draw, so it samples with that draw's key (base + j - 1) —
        # a draft whose logits match the target bitwise accepts 100%.
        cur = jnp.asarray(self.next_token)
        pool = self.cache
        for j in range(1, T):
            with span("serve.decode_dispatch"):
                logits, pool = self._draft_decode(self.draft_params, cur,
                                                  cache)
                if self.greedy:
                    keys = self._dummy_keys
                else:
                    keys = jnp.stack([
                        jax.random.fold_in(self._slot_keys[s],
                                           base[s] + j - 1)
                        if decode_mask[s] else self._dummy_keys[0]
                        for s in range(self.B)])
                toks = self._sampler(logits, keys,
                                     jnp.float32(self.temperature))
            with span("serve.token_wait"):
                toks = np.asarray(toks, np.int32)
            inputs[:, j] = toks
            cur = jnp.asarray(toks)
            if j < T - 1:
                with span("serve.refresh_meta"):
                    drafted = self.lengths + np.where(
                        decode_mask, j, 0).astype(np.int32)
                    cache = self._refresh_meta(pool, decode_mask,
                                               lengths=drafted)
        # the draft's page writes are placeholders: the verify pass below
        # re-inserts every one of the T positions with the serve policy's
        # codes (per layer, before its attention reads them)
        with span("serve.refresh_meta"):
            cache = self._refresh_meta(pool, decode_mask)
        with span("serve.decode_dispatch"):
            keys = self._spec_verify_keys(decode_mask, T, base)
            toks_bt, self.cache = self._verify(
                self.params, jnp.asarray(inputs), cache, keys,
                jnp.float32(self.temperature))
        with span("serve.token_wait"):
            toks = np.asarray(toks_bt, np.int32)
        self.stats["spec_rounds"] += 1
        self.stats["decode_steps"] += 1
        self.stats["decode_rows"] += len(slots)
        # per draft token: one draft decode + one sampler dispatch
        self.stats["decode_device_programs"] += 2 * (T - 1) + 1
        with span("serve.retire"):
            for s in slots:
                self._spec_commit(s, inputs, toks, T, base[s])

    def _spec_commit(self, s: int, inputs, toks, T: int, base: int):
        """Commit slot s's accepted tokens of a verified round."""
        req = self.slot_req[s]
        # accept the longest prefix whose drafts matched the verified
        # targets: t_j is trustworthy iff inputs[1..j] == t[0..j-1]
        n_acc = 1
        while n_acc < T and inputs[s, n_acc] == toks[s, n_acc - 1]:
            n_acc += 1
        self.stats["spec_draft_tokens"] += T - 1
        self.stats["spec_accepted_tokens"] += n_acc - 1
        commit = []
        for j in range(min(n_acc, int(self.slot_remaining[s]))):
            t = int(toks[s, j])
            commit.append(t)
            if req.eos_id is not None and t == req.eos_id:
                break
        c = len(commit)
        req.out_tokens.extend(commit)
        if not self.greedy:
            self._slot_sampled[s] = base + c
        self.lengths[s] += c
        self.next_token[s] = commit[-1]
        self.slot_remaining[s] -= c
        self.stats["spec_committed_tokens"] += c
        if self.slot_remaining[s] <= 0 or (
                req.eos_id is not None and commit[-1] == req.eos_id):
            self._retire(s)

    def run(self, max_iters: int = 10_000):
        it = 0
        while (self.queue or (self.slot_phase != _FREE).any()) \
                and it < max_iters:
            if not self.step():
                break
            it += 1
        return self.done
