"""Batched serving engine: continuous batching over a paged KV cache.

The engine owns ``n_slots`` decode lanes. By default (for families that
implement the paged protocol) the cache is **paged**: a shared pool of
fixed-size pages plus per-slot page tables (see
:mod:`repro.serving.kvcache`). Admission runs **chunked prefill at true
prompt length** — the prompt is processed in fixed-size chunks whose K/V
(or recurrent state) is written straight into the slot's pages, so
admission costs O(prompt pages) with no bucket padding, no
right-alignment, and no full-cache copy; ``lengths`` tracks real token
counts. Pages are allocated at admission (enough for prompt +
``max_new_tokens``, so decode can never run out mid-flight) and freed on
completion; when the pool is exhausted, requests simply wait in the queue.
Decode advances all active slots through one batched ``decode_paged`` step
using the paged flash-decode kernel.

**Iteration-level continuous batching** (the default): slots join and
leave the decode batch every step. Admission *begins* a prefill (pages
allocated, slot bound) and its chunks are pumped across subsequent steps
under a per-step token budget — each decode lane reserves one token, the
remainder goes to prefill — so a burst of long prompts cannot stall
in-flight decodes. The policy (admission order with priority aging, TTFT
deadlines, bounded cached-prefix bypass, preemption of the weakest active
slot back to the queue, load shedding) lives in
:mod:`repro.serving.scheduler`; ``scheduler=SchedulerConfig(
token_budget=None)`` selects the legacy synchronous mode (whole prompt
prefilled inside the admission call), kept as the non-continuous
reference for latency benchmarks. Preemption is token-exact: the victim's
pages are registered in the prefix trie, its committed tokens (minus the
last) become a ``resume`` suffix re-prefilled on re-admission, and greedy
determinism re-derives the final committed token.

**Prefix sharing (copy-on-write)**: the engine keeps a
:class:`~repro.serving.kvcache.PrefixIndex` — a trie mapping page-aligned
token prefixes to resident page chains. Admission looks up the longest
cached prefix of each prompt, bumps the matched pages' refcounts, installs
them into the slot's page table, and chunk-prefills only the uncached
suffix: the page-table indirection in the paged decode/prefill kernels
reads shared pages with no kernel change. Shared pages are read-only — if
a slot must write into a partially-filled shared page (a whole-prompt hit
whose final token is recomputed for first-token logits), it first copies
the page (COW) and writes into its private copy. Admission is
*prefix-aware*: under page pressure, a queued request whose prefix is
cached (and therefore needs fewer private pages) may be admitted while the
FIFO head waits for capacity. Families with recurrent state (SSM/hybrid)
fall back gracefully: the trie tracks would-be hits for stats, but
recurrent state is not page-addressable, so their prefill is never
skipped.

**Speculative decoding**: construct the engine with a paired ``draft``
model (a small same-vocab family member, see
``repro.configs.DRAFT_PAIRS``) and each decode step becomes a
draft+verify round: the draft proposes ``spec_k`` tokens by sequential
paged decode, the target verifies the whole window in ONE chunked paged
forward pass (``verify_paged``, a fold that is bitwise identical to
sequential decode — the exactness guarantee), and the longest matching
prefix commits 1..k+1 tokens. Rejection rolls back by page offset:
lengths stop at the accepted point; stale K/V past them sits beyond
every length mask and is rewritten before any read. The draft's paged
cache leaves live inside the engine cache under a ``draft_`` prefix,
addressed by the *same* page tables and pool pages, so COW, prefix
sharing, spill and snapshots cover them for free. Greedy spec decode is
token-for-token identical to non-speculative decode (enforced in
tier-1 tests); sampled lanes stay reproducible because their Gumbel
noise is keyed by (seed, position), which the verify window can replay.

**Decode-page sharing / fork**: completed requests register their
*generated* pages (not just the prompt) in the prefix trie, and
``fork()`` splits n sampling children off a live slot sharing every
full committed page copy-on-write — n-way fan-out shares all pages up
to the divergence point instead of stopping at the prompt boundary.

**Multi-host page spill**: with a
:class:`~repro.serving.kvcache.RemotePagePool` attached, reallocation
pressure that would destroy retained prefix-cache pages instead *lends*
the coldest ones (pool LRU order) to a neighbor cloudlet host, leaving
spill stubs in the trie. Admission that hits a spilled prefix recalls the
pages — batched, bounded by ``recall_budget`` per request — installs them
into fresh local pages, and chunk-prefills only the remaining suffix; the
scheduler then *recall-holds* the slot for the simulated transfer time
(``slot_hold`` decode steps) so borrowed-memory latency is accounted
without changing a single token. A peer's ``leave()`` (churn) revokes its
leases: the recall misses, the stub's subtree is dropped, and the prefix
is recomputed — never served stale.

**Multimodal families**: all six families run paged by default. VLM
prompts chunk their image embeddings *inline* — image rows occupy
ordinary cache positions/pages, keyed in the trie by content-derived
pseudo-tokens, so an identical image + shared text prefix hits the COW
path like any text prefix. Enc-dec requests additionally carry a
**cross-attention (encoder output) region**: a per-request page chain
filled once at admission by the family's ``prefill_cross`` (the encoder
runs exactly once per distinct input), refcounted so requests with
identical frames share one region, LRU-evictable and spillable to peer
hosts like any retained prefix page. Decoder-prompt prefix keys are
salted with the frames digest — the prompt K/V depends on the encoder
input through cross-attention, so identical text under different audio
never falsely shares pages.

The legacy dense path (``paged=False``) keeps the original
``(n_slots, max_seq)`` cache with bucket-padded prefill — retained as
the parity oracle and for engines that opt out of paging.

Greedy sampling keeps runs deterministic — a restored engine replays
identically, which is what lets the ad hoc cloud's continuity protocol
cover serving guests: an engine snapshot (page pool + page tables + slot
bookkeeping, or the dense cache) restored on another host continues
mid-generation without re-prefilling. Paged snapshots are proportional to
the pool size, not ``n_slots × max_seq`` — smaller continuity blobs on
harvested hosts.
"""

from __future__ import annotations

import base64
import json
import weakref
import zlib
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.serializer import deserialize_tree, serialize_tree
from repro.models.model_api import ModelFns
from repro.serving.scheduler import Scheduler, SchedulerConfig
from repro.serving.kvcache import (
    PagePool,
    PrefixIndex,
    RemotePagePool,
    SpilledPage,
    expand_prefill_cache,
    extract_page_payload,
    init_cache,
    init_paged_cache,
    page_payload_like,
    pages_needed,
    scatter_slot,
)

Pytree = Any

# Trie key namespaces for multimodal content. Text token ids are < 2^32
# and salted/digest-mixed keys stay < 2^70, so pseudo-tokens derived from
# modality bytes can never collide with (or be spoofed by) a text prompt,
# and the three key kinds can never collide with each other.
_MM_NS = 1 << 70                    # vlm image-embedding rows
_CROSS_NS = 2 << 70                 # enc-dec encoder-frame rows
_CROSS_PAD = 1 << 33                # cross-key pad sentinel (crc32 < 2^32)
_SALT_SHIFT = 34                    # frames-digest salt for enc-dec keys


def _content_keys(arr) -> list[int]:
    """One deterministic pseudo-token per modality row (image patch /
    audio frame): a CRC of the raw bytes, stable across processes so a
    restored engine's trie keys keep matching."""
    a = np.ascontiguousarray(np.asarray(arr))
    return [zlib.crc32(r.tobytes()) for r in a.reshape(-1, a.shape[-1])]


@dataclass
class Request:
    req_id: int
    prompt: list[int]
    max_new_tokens: int
    eos_id: int | None = None
    extra: dict = field(default_factory=dict)   # modality inputs (frames/embeds)
    # SLO scheduling (see repro.serving.scheduler): higher priority wins;
    # deadline_ms is a TTFT budget in simulated milliseconds from submission
    priority: int = 0
    deadline_ms: float | None = None
    arrival_step: int = 0
    # preemption: committed tokens (all but the last) re-prefilled after the
    # prompt on re-admission, so a preempted stream resumes token-exactly
    resume: list[int] = field(default_factory=list)
    # spill-backed preemption: cache positions held by the slot-spill
    # group lease-tracked under this request's id in the RemotePagePool
    # (0 = no spilled chain; ``resume`` stays set as the recall-miss
    # fallback while a chain is out)
    spill_len: int = 0
    shed: bool = False     # dropped by the scheduler, not completed
    # sampling: temperature 0 is greedy (the deterministic default);
    # temperature > 0 draws per-position Gumbel noise from ``seed`` so a
    # sampled stream is still a pure function of (prompt, seed) — forked
    # fan-out children differ only in their seeds
    temperature: float = 0.0
    seed: int = 0
    generated: list[int] = field(default_factory=list)
    slot: int | None = None
    done: bool = False
    # memo for derived trie keys / modality lengths (pure functions of the
    # immutable prompt+extra): not snapshotted, recomputed after restore
    key_cache: dict = field(default_factory=dict, repr=False)

    @property
    def text_len(self) -> int:
        return len(self.prompt) + len(self.generated)


def _bucket(n: int, minimum: int = 32) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _encode_extra(extra: dict) -> dict:
    """JSON-encode modality arrays (frames/embeds) for the snapshot meta."""
    out = {}
    for k, v in extra.items():
        a = np.asarray(v)
        out[k] = {
            "dtype": str(a.dtype),
            "shape": list(a.shape),
            "data": base64.b64encode(np.ascontiguousarray(a).tobytes()).decode(),
        }
    return out


def _decode_extra(enc: dict) -> dict:
    out = {}
    for k, ent in enc.items():
        dt = np.dtype(ent["dtype"])
        out[k] = np.frombuffer(
            base64.b64decode(ent["data"]), dt
        ).reshape(ent["shape"])
    return out


def _copy_pages(cache: Pytree, src: jax.Array, dst: jax.Array) -> Pytree:
    """COW: duplicate physical page ``src`` into ``dst`` in every paged
    leaf (``*_pages``, laid out ``(layers, n_pages, page, ...)``). Rows of
    ``dst`` past the copied prefix are dead — they are either overwritten
    by the suffix prefill/decode before being read, or masked causally."""
    return {
        k: (v.at[:, dst].set(v[:, src]) if k.endswith("_pages") else v)
        for k, v in cache.items()
    }


def _install_page(cache: Pytree, dst: jax.Array, vals: Pytree) -> Pytree:
    """Recall: write a lent page's deserialized payload into physical page
    ``dst`` of the paged leaves it carries (the inverse of
    :func:`~repro.serving.kvcache.extract_page_payload` — region-split
    payloads only hold one region's leaves)."""
    return {
        k: (v.at[:, dst].set(vals[k].astype(v.dtype)) if k in vals else v)
        for k, v in cache.items()
    }


class SlotLifecycle:
    """The slot-binding state machine every admission flavor shares.

    Three paths end in an active decode lane, and all must agree on the
    slot invariants (page-table row mirrors the chain, ``lengths`` counts
    the cache-resident positions, ``last_token`` is the last committed
    token):

    - **fresh prefill**: chunked prefill computes the prompt; the final
      chunk's argmax becomes the first committed token
      (:meth:`activate`);
    - **resume re-prefill**: a preempted request recomputes prompt +
      ``resume`` tokens and :meth:`activate` re-derives (and verifies)
      the final committed token instead of emitting a new one;
    - **recall resume**: the victim's spilled chain is recalled and
      installed verbatim — :meth:`resume_recalled` rebinds the slot with
      *zero* recomputed tokens, and the next decode step continues from
      the last committed token as if the preemption never happened.
    """

    def __init__(self, engine: "ServeEngine"):
        # a proxy, not a reference: a cycle would keep a dropped engine's
        # params and KV pool on the device until the cyclic GC runs
        self.eng = weakref.proxy(engine)

    def bind(self, slot: int, req: Request, chain: list[int]) -> None:
        """Install ``chain`` as the slot's page-table row and bind the
        request to the lane (paged engines only)."""
        eng = self.eng
        eng.slot_pages[slot] = list(chain)
        eng.page_table[slot, :] = 0
        eng.page_table[slot, : len(chain)] = chain
        eng.slot_req[slot] = req.req_id
        req.slot = slot

    def activate(self, slot: int, req: Request, first: int,
                 length: int) -> None:
        """Prefill finished at ``length`` positions producing logits whose
        argmax is ``first``: commit the first token — or, for a request
        resuming from a preemption, verify that the recomputed token
        re-derives the already-committed one (greedy decode is
        deterministic; a mismatch means the cache was rebuilt wrong)."""
        eng = self.eng
        resumed = bool(req.generated)
        if resumed:
            committed = req.generated[len(req.resume)]
            if first != committed:
                eng.stats["resume_mismatches"] += 1
            first = committed
            req.resume = []
            req.key_cache.pop("admit_keys", None)
        else:
            req.generated.append(first)
        req.slot = slot
        eng.slot_req[slot] = req.req_id
        eng.lengths[slot] = length
        eng.last_token[slot] = first
        if not resumed and req.eos_id is not None and first == req.eos_id:
            req.done = True
            req.slot = None
            eng._release_slot(slot)

    def resume_recalled(self, slot: int, req: Request, length: int) -> None:
        """Recall hit: the slot's cache already holds every committed
        position (installed verbatim from the spilled chain), so the
        stream picks up at its last committed token — no re-prefill, no
        re-derivation, nothing to verify."""
        eng = self.eng
        req.resume = []
        req.key_cache.pop("admit_keys", None)
        eng.lengths[slot] = length
        eng.last_token[slot] = req.generated[-1]


@dataclass
class _PrefillTask:
    """One admission's chunked prefill, in flight across engine steps
    (iteration-level continuous batching). The slot's pages are allocated
    and its request bound when the task is created; the slot's page-table
    *row* stays on the scratch page until the last chunk lands, so the
    batched decode's inert write for this lane can never scribble on real
    (possibly shared) pages — chunks write through a private row built
    from ``slot_pages`` instead."""

    req: Request
    tlen: int                    # mm + prompt + resume positions
    mm: int                      # inline modality positions (vlm)
    ptoks: list[int]             # prompt + resume (text positions)
    offset: int                  # next position to compute
    key_tokens: list[int]        # trie keys registered at completion
    embeds: Any | None = None    # (mm, d) image rows, vlm only
    logits: Any | None = None    # last chunk's logits (first-token source)


class ServeEngine:
    def __init__(
        self,
        model: ModelFns,
        params: Pytree,
        *,
        n_slots: int = 8,
        max_seq: int = 1024,
        max_cross_seq: int | None = None,
        cache_dtype=jnp.bfloat16,
        paged: bool | None = None,
        page_size: int = 64,
        n_pages: int | None = None,
        prefill_chunk: int = 256,
        prefix_share: bool | None = None,
        remote_pool: RemotePagePool | None = None,
        recall_budget: int = 8,
        write_behind: bool = False,
        decode_step_s: float = 5e-3,
        active_cap: int | None = None,
        scheduler: SchedulerConfig | None = None,
        draft: ModelFns | None = None,
        draft_params: Pytree | None = None,
        spec_k: int = 4,
    ):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        # elastic serving: a cell may cap concurrent decode lanes below
        # n_slots when its survivor mesh shrinks (slots stay allocated so
        # snapshots keep their shape; admission just stops above the cap)
        self.active_cap = active_cap
        # SLO policy: admission order, aging, bypass, preemption, shedding,
        # and the per-step token budget (None budget = legacy synchronous)
        self.sched = Scheduler(scheduler, decode_step_s=decode_step_s)
        # slot -> in-flight chunked prefill (continuous batching only; the
        # synchronous mode drains each task within its admission call)
        self.prefilling: dict[int, _PrefillTask] = {}
        self.last_step_tokens = 0  # decode lanes + prefill chunk tokens
        self._step_prefill_tokens = 0  # chunk tokens since the last _admit
        self._has_deadlines = False
        self.max_seq = max_seq
        if paged is None:
            paged = model.supports_paged
        elif paged and not model.supports_paged:
            raise ValueError(
                f"{model.cfg.arch_id}: family has no paged serving path; "
                "use paged=False"
            )
        self.paged = paged
        # multimodal capabilities (orthogonal to paged): inline modality
        # embeddings in the prompt (vlm) / a paged cross-attention region
        # written once per request by the encoder (enc-dec)
        self._mm = getattr(model, "paged_mm_inline", False)
        self.cross = paged and model.supports_paged_cross
        # speculative decoding: a paired draft model proposes spec_k
        # tokens per step; the target verifies the whole window in one
        # chunked paged forward pass. The draft's paged cache leaves ride
        # inside self.cache under a draft_ prefix, addressed by the SAME
        # page tables / pool pages as the target — so COW, prefix sharing,
        # spill and snapshots cover the draft cache with no extra
        # bookkeeping (every *_pages helper matches the suffix).
        self._draft = draft
        self.draft_params = draft_params
        self.spec_k = spec_k
        if draft is not None:
            if not paged:
                raise ValueError("speculative decoding needs the paged cache")
            if self._mm or model.supports_paged_cross:
                raise ValueError(
                    "speculative decoding covers text-only paged families"
                )
            if not model.supports_spec_decode:
                raise ValueError(
                    f"{model.cfg.arch_id}: family has no paged verify path"
                )
            if not draft.supports_spec_decode:
                raise ValueError(
                    f"{draft.cfg.arch_id}: draft family cannot share paged "
                    "decode state"
                )
            if draft.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft.cfg.vocab_size} != target vocab "
                    f"{model.cfg.vocab_size}: accepted draft tokens must be "
                    "target tokens"
                )
            if spec_k < 1:
                raise ValueError("spec_k must be >= 1")
        self.lengths = np.zeros((n_slots,), np.int32)
        self.last_token = np.zeros((n_slots,), np.int32)
        self.slot_req: list[int | None] = [None] * n_slots
        # the shared bind/activate tail of every admission flavor (fresh
        # prefill, resume re-prefill, recall resume)
        self.lifecycle = SlotLifecycle(self)
        self.queue: list[Request] = []
        self.requests: dict[int, Request] = {}
        self._req_counter = 0
        self.steps = 0
        self.stats = {
            "prefill_tokens": 0,         # prompt tokens actually computed
            "prefill_tokens_shared": 0,  # prompt tokens served from shared pages
            "prefix_hit_tokens": 0,      # tokens covered by trie hits (incl. would-be)
            "prefix_hits": 0,
            "cow_copies": 0,
            "peak_pages": 0,             # high-water mark of live pool pages
            # spill tier (all zero when no remote pool is attached)
            "pages_spilled": 0,          # cold pages lent to a peer
            "pages_recalled": 0,         # lent pages pulled back on a hit
            "recall_misses": 0,          # recalls lost to peer churn
            "prefix_evictions": 0,       # trie nodes whose content was lost
            "recall_hold_steps": 0,      # decode steps slots spent recall-held
            # high-water mark of pages whose content is resident locally
            # (live + free-but-cached) — what spilling actually shrinks
            "peak_resident_pages": 0,
            # cross-attention (encoder output) region, enc-dec only
            "cross_regions_computed": 0,  # encoder runs at admission
            "cross_regions_shared": 0,    # regions served from cached pages
            "cross_pages_shared": 0,      # pages those shared regions cover
            # teacher-forced replay (elastic cell mid-stream resume)
            "forced_tokens": 0,           # decode steps with a forced token
            "forced_mismatches": 0,       # forced token != engine's argmax
            # SLO scheduler (continuous batching)
            "preemptions": 0,             # active slots sent back to queue
            "shed_expired": 0,            # waiting requests past deadline
            "shed_overflow": 0,           # waiting requests over max_queue
            "resume_mismatches": 0,       # resumed recompute != committed
            # spill-backed preemption (one slot lifecycle: a preemption
            # is a page movement, not a recompute)
            "preempt_spills": 0,          # preemptions whose chain spilled
            "recall_resumes": 0,          # re-admissions served by recall
            "resume_fallbacks": 0,        # spilled chains lost → re-prefill
            # tokens recomputed while resuming via recall: zero by
            # construction (a hit restores the whole chain verbatim),
            # counter-asserted so a silent regression to recompute fails
            "recall_resume_prefill_tokens": 0,
            "pages_staged": 0,            # write-behind staged full pages
            # speculative decoding (zero without a draft model)
            "spec_rounds": 0,             # lane-rounds of draft+verify
            "spec_proposed": 0,           # draft tokens proposed
            "spec_accepted": 0,           # draft tokens the target accepted
            # sampling fan-out (fork)
            "forks": 0,                   # children forked off live slots
            "fork_shared_pages": 0,       # full pages children share (logical)
        }

        if paged:
            self.page_size = page_size
            self.max_pages = -(-max_seq // page_size)
            # cross-attention region capacity (enc-dec): pages per slot for
            # the encoder output, on top of the decoder self-attn pages
            self.max_cross_seq = (
                (max_cross_seq if max_cross_seq is not None else max_seq)
                if self.cross else 0
            )
            self.max_cross_pages = -(-self.max_cross_seq // page_size)
            # default pool: full capacity (one spare page for scratch);
            # pass a smaller n_pages to oversubscribe slots against the pool
            self.n_pages = (
                n_pages if n_pages is not None
                else n_slots * (self.max_pages + self.max_cross_pages) + 1
            )
            self.pool = PagePool(self.n_pages)
            self.page_table = np.zeros((n_slots, self.max_pages), np.int32)
            self.slot_pages: list[list[int]] = [[] for _ in range(n_slots)]
            if self.cross:
                self.cross_table = np.zeros(
                    (n_slots, self.max_cross_pages), np.int32
                )
                self.cross_len = np.zeros((n_slots,), np.int32)
                self.slot_cross_pages: list[list[int]] = [
                    [] for _ in range(n_slots)
                ]
                self._prefill_cross = jax.jit(model.prefill_cross)
            self.prefill_chunk = min(prefill_chunk,
                                     self.max_pages * page_size)
            # prefix sharing: on by default; families with recurrent state
            # (not page-addressable) keep trie bookkeeping only
            enabled = True if prefix_share is None else prefix_share
            self.prefix_cache = enabled
            self.prefix_share = enabled and model.supports_prefix_sharing
            self.prefix_index = PrefixIndex(page_size)
            self._phantom_next = self.n_pages  # bookkeeping-only node ids
            # spill tier: lend cold cached pages to neighbor hosts instead
            # of evicting them (only meaningful with page-addressable
            # prefix sharing — recurrent state cannot be lent page-wise)
            self.remote_pool = remote_pool
            self.recall_budget = recall_budget
            self.decode_step_s = decode_step_s
            self.spill = remote_pool is not None and self.prefix_share
            # write-behind staging: lend each decode page to a peer the
            # moment it fills, so a later preemption ships only the
            # unstaged remainder (cross regions have their own spill path)
            self.write_behind = bool(write_behind) and self.spill \
                and not self.cross
            self.spilled: dict[int, SpilledPage] = {}
            self._spill_next = self.n_pages  # stub ids, never page-table ids
            self.slot_hold = np.zeros((n_slots,), np.int32)
            self.cache = init_paged_cache(model, n_slots, self.n_pages,
                                          page_size, cache_dtype)
            if draft is not None:
                # same n_slots / n_pages / page_size: physical page ids in
                # the target's page tables address the draft leaves too
                dcache = init_paged_cache(draft, n_slots, self.n_pages,
                                          page_size, cache_dtype)
                for k, v in dcache.items():
                    self.cache["draft_" + k] = v

                # both models' fns rebuild their cache dict, so each side
                # runs on its own view and the other side's leaves are
                # carried through unchanged
                def _split(cache):
                    t = {k: v for k, v in cache.items()
                         if not k.startswith("draft_")}
                    d = {k[6:]: v for k, v in cache.items()
                         if k.startswith("draft_")}
                    return t, d

                def _join(t, d):
                    out = dict(t)
                    out.update({"draft_" + k: v for k, v in d.items()})
                    return out

                def _d_decode(dparams, cache, batch):
                    t, d = _split(cache)
                    logits, d = draft.decode_paged(dparams, d, batch)
                    return logits, _join(t, d)

                def _d_prefill(dparams, cache, batch, *, offset):
                    t, d = _split(cache)
                    _, d = draft.prefill_chunk(dparams, d, batch,
                                               offset=offset)
                    return _join(t, d)

                def _t_decode(params, cache, batch):
                    t, d = _split(cache)
                    logits, t = model.decode_paged(params, t, batch)
                    return logits, _join(t, d)

                def _t_prefill(params, cache, batch, *, offset):
                    t, d = _split(cache)
                    logits, t = model.prefill_chunk(params, t, batch,
                                                    offset=offset)
                    return logits, _join(t, d)

                def _t_verify(params, cache, batch):
                    t, d = _split(cache)
                    logits, t = model.verify_paged(params, t, batch)
                    return logits, _join(t, d)

                self._draft_decode = jax.jit(_d_decode)
                self._draft_prefill = jax.jit(_d_prefill,
                                              static_argnames=("offset",))
                self._verify_paged = jax.jit(_t_verify)
                self._decode_paged = jax.jit(_t_decode)
                self._prefill_chunk = jax.jit(_t_prefill,
                                              static_argnames=("offset",))
            else:
                self._decode_paged = jax.jit(model.decode_paged)
                self._prefill_chunk = jax.jit(
                    model.prefill_chunk,
                    static_argnames=(
                        ("offset", "mm_len") if self._mm else ("offset",)
                    ),
                )
            # donate the cache: COW duplicates one page in place instead
            # of materializing a second copy of every page pool
            self._copy_pages = jax.jit(_copy_pages, donate_argnums=(0,))
            self._install_page = jax.jit(_install_page, donate_argnums=(0,))
            self._admit_ready = True  # new submits / freed pages to try
        else:
            if remote_pool is not None:
                raise ValueError(
                    "the spill tier needs the paged cache; use paged=True"
                )
            self.write_behind = False
            self.cache = init_cache(model, n_slots, max_seq, cache_dtype)
            self._prefill = jax.jit(model.prefill)
            self._decode = jax.jit(model.decode_step)
            self._scatter = jax.jit(scatter_slot)

    # --------------------------------------------------------- multimodal
    def _mm_len(self, req: Request) -> int:
        """Cache positions occupied by inline modality embeddings (vlm
        image rows) ahead of the text prompt; 0 for text-only families."""
        if self._mm and "embeds" in req.extra:
            if "mm_len" not in req.key_cache:
                req.key_cache["mm_len"] = int(
                    np.asarray(req.extra["embeds"]).shape[-2]
                )
            return req.key_cache["mm_len"]
        return 0

    def _total_len(self, req: Request) -> int:
        return self._mm_len(req) + len(req.prompt)

    def _frames_salt(self, req: Request) -> int:
        """CRC of the request's whole frames payload: mixed into every
        enc-dec trie key so regions/prompts only ever share on an exact
        full-input match."""
        if "salt" not in req.key_cache:
            req.key_cache["salt"] = zlib.crc32(
                np.ascontiguousarray(np.asarray(req.extra["frames"])).tobytes()
            )
        return req.key_cache["salt"]

    def _key_tokens(self, req: Request) -> list[int]:
        """Trie key sequence for the prompt pages. VLM image rows occupy
        real cache positions, so their content pseudo-tokens are simply
        prepended — an identical image + shared text prefix then walks the
        trie like any text prefix. Enc-dec prompt K/V depends on the
        encoder input through cross-attention, so the text tokens are
        salted with the frames digest: identical transcripts of different
        audio never falsely share pages. Memoized on the request (pure
        function of the immutable prompt+extra) so queued requests are
        not re-hashed on every admission scan."""
        if "key_tokens" not in req.key_cache:
            if self._mm and "embeds" in req.extra:
                ks = [_MM_NS | c
                      for c in _content_keys(req.extra["embeds"])] + req.prompt
            elif self.cross and "frames" in req.extra:
                salt = self._frames_salt(req)
                ks = [t + ((salt + 1) << _SALT_SHIFT) for t in req.prompt]
            else:
                ks = list(req.prompt)
            req.key_cache["key_tokens"] = ks
        return req.key_cache["key_tokens"]

    def _gen_keys(self, req: Request, toks: list[int]) -> list[int]:
        """Trie keys for *generated* tokens (preemption resume / the pages
        a preempted slot leaves behind): plain token ids, salted with the
        frames digest for enc-dec exactly like the prompt keys."""
        if self.cross and "frames" in req.extra:
            salt = self._frames_salt(req)
            return [t + ((salt + 1) << _SALT_SHIFT) for t in toks]
        return list(toks)

    def _admit_keys(self, req: Request) -> list[int]:
        """Trie key sequence for admission: the prompt keys plus one key
        per ``resume`` token (a preempted request re-prefills its
        committed tokens, so its cache positions extend past the prompt).
        Memoized until the resume suffix changes."""
        if "admit_keys" not in req.key_cache:
            ks = self._key_tokens(req)
            if req.resume:
                ks = ks + self._gen_keys(req, req.resume)
            req.key_cache["admit_keys"] = ks
        return req.key_cache["admit_keys"]

    def _cross_keys(self, req: Request) -> list[int]:
        """Trie key sequence for the encoder-output region: one content
        pseudo-token per frame, padded to a page multiple with a sentinel
        so the whole region maps to full trie blocks. Every key mixes in
        the *whole-frames* digest: the encoder is non-causal, so a region
        is only reusable on an exact full-input match — without the
        digest, frames that are a page-aligned prefix of a longer cached
        input would produce a false "full-chain" hit."""
        if "cross_keys" not in req.key_cache:
            ns = _CROSS_NS | (self._frames_salt(req) << _SALT_SHIFT)
            ks = [ns | c for c in _content_keys(req.extra["frames"])]
            pad = -len(ks) % self.page_size
            req.key_cache["cross_keys"] = ks + [ns | _CROSS_PAD] * pad
        return req.key_cache["cross_keys"]

    def _n_frames(self, req: Request) -> int:
        if "n_frames" not in req.key_cache:
            req.key_cache["n_frames"] = int(
                np.asarray(req.extra["frames"]).shape[-2]
            )
        return req.key_cache["n_frames"]

    # ------------------------------------------------------------- interface
    def submit(self, prompt: list[int], *, max_new_tokens: int = 16,
               eos_id: int | None = None, extra: dict | None = None,
               priority: int = 0,
               deadline_ms: float | None = None,
               temperature: float = 0.0, seed: int = 0) -> Request:
        extra = dict(extra or {})
        probe = Request(-1, list(prompt), max_new_tokens, eos_id, extra)
        allowed = ({"embeds"} if self._mm else set()) | (
            {"frames"} if self.cross else set()
        )
        if self.paged and set(extra) - allowed:
            raise ValueError(
                f"unsupported modality extras {sorted(set(extra) - allowed)} "
                "for this family's paged path; construct the engine with "
                "paged=False"
            )
        if self._mm and "embeds" not in extra:
            raise ValueError("vlm requests need extra={'embeds': ...}")
        if self.cross and "frames" not in extra:
            raise ValueError("enc-dec requests need extra={'frames': ...}")
        tlen = self._total_len(probe)
        if not 1 <= len(prompt) or not tlen < self.max_seq:
            raise ValueError(
                f"prompt length {len(prompt)} (+{tlen - len(prompt)} "
                f"modality positions) outside [1, {self.max_seq})"
            )
        if self.paged:
            need = pages_needed(
                min(tlen + max_new_tokens, self.max_seq), self.page_size
            )
            if self.cross:
                n_cp = pages_needed(self._n_frames(probe), self.page_size)
                if (n_cp > self.max_cross_pages
                        or self._n_frames(probe) > self.max_cross_seq):
                    raise ValueError(
                        f"{self._n_frames(probe)} frames exceed "
                        f"max_cross_seq={self.max_cross_seq}"
                    )
                need += n_cp
            if need > self.n_pages - 1:
                raise ValueError(
                    f"request needs {need} pages but the pool only has "
                    f"{self.n_pages - 1} allocatable pages"
                )
        req = Request(self._req_counter, list(prompt), max_new_tokens, eos_id,
                      extra, priority=priority, deadline_ms=deadline_ms,
                      arrival_step=self.steps,
                      temperature=temperature, seed=seed)
        if deadline_ms is not None:
            self._has_deadlines = True
        self._req_counter += 1
        self.requests[req.req_id] = req
        self.queue.append(req)
        if self.paged:
            self._admit_ready = True
        return req

    def pending(self) -> int:
        return len(self.queue) + sum(s is not None for s in self.slot_req)

    def cancel(self, req_id: int) -> Request:
        """Withdraw a request: dequeue it if waiting, release its slot
        (freeing its private pages; shared pages just drop one ref) if
        active. Returns the removed request — its ``generated`` tokens so
        far stay on it, so a scheduler shedding load can report the
        partial stream instead of silently dropping it."""
        req = self.requests.pop(req_id)
        if req in self.queue:
            self.queue.remove(req)
        if req.slot is not None:
            self._release_slot(req.slot)
            req.slot = None
        if self.paged and self.remote_pool is not None:
            # drop the slot-spill group (preempted chain or write-behind
            # staged pages) — nobody will ever recall it
            self.remote_pool.release_slot(req_id)
            req.spill_len = 0
        return req

    def reset_stats(self) -> None:
        """Zero the counters (e.g. between a warmup and a measured pass)."""
        for k in self.stats:
            self.stats[k] = 0

    def step(self, force_tokens: dict[int, int] | None = None) -> int:
        """Admit waiting requests, then advance every active slot by one
        token. Returns the number of active slots that generated.

        ``force_tokens`` maps req_id -> token id to **teacher-force** this
        step: the slot's K/V is still written from its real last token
        and the model's argmax is still computed (and compared — a
        difference counts as a ``forced_mismatch``), but the *committed*
        token is the forced one. The elastic cell uses this to replay a
        resumed stream token-for-token: whatever the restored engine
        would now sample, the tokens already streamed to the client are
        what the cache is rebuilt from. Forcing is keyed by request id,
        not slot index, so replay is **slot-stable**: a preemption (or
        any re-admission) that moves a stream to a different lane
        mid-replay keeps receiving its own committed tokens.

        Slots whose admission recalled spilled pages are **recall-held**
        for the simulated transfer time (``slot_hold`` decode steps): the
        scheduler keeps them admitted (their pages are pinned) but skips
        their lanes until the hold drains, so borrowed-memory latency
        costs wall-clock steps without ever changing tokens. A held lane
        still rides through the batched kernel — its K/V write is
        idempotent (same token, same position as its first real step) and
        its logits are discarded.

        With a continuous-batching scheduler (the default: see
        :mod:`repro.serving.scheduler`) each step additionally sheds
        expired/overflow load, admits under the SLO admission order,
        advances in-flight prefill chunks under the step's token budget
        (decode lanes reserve one token each; leftover budget goes to
        prefill), and preempts the weakest active slot when a blocked
        waiting request outranks it — slots join and leave the decode
        batch every iteration. ``last_step_tokens`` records the step's
        decode + prefill token total for budget accounting.
        """
        if self.paged and not self.sched.cfg.synchronous:
            self._shed_pass()
            self._admission_scan()
            lanes = [
                i for i, r in enumerate(self.slot_req)
                if r is not None and i not in self.prefilling
                and not self.slot_hold[i]
            ]
            # a speculating lane consumes a whole draft+verify window of
            # the step's token budget, not one token — prefill gets what
            # is left after that reservation
            per_lane = (self._spec_tokens_per_lane()
                        if force_tokens is None and self._spec_feasible(lanes)
                        else 1)
            prefill_used = self._pump_prefill(
                self.sched.prefill_budget(len(lanes), bool(self.prefilling),
                                          tokens_per_lane=per_lane)
            )
            self._preempt_pass()
        else:
            prefill_used = self._admit()
        if self.paged:
            held = self.slot_hold > 0
            active = [i for i, r in enumerate(self.slot_req)
                      if r is not None and not held[i]
                      and i not in self.prefilling]
            self.slot_hold[held] -= 1  # transfers progress as time passes
            if not active:
                if np.any(held) or self.prefilling:
                    # recall waits drain / chunks ran: time passes
                    self.steps += 1
                self.last_step_tokens = prefill_used
                return 0
        else:
            active = [i for i, r in enumerate(self.slot_req)
                      if r is not None]
        if not active:
            self.last_step_tokens = prefill_used
            return 0
        if force_tokens is None and self._spec_feasible(active):
            # speculative rounds complete within one step(): spec holds no
            # cross-step state, so snapshot/preempt/cancel never see a
            # half-verified draft
            self._spec_step(active)
            self.steps += 1
            self.last_step_tokens = (
                prefill_used + len(active) * self._spec_tokens_per_lane()
            )
            return len(active)
        tokens = jnp.asarray(self.last_token)[:, None]
        positions = jnp.asarray(self.lengths)
        if self.paged:
            batch = {
                "tokens": tokens,
                "positions": positions,
                "page_table": jnp.asarray(self.page_table),
            }
            if self.cross:
                batch["cross_page_table"] = jnp.asarray(self.cross_table)
                batch["cross_len"] = jnp.asarray(self.cross_len)
            logits, self.cache = self._decode_paged(self.params, self.cache,
                                                    batch)
            if self._draft is not None:
                # keep the draft cache position-complete through
                # non-speculative steps (forced replay, budget fallback):
                # draft K/V holes would only degrade later proposals, but
                # there is no reason to accept the degradation
                _, self.cache = self._draft_decode(self.draft_params,
                                                   self.cache, batch)
        else:
            logits, self.cache = self._decode(
                self.params, self.cache,
                {"tokens": tokens, "positions": positions},
            )
        next_tokens = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        rows = (np.asarray(logits, np.float32)
                if self._any_sampled(active) else None)
        for i in active:
            req = self.requests[self.slot_req[i]]
            if rows is not None and req.temperature > 0:
                tok = self._choose(rows[i], req, int(self.lengths[i]))
            else:
                tok = int(next_tokens[i])
            if force_tokens is not None and req.req_id in force_tokens:
                forced = int(force_tokens[req.req_id])
                self.stats["forced_tokens"] += 1
                if forced != tok:
                    self.stats["forced_mismatches"] += 1
                tok = forced
            self._commit_token(i, req, tok)
        self.steps += 1
        self.last_step_tokens = prefill_used + len(active)
        return len(active)

    def run(self, max_steps: int = 10_000) -> list[Request]:
        while self.pending() and max_steps > 0:
            self.step()
            max_steps -= 1
        return [r for r in self.requests.values() if r.done]

    # ------------------------------------------------- speculation / sampling
    def _spec_tokens_per_lane(self) -> int:
        """Step-budget cost of one speculating lane: k draft proposals,
        one draft cache-fill step (position n+k, so a fully accepted
        window leaves no draft K/V hole), and a k+1-token verify."""
        return 2 * self.spec_k + 2

    def _spec_feasible(self, lanes: list[int]) -> bool:
        """Speculate this step? Needs a draft, every lane at least
        ``spec_k + 1`` positions from the sequence cap (the verify window
        must never write past ``max_seq``), and — under a continuous
        scheduler — a token budget that covers every lane's window
        (otherwise the step falls back to plain decode; the synchronous
        mode always speculates)."""
        if self._draft is None or not lanes:
            return False
        k = self.spec_k
        if any(self.lengths[i] + k + 1 >= self.max_seq for i in lanes):
            return False
        if self.sched.cfg.synchronous:
            return True
        return (len(lanes) * self._spec_tokens_per_lane()
                <= self.sched.cfg.token_budget)

    def _any_sampled(self, lanes: list[int]) -> bool:
        return any(self.requests[self.slot_req[i]].temperature > 0
                   for i in lanes)

    @staticmethod
    def _choose(row: np.ndarray, req: Request, pos: int) -> int:
        """The committed token for logits ``row`` computed at cache
        position ``pos``: greedy argmax at temperature 0, else argmax of
        ``row/T`` plus Gumbel noise drawn deterministically from
        ``(seed, pos)`` — the Gumbel-max trick samples the softmax, and
        keying the noise by *position* (not sampling history) makes a
        sampled stream re-derivable token-for-token by the speculative
        verify window and by preemption resume alike."""
        if req.temperature <= 0:
            return int(np.argmax(row))
        rng = np.random.default_rng([int(req.seed) & 0xFFFFFFFF, int(pos)])
        u = rng.random(row.shape[-1])
        g = -np.log(-np.log(u + 1e-20) + 1e-20)
        return int(np.argmax(row.astype(np.float64) / req.temperature + g))

    def _commit_token(self, i: int, req: Request, tok: int) -> bool:
        """Append one committed token to lane ``i``; returns True when
        the request completed (slot released)."""
        req.generated.append(tok)
        self.lengths[i] += 1
        self.last_token[i] = tok
        if (
            (req.eos_id is not None and tok == req.eos_id)
            or len(req.generated) >= req.max_new_tokens
            or self.lengths[i] >= self.max_seq - 1
        ):
            self._finish_request(i, req)
            return True
        if self.write_behind and self.lengths[i] % self.page_size == 0:
            # a chain page just filled; full pages are immutable (every
            # position below ``lengths`` is committed, and speculative
            # writes only land at positions >= ``lengths``), so its bytes
            # can pre-stage on a peer now — a later preemption then ships
            # only the unstaged remainder. Fail-soft on peer pressure.
            idx = int(self.lengths[i]) // self.page_size - 1
            page = self.slot_pages[i][idx]
            if self.remote_pool.stage_page(
                    req.req_id, idx, extract_page_payload(self.cache, page)):
                self.stats["pages_staged"] += 1
        return False

    def _finish_request(self, i: int, req: Request) -> None:
        """Completion: register the slot's pages — prompt *and* decode-
        generated — in the prefix trie before release, so a later prompt
        that extends this request's transcript (the multi-turn pattern)
        shares pages up to the divergence point instead of stopping at
        the old prompt boundary. Only fully committed pages are keyed
        (``lengths // page_size``), so a page's stale tail beyond the
        last committed token is never served as cached content."""
        if self.paged and self.prefix_share:
            covered = int(self.lengths[i])
            gen = req.generated[: covered - self._total_len(req)]
            self._register_prefix(
                self._key_tokens(req) + self._gen_keys(req, gen),
                self.slot_pages[i],
            )
        if self.paged and self.remote_pool is not None:
            # write-behind staged pages die with the request; a spilled
            # chain cannot exist here (the request was actively decoding)
            self.remote_pool.release_slot(req.req_id)
        req.done = True
        req.slot = None
        self._release_slot(i)

    def _spec_step(self, active: list[int]) -> None:
        """One speculative round for every active lane, batched.

        With ``lengths[i] = n``: the draft proposes ``d1..dk`` by k
        sequential paged decode steps feeding ``[last, d1..d_{k-1}]`` at
        positions ``n..n+k-1`` (plus one cache-fill step for ``d_k`` at
        ``n+k``), then the target verifies the whole window
        ``[last, d1..dk]`` in ONE chunked paged forward pass whose fold
        is bitwise identical to k+1 sequential decode steps — logits
        ``L_0..L_k`` with ``g_{j+1}`` chosen from ``L_j``. The longest
        prefix with ``d_j == g_j`` is accepted and ``g_1..g_{a+1}``
        commit (1..k+1 tokens). Rejection rolls back by *page offset*:
        lengths simply stop at ``n+a+1``; stale K/V beyond that sits past
        every length mask and is rewritten in order before any read
        reaches it (the same scratch-row isolation rules as prefill —
        table entries beyond a lane's chain stay on the scratch page).

        Held / prefilling / idle lanes ride through the batched calls
        exactly as in plain decode: scratch-page writes for unbound
        rows, rewritten-before-read positions for held ones."""
        k = self.spec_k
        n0 = self.lengths.copy()
        table = jnp.asarray(self.page_table)
        sampled = self._any_sampled(active)
        toks = self.last_token.copy()
        pos = self.lengths.copy()
        draft_toks = np.zeros((self.n_slots, k), np.int32)
        for j in range(k + 1):
            batch = {
                "tokens": jnp.asarray(toks)[:, None],
                "positions": jnp.asarray(pos),
                "page_table": table,
            }
            dlogits, self.cache = self._draft_decode(self.draft_params,
                                                     self.cache, batch)
            if j < k:
                nxt = np.array(jnp.argmax(dlogits, axis=-1), np.int32)
                if sampled:
                    # the draft guesses with the lane's own noise: if the
                    # draft models the target well, its sampled guess is
                    # the target's sampled choice
                    drows = np.asarray(dlogits, np.float32)
                    for i in active:
                        req = self.requests[self.slot_req[i]]
                        if req.temperature > 0:
                            nxt[i] = self._choose(drows[i], req, int(pos[i]))
                draft_toks[:, j] = nxt
                toks = nxt
            pos = pos + 1
        window = np.concatenate([self.last_token[:, None], draft_toks],
                                axis=1)  # (n_slots, k+1)
        vbatch = {
            "tokens": jnp.asarray(window),
            "positions": jnp.asarray(n0),
            "page_table": table,
        }
        vlogits, self.cache = self._verify_paged(self.params, self.cache,
                                                 vbatch)
        greedy = np.asarray(jnp.argmax(vlogits, axis=-1), np.int32)
        vrows = np.asarray(vlogits, np.float32) if sampled else None
        for i in active:
            req = self.requests[self.slot_req[i]]
            base = int(n0[i])
            if vrows is not None and req.temperature > 0:
                target = [self._choose(vrows[i, j], req, base + j)
                          for j in range(k + 1)]
            else:
                target = [int(greedy[i, j]) for j in range(k + 1)]
            a = 0
            while a < k and int(draft_toks[i, a]) == target[a]:
                a += 1
            self.stats["spec_rounds"] += 1
            self.stats["spec_proposed"] += k
            self.stats["spec_accepted"] += a
            for tok in target[: a + 1]:
                if self._commit_token(i, req, tok):
                    break

    def fork(self, req_id: int, n: int, *, temperature: float = 1.0,
             seeds: list[int] | None = None) -> list[Request]:
        """Fork ``n`` sampling children off a live decode slot.

        Each child continues the parent's stream from its current
        position: every *full* committed page — prompt AND decode-
        generated — is shared copy-on-write (refcount bump, zero copies),
        the partially filled last page is COW-copied, and only the
        remaining capacity is privately allocated. Children then diverge
        through their own ``(temperature, seed)`` sampling; the physical
        pages up to the fork point stay shared for their whole lifetime
        (they are read-only — every lane writes only at positions past
        its fork length).

        Requires ``n`` free slots and enough free pages; raises
        ``ValueError`` (no side effects) otherwise."""
        assert self.paged, "fork needs the paged cache"
        req = self.requests[req_id]
        slot = req.slot
        if slot is None or slot in self.prefilling:
            raise ValueError("fork needs an active decode slot")
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        if len(free) < n:
            raise ValueError(f"fork of {n} needs {n} free slots, "
                             f"have {len(free)}")
        P = self.page_size
        chain = self.slot_pages[slot]
        length = int(self.lengths[slot])
        full = length // P
        partial = length % P != 0
        need = pages_needed(
            min(self._total_len(req) + req.max_new_tokens, self.max_seq), P
        )
        priv_n = need - full
        if n * priv_n > self.pool.available:
            raise ValueError(
                f"fork of {n} needs {n * priv_n} pages, "
                f"have {self.pool.available}"
            )
        seeds = list(seeds) if seeds is not None else list(range(n))
        if len(seeds) != n:
            raise ValueError(f"need {n} seeds, got {len(seeds)}")
        children: list[Request] = []
        for c, seed in zip(free[:n], seeds):
            child = Request(
                self._req_counter, list(req.prompt), req.max_new_tokens,
                req.eos_id, dict(req.extra), priority=req.priority,
                arrival_step=self.steps, temperature=temperature, seed=seed,
            )
            self._req_counter += 1
            child.generated = list(req.generated)
            self.requests[child.req_id] = child
            self.pool.share(chain[:full])
            priv = self.pool.alloc(priv_n)
            assert priv is not None  # guaranteed by the pre-check
            self._retire_cached(priv)
            if partial:
                self.cache = self._copy_pages(
                    self.cache, jnp.asarray(chain[full], jnp.int32),
                    jnp.asarray(priv[0], jnp.int32),
                )
                self.stats["cow_copies"] += 1
            cchain = chain[:full] + priv
            self.slot_pages[c] = cchain
            self.page_table[c, :] = 0
            self.page_table[c, : len(cchain)] = cchain
            self.lengths[c] = length
            self.last_token[c] = self.last_token[slot]
            self.slot_req[c] = child.req_id
            child.slot = c
            # carry the parent's write-behind coverage: pages it already
            # pre-staged are immutable and shared with the child, so the
            # child's spill group pre-stages them too (own leases — a
            # lease has a single borrower) and a later child preemption
            # ships only the pages past the fork point
            if self.write_behind and self.remote_pool is not None:
                for idx in self.remote_pool.staged_pages(req.req_id):
                    if idx < full and self.remote_pool.stage_page(
                            child.req_id, idx,
                            extract_page_payload(self.cache, cchain[idx])):
                        self.stats["pages_staged"] += 1
            self.stats["forks"] += 1
            self.stats["fork_shared_pages"] += full
            children.append(child)
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self.pool.outstanding)
        return children

    # ----------------------------------------------------------------- admit
    def _admit(self) -> int:
        """Synchronous admission entry point: one shed + admission pass,
        then drain any in-flight prefills to completion regardless of the
        token budget. ``step()`` uses it when the scheduler is
        synchronous; the elastic cell calls it directly before replay so
        a restored engine admits exactly as the snapshotted one did.
        Returns the prefill tokens computed (including drains that ran
        inside the admission scan), so the synchronous mode's
        ``last_step_tokens`` accounts admission stalls like the
        continuous mode does (the latency bench's simulated clock)."""
        self._step_prefill_tokens = 0
        self._shed_pass()
        self._admission_scan()
        if self.paged and self.prefilling:
            self._pump_prefill(None)
        return self._step_prefill_tokens

    def _admission_scan(self) -> None:
        """Admit waiting requests into free slots in the scheduler's
        order (effective priority desc, earliest deadline, FIFO among
        peers). Under page pressure a lower-ranked request whose cached
        prefix shrinks its private-page need may be admitted past a
        blocked higher-ranked one — but only while the blocked request's
        aged effective-priority lead stays below ``bypass_margin``: the
        blocked request ages while bypass candidates keep arriving fresh,
        so bypass shuts off after a bounded wait and freed pages
        accumulate for it. (The old fixed-skip-count rule reset on every
        admission and could starve an oversized head indefinitely under a
        steady prefix-hit stream.)"""
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        if self.active_cap is not None:
            headroom = self.active_cap - sum(
                r is not None for r in self.slot_req)
            free = free[:max(0, headroom)]
        if not self.paged:
            while free and self.queue:
                req = self.sched.order(self.queue, self.steps)[0]
                self.queue.remove(req)
                self._prefill_into(free.pop(0), req)
            return
        while free and self.queue:
            if not self._admit_ready:
                return  # nothing changed since the last failed scan
            ranked = self.sched.order(self.queue, self.steps)
            admitted = False
            deferred = False
            blocked: Request | None = None
            attempts = 0
            for req in ranked:
                if attempts >= self.sched.cfg.scan_limit:
                    break
                if blocked is not None and not self.sched.may_bypass(
                        blocked, req, self.steps):
                    break  # ranked order: later candidates' leads only grow
                attempts += 1
                if self._await_inflight_prefix(req):
                    deferred = True
                    continue
                if self._try_admit(free[0], req,
                                   require_shared=blocked is not None):
                    self.queue.remove(req)
                    free.pop(0)
                    admitted = True
                    break
                if blocked is None:
                    blocked = req
            if not admitted:
                if not deferred:
                    # don't rescan (O(queue) trie lookups) until a
                    # completion frees pages or a new request arrives;
                    # deferred candidates rescan next step — their source
                    # prefill is about to register
                    self._admit_ready = False
                return

    def _await_inflight_prefix(self, req: Request) -> bool:
        """True when a still-prefilling slot will register a longer
        usable prefix for this request than the trie holds right now.
        Pages enter the trie only once their content exists, so admitting
        such a request immediately would forfeit the sharing and prefill
        the duplicate prefix from scratch; deferring it a step (until the
        source task finishes and registers) keeps burst arrivals of a
        shared prefix paying its FLOPs once."""
        if not self.prefix_share or not self.prefilling:
            return False
        keys = self._admit_keys(req)
        best = 0
        for task in self.prefilling.values():
            m = 0
            for a, b in zip(keys, task.key_tokens):
                if a != b:
                    break
                m += 1
            best = max(best, m // self.page_size)
        if not best:
            return False
        return best > len(self.prefix_index.lookup(keys))

    # ------------------------------------------------------- shed / preempt
    def _shed_pass(self) -> None:
        """Degrade instead of queueing unboundedly: drop waiting requests
        whose TTFT deadline already passed, then the lowest-ranked tail
        beyond ``max_queue``. Shed requests are cancelled with their
        ``shed`` flag set, so callers can tell drop from completion."""
        if not self.queue:
            return
        if self._has_deadlines:
            for req in list(self.queue):
                if (req.deadline_ms is not None
                        and self.sched.expired(req, self.steps)):
                    self._shed(req, "shed_expired")
        if self.sched.cfg.max_queue is not None:
            for req in self.sched.overflow(self.queue, self.steps):
                self._shed(req, "shed_overflow")

    def _shed(self, req: Request, counter: str) -> None:
        req.shed = True
        self.cancel(req.req_id)
        self.stats[counter] += 1

    def preempt(self, req_id: int) -> Request:
        """Preempt an active decode slot back to the waiting queue,
        token-exactly — as a **page movement**, not a recompute, when a
        spill tier is attached.

        With a :class:`~repro.serving.kvcache.RemotePagePool`, the slot's
        whole used page chain (prompt + generated tokens, including the
        partially filled last page) is lease-tracked on neighbor hosts as
        a slot-spill group keyed by the request id; pages already
        write-behind staged ship for free. Re-admission recalls the chain
        verbatim and resumes with zero recomputed tokens
        (:meth:`SlotLifecycle.resume_recalled`).

        The re-prefill fallback stays armed either way: ``generated[:-1]``
        becomes the request's ``resume`` suffix (re-prefilled after the
        prompt when the spill failed, the chain exceeds the recall
        budget, or a holder churns away) and the final committed token is
        re-derived from the recomputed logits — greedy decode is
        deterministic, so the stream never changes across a preemption.
        Before the slot is released its pages are registered in the
        prefix trie under the full prompt+generated key sequence: the
        free list's content retention (and any sharers' refcounts) keeps
        them resident until re-admission revives them or pool pressure
        evicts/spills them, so even the fallback usually costs one COW
        recompute, not a full prefill."""
        req = self.requests[req_id]
        slot = req.slot
        assert self.paged, "preemption needs the paged cache"
        assert slot is not None and slot not in self.prefilling, (
            "only active decode slots can be preempted"
        )
        if self.prefix_cache:
            covered = int(self.lengths[slot])
            gen = req.generated[: covered - self._total_len(req)]
            self._register_prefix(
                self._key_tokens(req) + self._gen_keys(req, gen),
                self.slot_pages[slot],
            )
        if self.spill and not self.cross:
            # whole-chain spill: only the pages holding real positions
            # travel (the chain's tail pages past ``lengths`` are
            # garbage); staged indices are skipped — already on a peer
            length = int(self.lengths[slot])
            chain = self.slot_pages[slot]
            staged = self.remote_pool.staged_pages(req.req_id)
            payloads = {
                idx: extract_page_payload(self.cache, chain[idx])
                for idx in range(pages_needed(length, self.page_size))
                if idx not in staged
            }
            if self.remote_pool.spill_slot(req.req_id, payloads):
                req.spill_len = length
                self.stats["preempt_spills"] += 1
        req.resume = list(req.generated[:-1])
        req.key_cache.pop("admit_keys", None)
        # aging restarts from the preemption: a victim that kept its
        # credit would immediately outrank (and bypass back past) the
        # very request that preempted it
        req.arrival_step = self.steps
        self._release_slot(slot)
        req.slot = None
        self.queue.append(req)
        self.stats["preemptions"] += 1
        return req

    def _preempt_pass(self) -> None:
        """After the admission scan: if the best waiting request outranks
        (by *base* priority — aging never preempts, see the scheduler
        docstring) the weakest active decode slot by ``preempt_margin``,
        preempt that slot; the freed lane and pages admit the candidate
        on the next step's scan. One victim per step — pressure relief is
        gradual, not a stampede. Victim choice is spill-cost-aware:
        among equal-priority victims the one whose chain is cheapest to
        move (most pages already write-behind staged) goes first."""
        if self.sched.cfg.preempt_margin is None or not self.queue:
            return
        cand = min(self.queue,
                   key=lambda r: (-r.priority, r.arrival_step, r.req_id))
        active = [
            self.requests[r] for i, r in enumerate(self.slot_req)
            if r is not None and i not in self.prefilling
            and not self.slot_hold[i]
        ]
        victim = self.sched.pick_victim(cand, active,
                                        spill_cost=self._spill_cost)
        if victim is not None:
            self.preempt(victim.req_id)

    def _spill_cost(self, req: Request) -> int:
        """Pages a preemption of ``req`` would still have to transfer:
        its used chain minus the pages already write-behind staged. Zero
        when the spill tier is off — every victim is equally cheap (the
        fallback re-prefill cost is priced by the scheduler's base
        ordering, not here)."""
        if not self.spill or self.cross or req.slot is None:
            return 0
        n_chain = pages_needed(int(self.lengths[req.slot]), self.page_size)
        staged = sum(1 for idx in self.remote_pool.staged_pages(req.req_id)
                     if idx < n_chain)
        return n_chain - staged

    def _try_admit(self, slot: int, req: Request, *,
                   require_shared: bool = False) -> bool:
        """One admission attempt, recall-first: a request whose preempted
        chain is spilled tries to recall it whole (zero recompute);
        everything else — and every fallback — goes through the prefix-
        aware re-prefill plan. Under bypass (``require_shared``) a
        spilled candidate just waits: recalling restores its full page
        need, so it can never shrink past a blocked head."""
        if req.spill_len and not require_shared:
            got = self._try_admit_recall(slot, req)
            if got is not None:
                return got
            # chain lost (holder churn / over budget): the resume
            # fallback re-prefills through the ordinary path below
        elif req.spill_len:
            return False
        return self._try_admit_paged(slot, req, require_shared=require_shared)

    def _try_admit_recall(self, slot: int, req: Request) -> bool | None:
        """Admit a preempted request by recalling its spilled slot chain.

        Returns True when the slot resumed from the recalled pages, False
        (no side effects) when the pool cannot host the chain yet — the
        request keeps waiting with its group intact — or None when the
        chain is unrecoverable (recall miss on a churned holder, or a
        chain longer than ``recall_budget``): the group is dropped, the
        ``resume_fallbacks`` counter bumped, and the caller falls back to
        re-prefill in the same scan."""
        P = self.page_size
        if pages_needed(req.spill_len, P) > self.recall_budget:
            self.remote_pool.release_slot(req.req_id)
            req.spill_len = 0
            self.stats["resume_fallbacks"] += 1
            return None
        need = pages_needed(
            min(self._total_len(req) + req.max_new_tokens, self.max_seq), P
        )
        if need > self.pool.available:
            return False
        payloads, wait_s = self.remote_pool.recall_slot(req.req_id)
        length, req.spill_len = req.spill_len, 0
        if payloads is None:
            self.stats["recall_misses"] += 1
            self.stats["resume_fallbacks"] += 1
            return None
        chain = self.pool.alloc(need)
        assert chain is not None  # guaranteed by the pre-check
        self._retire_cached(chain)
        like = page_payload_like(self.cache, self._region_keys(cross=False))
        for idx, blob in payloads.items():
            vals = deserialize_tree(blob, like)
            self.cache = self._install_page(
                self.cache, jnp.asarray(chain[idx], jnp.int32),
                {k: jnp.asarray(v) for k, v in vals.items()},
            )
        self.stats["pages_recalled"] += len(payloads)
        self.lifecycle.bind(slot, req, chain)
        self.lifecycle.resume_recalled(slot, req, length)
        self.stats["recall_resumes"] += 1
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self.pool.outstanding)
        hold = (int(np.ceil(wait_s / self.decode_step_s))
                if wait_s > 0 else 0)
        if hold:
            self.slot_hold[slot] = hold
            self.stats["recall_hold_steps"] += hold
        return True

    def _try_admit_paged(self, slot: int, req: Request, *,
                         require_shared: bool = False) -> bool:
        """Plan + execute one paged admission: trie lookup, batched recall
        of spilled prefix/encoder pages, refcount bumps on the shared
        pages, private allocation for the rest.

        Enc-dec requests also plan their **encoder-output region** here:
        a full-chain trie hit on the frames' content keys shares the
        cached cross pages (the encoder is skipped entirely); otherwise
        fresh pages are allocated and ``prefill_cross`` fills them. Cross
        stubs recall through the same budget-bounded path as prefix
        stubs.

        Returns False (no *local* side effects) if the pool cannot satisfy
        it, or if ``require_shared`` and no resident cached pages shrink
        the request. The plan loop re-plans after a recall miss (a peer
        churned away mid-recall): the missed stub's subtree is dropped and
        the prefix (or encoder region) recomputed — churn degrades to
        recompute, never to wrong tokens. Payloads already recalled by an
        attempt that then fails are re-lent (or, failing that, evicted),
        so no cached page is silently lost.
        """
        # a preempted request re-prefills its committed tokens after the
        # prompt, so its admission length includes the resume suffix; the
        # page reservation is unchanged (prompt + max_new covers resume +
        # the remaining new tokens exactly)
        tlen = self._total_len(req) + len(req.resume)
        P = self.page_size
        need = pages_needed(
            min(self._total_len(req) + req.max_new_tokens, self.max_seq), P
        )
        key_tokens = self._admit_keys(req)
        cross_keys = self._cross_keys(req) if self.cross else []
        n_cp = len(cross_keys) // P
        payloads: dict[int, bytes] = {}  # stub id -> recalled page bytes
        wait_s = 0.0
        allow_spill = self.spill
        while True:
            matched, shared, recalls, would_be = 0, [], [], 0
            cross_shared: list[int] = []
            cross_recalls: list[int] = []
            budget = self.recall_budget - len(payloads)
            if self.prefix_cache:
                chain = self.prefix_index.lookup(key_tokens)
                # usable prefix: resident pages, plus spilled stubs within
                # the per-request recall budget; truncated at the first
                # stub the budget (or a disabled spill tier) cannot cover
                usable: list[int] = []
                for sid in chain:
                    if sid < self.n_pages:
                        usable.append(sid)
                    elif (allow_spill and sid in self.spilled
                          and (sid in payloads or budget > 0)):
                        usable.append(sid)
                        if sid not in payloads:
                            budget -= 1
                    else:
                        break
                # cap at tlen-1: at least one suffix token must run
                # through the model to produce the first-token logits
                matched = min(len(usable) * P, tlen - 1)
                if not self.prefix_share:
                    # recurrent state is not page-addressable: trie tracks
                    # would-be hits only, prefill is never skipped
                    would_be = min(len(chain) * P, tlen - 1)
                    matched = 0
                elif matched:
                    shared = usable[: pages_needed(matched, P)]
                    recalls = [s for s in shared if s >= self.n_pages]
                # encoder-output region: reusable only on a full-chain hit
                # (the encoder is non-causal — a prefix of its output is
                # not a function of a prefix of its input)
                if self.prefix_share and n_cp:
                    cchain = self.prefix_index.lookup(cross_keys)
                    tentative: list[int] | None = (
                        [] if len(cchain) == n_cp else None
                    )
                    used = 0
                    for sid in cchain if tentative is not None else []:
                        if sid < self.n_pages:
                            tentative.append(sid)
                        elif (allow_spill and sid in self.spilled
                              and (sid in payloads or budget - used > 0)):
                            tentative.append(sid)
                            if sid not in payloads:
                                used += 1
                        else:
                            tentative = None
                            break
                    if tentative is not None:
                        cross_shared = tentative
                        cross_recalls = [s for s in cross_shared
                                         if s >= self.n_pages]
            resident = [s for s in shared if s < self.n_pages]
            cross_resident = [s for s in cross_shared if s < self.n_pages]
            if require_shared and not (resident or cross_resident):
                self._abort_recalls(payloads)
                return False
            # feasibility pre-check so failure has no local side effects:
            # share() will pull revived (refcount-0) pages out of the free
            # list, alloc() needs the private (and freshly computed cross)
            # pages on top of that, and every recalled page needs a fresh
            # local page too
            revive = sum(1 for p in resident + cross_resident
                         if self.pool.refcount(p) == 0)
            cross_new = n_cp if (self.cross and not cross_shared) else 0
            if (need - matched // P) + len(recalls) + revive \
                    + cross_new + len(cross_recalls) > self.pool.available:
                if recalls or cross_recalls:
                    # recalling won't fit: retry using only the resident
                    # pages (the stubs stay spilled for a later hit)
                    allow_spill = False
                    continue
                self._abort_recalls(payloads)
                return False
            missing = [s for s in recalls + cross_recalls
                       if s not in payloads]
            if missing:
                got, w = self.remote_pool.recall(
                    [self.spilled[s].lease_id for s in missing]
                )
                wait_s += w
                missed = False
                for s in missing:
                    if s not in self.spilled:
                        continue  # dropped as a missed ancestor's subtree
                    blob = got.get(self.spilled[s].lease_id)
                    if blob is None:
                        # holder churned away: drop the stub's subtree and
                        # fall back to recomputing those tokens
                        self._evict_node(s)
                        self.stats["recall_misses"] += 1
                        missed = True
                    else:
                        payloads[s] = blob
                if missed:
                    continue  # re-plan against the pruned trie
            break
        # recalled payloads the final plan cannot use (a later re-plan
        # shrank the usable prefix): re-lend them so they stay cached
        unused = {s: payloads.pop(s) for s in list(payloads)
                  if s not in recalls and s not in cross_recalls}
        if unused:
            self._abort_recalls(unused)
        # ---- execute: guaranteed to succeed from here ----
        self.pool.share(resident)        # revive cached pages before alloc
        self.pool.share(cross_resident)
        hold = (int(np.ceil(wait_s / self.decode_step_s))
                if wait_s > 0 else 0)
        all_recalls = recalls + cross_recalls
        if all_recalls:
            local = self.pool.alloc(len(all_recalls))
            assert local is not None  # guaranteed by the pre-check
            self._retire_cached(local)
            for sid, page in zip(all_recalls, local):
                like = page_payload_like(
                    self.cache,
                    self._region_keys(cross=sid in cross_recalls),
                )
                vals = deserialize_tree(payloads.pop(sid), like)
                self.cache = self._install_page(
                    self.cache, jnp.asarray(page, jnp.int32),
                    {k: jnp.asarray(v) for k, v in vals.items()},
                )
                self.prefix_index.remap(sid, page)
                del self.spilled[sid]
                tgt = shared if sid in shared else cross_shared
                tgt[tgt.index(sid)] = page
            self.stats["pages_recalled"] += len(all_recalls)
        private = self.pool.alloc(need - matched // P)
        assert private is not None  # guaranteed by the pre-check
        self._retire_cached(private)
        cross_chain: list[int] | None = None
        cross_computed = False
        if self.cross:
            if cross_shared:
                cross_chain = cross_shared
                self.stats["cross_regions_shared"] += 1
                self.stats["cross_pages_shared"] += len(cross_shared)
            else:
                cross_chain = self.pool.alloc(n_cp)
                assert cross_chain is not None  # covered by the pre-check
                self._retire_cached(cross_chain)
                cross_computed = True
        if would_be:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += would_be
        self._prefill_paged(slot, req, shared, private, matched, key_tokens,
                            cross_keys, cross_chain, cross_computed)
        if hold and self.slot_req[slot] == req.req_id:
            # recall-in-flight: scheduler holds this lane's decode for the
            # simulated transfer time (see step())
            self.slot_hold[slot] = hold
            self.stats["recall_hold_steps"] += hold
        return True

    def _region_keys(self, *, cross: bool) -> frozenset[str] | None:
        """Cache leaves one region's page payload must carry: cross pages
        only the ``cross_*`` pools, prompt pages the rest. None (all
        ``*_pages`` leaves) for families without a cross region."""
        if not self.cross:
            return None
        names = {k for k in self.cache if k.endswith("_pages")}
        cross_names = {k for k in names if k.startswith("cross_")}
        return frozenset(cross_names if cross else names - cross_names)

    def _node_is_cross(self, page: int) -> bool:
        """A trie node belongs to the cross region iff its block keys
        carry the cross namespace (block[0] ≥ ``_CROSS_NS``)."""
        ent = self.prefix_index._nodes.get(page)
        return bool(ent and ent[1] and ent[1][0] >= _CROSS_NS)

    def _retire_cached(self, pages: list[int]) -> None:
        """Freshly reallocated pages lose their cached contents: **spill**
        still-cached ones to a peer host (the pool's LRU alloc order makes
        these the coldest retained prefixes) or, when no peer can take
        them, evict them from the trie."""
        if not self.prefix_cache:
            return
        for p in pages:
            if p not in self.prefix_index._nodes:
                continue
            if self.spill:
                lease = self.remote_pool.lend(
                    extract_page_payload(
                        self.cache, p,
                        self._region_keys(cross=self._node_is_cross(p)),
                    )
                )
                if lease is not None:
                    sid = self._spill_next
                    self._spill_next += 1
                    self.prefix_index.remap(p, sid)
                    self.spilled[sid] = SpilledPage(lease.lease_id,
                                                    lease.holder)
                    self.stats["pages_spilled"] += 1
                    continue
            self._evict_node(p)

    def _evict_node(self, node: int) -> None:
        """Drop a trie node (content lost) plus its subtree, releasing the
        leases of any spilled descendants — their pages become
        unreachable, so holding peer capacity for them would leak."""
        dropped = self.prefix_index.evict_pages([node])
        for d in dropped:
            sp = self.spilled.pop(d, None)
            if sp is not None and self.remote_pool is not None:
                self.remote_pool.release(sp.lease_id)
        self.stats["prefix_evictions"] += len(dropped)

    def _abort_recalls(self, payloads: dict[int, bytes]) -> None:
        """An admission attempt consumed recalls it cannot use: re-lend
        the payloads so the cached pages stay recallable (their leases
        were released by the recall); evict the ones no peer will take."""
        for sid, blob in list(payloads.items()):
            if sid not in self.prefix_index._nodes:
                continue  # stub already evicted (missed ancestor): discard
            lease = self.remote_pool.lend(blob) if self.remote_pool else None
            if lease is None:
                self._evict_node(sid)
            else:
                self.spilled[sid] = SpilledPage(lease.lease_id, lease.holder)
        payloads.clear()

    def _release_slot(self, slot: int) -> None:
        self.slot_req[slot] = None
        self.lengths[slot] = 0
        if self.paged:
            self.pool.free(self.slot_pages[slot])
            self.slot_pages[slot] = []
            self.page_table[slot, :] = 0  # scratch page: inert lane writes
            if self.cross:
                # drop this slot's reference on the encoder region; the
                # pages keep their contents in the free list, so a later
                # request with the same frames revives them trie-first
                self.pool.free(self.slot_cross_pages[slot])
                self.slot_cross_pages[slot] = []
                self.cross_table[slot, :] = 0
                self.cross_len[slot] = 0
            self.slot_hold[slot] = 0
            self.prefilling.pop(slot, None)
            self._admit_ready = True      # freed capacity: rescan the queue

    def _finish_admit(self, slot: int, req: Request, first: int,
                      length: int) -> None:
        # fresh admissions commit their first token; a request with
        # committed tokens is resuming from a preemption via re-prefill
        # and the recomputed argmax is verified against (never replaces)
        # the committed stream — see SlotLifecycle.activate
        self.lifecycle.activate(slot, req, first, length)

    def _prefill_paged(self, slot: int, req: Request, shared: list[int],
                       private: list[int], matched: int,
                       key_tokens: list[int] | None = None,
                       cross_keys: list[int] | None = None,
                       cross_chain: list[int] | None = None,
                       cross_computed: bool = False) -> None:
        """Chunked prefill of the uncached suffix at true prompt length:
        each chunk's K/V (or recurrent state) is written straight into the
        slot's private pages, while attention reads the shared prefix
        pages through the page table.

        VLM prompts span ``mm_len`` image positions followed by the text
        tokens: the chunk loop slices the request's image embeddings into
        each chunk (``embeds`` + static ``mm_len``), so image rows land in
        ordinary pages and the whole image+text prefix is shareable.
        Enc-dec requests first install their encoder-output region
        (``cross_chain``), running ``prefill_cross`` only when the region
        was not served from cache.

        ``shared`` holds the trie-matched prefix pages (refcounts already
        bumped); ``matched`` is the token count they cover, page-aligned
        except for a whole-prompt hit (capped at ``tlen - 1``), where the
        final, partially-used shared page is **copied on write**: the slot
        gets a fresh page with the copied tail and recomputes only the
        last prompt token into it for the first-token logits.

        Suffix offsets are page multiples, so ``prefill_chunk`` compiles
        at most ``max_pages`` offset variants (warmable, like the dense
        engine's buckets); the whole-prompt COW recompute reuses the
        already-compiled ``decode_paged`` instead of adding a
        per-prompt-length prefill variant.

        Under a continuous-batching scheduler this method only *begins*
        the prefill: pages and the slot are bound, a ``_PrefillTask`` is
        queued, and ``step()`` pumps the chunks across iterations under
        the token budget (the synchronous mode drains the task inline).
        A preempted request's ``resume`` tokens prefill here exactly like
        prompt tokens — they extend ``ptoks`` past the prompt."""
        ptoks = req.prompt + req.resume
        plen = len(ptoks)
        mm = self._mm_len(req)
        tlen = mm + plen
        assert plen >= 1 and tlen < self.max_seq, (plen, tlen)
        if key_tokens is None:
            key_tokens = self._admit_keys(req)
        P = self.page_size
        full = matched // P
        cow = bool(matched % P)
        if cow:
            # COW: private[0] replaces the partially-used shared page
            src, dst = shared[full], private[0]
            self.cache = self._copy_pages(
                self.cache, jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32),
            )
            self.pool.free([src])  # drop this slot's read ref on the original
            self.stats["cow_copies"] += 1
        chain = shared[:full] + private
        self.slot_pages[slot] = chain
        # the page-table row stays on the scratch page until the last
        # chunk lands (see _PrefillTask); chunks write through a private
        # row, and the COW whole-prompt path installs the row right below
        # because it finishes within this call
        self.page_table[slot, :] = 0
        if self.cross:
            # install the encoder-output region before any decoder compute
            # (chunk prefill and the COW recompute both read it)
            self.slot_cross_pages[slot] = list(cross_chain)
            self.cross_table[slot, :] = 0
            self.cross_table[slot, : len(cross_chain)] = cross_chain
            self.cross_len[slot] = self._n_frames(req)
            if cross_computed:
                frames = np.asarray(req.extra["frames"])
                if frames.ndim == 2:
                    frames = frames[None]
                self.cache = self._prefill_cross(self.params, self.cache, {
                    "frames": jnp.asarray(frames),
                    "cross_page_table": jnp.asarray(self.cross_table[slot]),
                })
                self.stats["cross_regions_computed"] += 1
                if self.prefix_share:
                    self.prefix_index.insert(cross_keys, cross_chain)
        # bind the slot for the whole (possibly multi-step) prefill:
        # cancel/preempt/force-map consumers see the request as admitted
        self.slot_req[slot] = req.req_id
        req.slot = slot
        self.stats["prefill_tokens"] += tlen - matched
        self.stats["prefill_tokens_shared"] += matched
        if matched:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += matched
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self.pool.outstanding)
        if self.prefix_cache and not self.prefix_share:
            # bookkeeping-only trie (recurrent state): phantom ids carry
            # no page content, so they register at begin — sharing
            # families must wait for the content (_finish_prefill)
            self._register_prefix(key_tokens, chain)
        if cow:
            # whole-prompt hit: only token tlen-1 needs recomputing, so
            # the prefill finishes within this call. Install the row now;
            # one synthetic decode_paged step writes the final token's K/V
            # into the COW'd private page and returns its logits. Other
            # lanes re-write the K/V the next real step writes anyway
            # (same token, same position — idempotent), and their logits
            # are discarded; inactive lanes scatter into the scratch page.
            self.page_table[slot, : len(chain)] = chain
            toks = self.last_token.copy()
            toks[slot] = ptoks[-1]
            pos = self.lengths.copy()
            pos[slot] = tlen - 1
            batch = {
                "tokens": jnp.asarray(toks)[:, None],
                "positions": jnp.asarray(pos),
                "page_table": jnp.asarray(self.page_table),
            }
            if self.cross:
                batch["cross_page_table"] = jnp.asarray(self.cross_table)
                batch["cross_len"] = jnp.asarray(self.cross_len)
            logits, self.cache = self._decode_paged(self.params, self.cache,
                                                    batch)
            if self._draft is not None:
                # the recomputed final prompt token needs its draft K/V too
                _, self.cache = self._draft_decode(self.draft_params,
                                                   self.cache, batch)
            first = int(np.asarray(jnp.argmax(logits[slot])))
            self._finish_prefill(slot, req, key_tokens, chain, first, tlen)
            return
        embeds = (
            np.asarray(req.extra["embeds"]).reshape(mm, -1) if mm else None
        )
        self.prefilling[slot] = _PrefillTask(
            req=req, tlen=tlen, mm=mm, ptoks=ptoks, offset=matched,
            key_tokens=key_tokens, embeds=embeds,
        )
        if self.sched.cfg.synchronous:
            self._advance_prefill(slot, None)

    def _advance_prefill(self, slot: int, budget: int | None,
                         force: bool = False) -> int:
        """Run prefill chunks for one in-flight task. ``budget`` bounds
        the tokens computed (None = drain to completion); ``force``
        grants the first chunk even over budget so a saturated step still
        makes progress (no prefill livelock when decode lanes consume the
        whole token budget). Returns the prefill tokens computed."""
        task = self.prefilling[slot]
        C = self.prefill_chunk
        mm = task.mm
        chain = self.slot_pages[slot]
        row = np.zeros((self.max_pages,), np.int32)
        row[: len(chain)] = chain
        table_row = jnp.asarray(row)
        used = 0
        while task.offset < task.tlen:
            n = min(C, task.tlen - task.offset)
            if (budget is not None and n > budget - used
                    and not (force and used == 0)):
                break
            off = task.offset
            si = min(max(mm - off, 0), n)  # image rows in this chunk
            toks = np.zeros((1, C), np.int32)
            if si < n:
                toks[0, si:n] = task.ptoks[off + si - mm: off + n - mm]
            batch = {
                "tokens": jnp.asarray(toks),
                "valid": jnp.asarray(n, jnp.int32),
                "slot": jnp.asarray(slot, jnp.int32),
                "page_table": table_row,
            }
            kw: dict[str, int] = {"offset": off}
            if self._mm:
                emb = np.zeros((1, C, task.embeds.shape[1]),
                               task.embeds.dtype)
                if si:
                    emb[0, :si] = task.embeds[off:off + si]
                batch["embeds"] = jnp.asarray(emb)
                kw["mm_len"] = mm
            if self.cross:
                batch["cross_page_table"] = jnp.asarray(
                    self.cross_table[slot]
                )
                batch["cross_len"] = jnp.asarray(self.cross_len[slot],
                                                 jnp.int32)
            task.logits, self.cache = self._prefill_chunk(
                self.params, self.cache, batch, **kw
            )
            if self._draft is not None:
                # the draft rides every prefill chunk: its K/V for the
                # prompt lands in the same pages, so shared/COW'd prefixes
                # arrive draft-complete (batch is identical — draft
                # families are text-only, no mm/cross extras)
                self.cache = self._draft_prefill(self.draft_params,
                                                 self.cache, batch,
                                                 offset=off)
            task.offset += n
            used += n
        if task.offset >= task.tlen:
            first = int(np.asarray(jnp.argmax(task.logits, axis=-1))[0])
            del self.prefilling[slot]
            self._finish_prefill(slot, task.req, task.key_tokens,
                                 chain, first, task.tlen)
        self._step_prefill_tokens += used
        return used

    def _pump_prefill(self, budget: int | None) -> int:
        """Advance every in-flight prefill under the step's remaining
        token budget (slot order; only the first slot may overshoot by
        one chunk — the progress guarantee). Returns tokens computed."""
        used = 0
        for slot in sorted(self.prefilling):
            rem = None if budget is None else budget - used
            if rem is not None and rem <= 0 and used > 0:
                break
            used += self._advance_prefill(slot, rem, force=(used == 0))
        return used

    def _finish_prefill(self, slot: int, req: Request, key_tokens: list[int],
                        chain: list[int], first: int, tlen: int) -> None:
        """The last chunk landed: install the real page-table row,
        register the prompt pages in the trie (only now — their content
        exists, so a concurrent admission can never share half-written
        pages), and commit the first token."""
        self.lifecycle.bind(slot, req, chain)
        if self.prefix_share:
            self._register_prefix(key_tokens, chain)
        # locally resident content = live pages + free-but-cached prefix
        # pages (what the spill tier moves to neighbor hosts)
        retained = sum(
            1 for p in self.prefix_index._nodes
            if p < self.n_pages and self.pool.refcount(p) == 0
        )
        self.stats["peak_resident_pages"] = max(
            self.stats["peak_resident_pages"],
            self.pool.outstanding + retained,
        )
        self._finish_admit(slot, req, first, tlen)

    def _register_prefix(self, tokens: list[int], chain: list[int]) -> None:
        """Index the full prompt pages of a freshly admitted request so
        later prompts can share them (or, for recurrent-state families,
        so the trie can count would-be hits via phantom ids). ``tokens``
        is the request's trie *key* sequence — the prompt, with modality
        pseudo-tokens prepended (vlm) or a frames salt mixed in
        (enc-dec); one key per cache position."""
        n = len(tokens) // self.page_size
        if n == 0:
            return
        if self.prefix_share:
            self.prefix_index.insert(tokens, chain[:n])
            return
        # bookkeeping-only trie: bound its growth, it holds no pages
        if len(self.prefix_index) > 8 * self.n_pages:
            return
        phantoms = list(range(self._phantom_next, self._phantom_next + n))
        self._phantom_next += n
        self.prefix_index.insert(tokens, phantoms)

    def _prefill_into(self, slot: int, req: Request) -> None:
        plen = len(req.prompt)
        mm = self._mm_len(req)
        assert plen >= 1 and mm + plen < self.max_seq, (plen, mm)
        # vlm: image rows occupy cache positions ahead of the text bucket,
        # so the admitted length (= the decode position) includes them
        bucket = min(_bucket(plen), self.max_seq - mm)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = req.prompt
        # right-align so position arithmetic matches an unpadded prompt
        toks = np.roll(toks, bucket - plen, axis=1)
        batch = {"tokens": jnp.asarray(toks)}
        for k, v in req.extra.items():
            batch[k] = jnp.asarray(v)
        logits, pcache = self._prefill(self.params, batch)
        # left-padding means cache rows [0, bucket-plen) belong to pad
        # tokens; with causal attention + right-aligned queries they are
        # attended but carry pad-token keys — acceptable for bucketed
        # serving (standard practice); exact tests use bucket == plen.
        pcache = expand_prefill_cache(
            pcache, jax.tree.map(lambda c: c[:, :1], self.cache)
        )
        self.cache = self._scatter(self.cache, pcache, jnp.asarray(slot))
        # logits may be (B, V) (logits_last) or (B, S, V); the sampled token
        # comes from the *last* position — position 0 is a pad row under
        # right-aligned bucketing
        row = logits[0, -1] if logits.ndim == 3 else logits[0]
        first = int(np.asarray(jnp.argmax(row)))
        self._finish_admit(slot, req, first, mm + bucket)

    # -------------------------------------------------------------- snapshot
    def snapshot(self) -> bytes:
        if self.paged and self.prefilling:
            # in-flight chunked prefills hold device-side logits that the
            # blob cannot carry; drain them so the snapshot captures a
            # clean admission boundary (tokens are unaffected)
            self._pump_prefill(None)
        state = {
            "cache": self.cache,
            "lengths": self.lengths,
            "last_token": self.last_token,
            "steps": np.asarray(self.steps, np.int64),
        }
        if self.paged:
            state["page_table"] = self.page_table
            if self.cross:
                state["cross_table"] = self.cross_table
                state["cross_len"] = self.cross_len
        blob = serialize_tree(state)
        meta = {
            "paged": self.paged,
            "slot_req": self.slot_req,
            "queue": [r.req_id for r in self.queue],
            "requests": {
                str(r.req_id): {
                    "prompt": r.prompt,
                    "max_new_tokens": r.max_new_tokens,
                    "eos_id": r.eos_id,
                    "generated": r.generated,
                    "slot": r.slot,
                    "done": r.done,
                    "extra": _encode_extra(r.extra),
                    "priority": r.priority,
                    "deadline_ms": r.deadline_ms,
                    "arrival_step": r.arrival_step,
                    "resume": r.resume,
                    "spill_len": r.spill_len,
                    "temperature": r.temperature,
                    "seed": r.seed,
                }
                for r in self.requests.values()
            },
        }
        if self.paged:
            pool_free, pool_ref, pool_touch = self.pool.serialize()
            meta["page_size"] = self.page_size
            meta["n_pages"] = self.n_pages
            meta["free_pages"] = pool_free
            meta["slot_pages"] = [
                [int(p) for p in ps] for ps in self.slot_pages
            ]
            if self.cross:
                meta["slot_cross_pages"] = [
                    [int(p) for p in ps] for ps in self.slot_cross_pages
                ]
            # prefix sharing: refcounts + the trie must survive a restore
            # on a substitute host, or shared pages would double-free
            meta["page_ref"] = {str(p): r for p, r in pool_ref.items()}
            meta["page_touch"] = {str(p): g for p, g in pool_touch.items()}
            meta["prefix_trie"] = (
                self.prefix_index.serialize() if self.prefix_cache else []
            )
            # spill tier: only the stubs + lease ids travel in the blob —
            # the lent payloads stay on their peers, and a restore
            # revalidates each lease against live cloudlet membership
            meta["spilled"] = {
                str(sid): [sp.lease_id, sp.peer]
                for sid, sp in self.spilled.items()
            }
            # slot-spill groups (preempted chains + write-behind staged
            # pages of live slots): like prefix stubs, only lease ids +
            # peers travel; a restore re-adopts each group after
            # revalidating every lease against live membership
            if self.remote_pool is not None:
                meta["slot_spills"] = {
                    str(r.req_id): {
                        str(i): [lid, peer]
                        for i, (lid, peer)
                        in self.remote_pool.slot_leases(r.req_id).items()
                    }
                    for r in self.requests.values()
                    if self.remote_pool.slot_leases(r.req_id)
                }
            meta["slot_hold"] = [int(h) for h in self.slot_hold]
        meta["stats"] = {k: int(v) for k, v in self.stats.items()}
        mb = json.dumps(meta).encode()
        return len(mb).to_bytes(4, "little") + mb + blob

    def restore(self, blob: bytes) -> None:
        mlen = int.from_bytes(blob[:4], "little")
        meta = json.loads(blob[4 : 4 + mlen].decode())
        assert meta.get("paged", False) == self.paged, (
            "snapshot/engine paged-mode mismatch"
        )
        like = {
            "cache": self.cache,
            "lengths": self.lengths,
            "last_token": self.last_token,
            "steps": np.asarray(self.steps, np.int64),
        }
        if self.paged:
            assert meta["page_size"] == self.page_size
            assert meta["n_pages"] == self.n_pages
            like["page_table"] = self.page_table
            if self.cross:
                like["cross_table"] = self.cross_table
                like["cross_len"] = self.cross_len
        state = deserialize_tree(blob[4 + mlen :], like)
        self.cache = jax.tree.map(jnp.asarray, state["cache"])
        self.lengths = np.asarray(state["lengths"]).copy()
        self.last_token = np.asarray(state["last_token"]).copy()
        self.steps = int(state["steps"])
        if self.paged:
            self.page_table = np.asarray(state["page_table"]).copy()
            if self.cross:
                self.cross_table = np.asarray(state["cross_table"]).copy()
                self.cross_len = np.asarray(state["cross_len"]).copy()
                self.slot_cross_pages = [
                    [int(p) for p in ps]
                    for ps in meta.get("slot_cross_pages",
                                       [[] for _ in range(self.n_slots)])
                ]
            # page_ref absent => legacy snapshot: every non-free page is
            # exclusively owned (refcount 1), which restore() infers
            self.pool.restore(meta["free_pages"], meta.get("page_ref"),
                              meta.get("page_touch"))
            self.slot_pages = [
                [int(p) for p in ps] for ps in meta["slot_pages"]
            ]
            snap_spilled = {
                int(sid): SpilledPage(int(ent[0]), ent[1])
                for sid, ent in meta.get("spilled", {}).items()
            }
            self.slot_hold = np.asarray(
                meta.get("slot_hold", [0] * self.n_slots), np.int32
            ).copy()
            if self.prefix_cache:
                self.prefix_index = PrefixIndex.load(
                    self.page_size, meta.get("prefix_trie", []),
                    # sharing engines install trie ids into page tables,
                    # so they must be real pool pages or known spill
                    # stubs; bookkeeping-only engines hold phantom ids
                    # >= n_pages
                    max_page=self.n_pages if self.prefix_share else None,
                    extra_ids=set(snap_spilled),
                )
                phantoms = [p for p in self.prefix_index._nodes
                            if p >= self.n_pages]
                self._phantom_next = max(phantoms, default=self.n_pages - 1) + 1
                self._spill_next = max(
                    snap_spilled, default=self.n_pages - 1
                ) + 1
                self._spill_next = max(self._spill_next, self.n_pages)
                # revalidate leases: stubs whose lease was revoked while
                # the snapshot sat idle (holder churned) — or that this
                # engine cannot recall (no remote pool) — fall back to
                # recompute; never to stale pages. All stubs are loaded
                # *before* any eviction so that dropping an invalid
                # ancestor releases the still-valid leases of its spilled
                # descendants (via _evict_node) instead of leaking them.
                self.spilled = {
                    sid: sp for sid, sp in snap_spilled.items()
                    if sid in self.prefix_index._nodes
                }
                if self.remote_pool is not None:
                    for sid, sp in snap_spilled.items():
                        if sid not in self.spilled:  # orphaned stub entry
                            self.remote_pool.release(sp.lease_id)
                for sid in list(self.spilled):
                    sp = self.spilled.get(sid)
                    if sp is None:
                        continue  # dropped with an evicted ancestor
                    if (self.remote_pool is None
                            or not self.remote_pool.lease_valid(sp.lease_id)):
                        if self.remote_pool is not None:
                            self.remote_pool.release(sp.lease_id)
                        self._evict_node(sid)
            self.prefilling = {}      # snapshots drain in-flight prefills
            self._admit_ready = True  # restored queue must be rescanned
        self.stats = {**self.stats,
                      **{k: int(v) for k, v in meta.get("stats", {}).items()}}
        self.requests = {}
        for rid, kv in meta["requests"].items():
            req = Request(
                int(rid), kv["prompt"], kv["max_new_tokens"], kv["eos_id"],
                _decode_extra(kv.get("extra", {})),
            )
            req.generated = kv["generated"]
            req.slot = kv["slot"]
            req.done = kv["done"]
            req.priority = int(kv.get("priority", 0))
            req.deadline_ms = kv.get("deadline_ms")
            req.arrival_step = int(kv.get("arrival_step", 0))
            req.resume = list(kv.get("resume", []))
            req.spill_len = int(kv.get("spill_len", 0))
            req.temperature = float(kv.get("temperature", 0.0))
            req.seed = int(kv.get("seed", 0))
            if req.deadline_ms is not None:
                self._has_deadlines = True
            self.requests[req.req_id] = req
        self.slot_req = meta["slot_req"]
        self.queue = [self.requests[rid] for rid in meta["queue"]]
        self._req_counter = (
            max(self.requests) + 1 if self.requests else 0
        )
        if self.paged:
            # re-adopt slot-spill groups: every lease must still be valid
            # (holder alive, payload stored) or the whole chain falls back
            # to re-prefill — churn-safe, never a stale partial recall
            for rid_s, leases in meta.get("slot_spills", {}).items():
                rid = int(rid_s)
                mapping = {int(i): int(ent[0]) for i, ent in leases.items()}
                req = self.requests.get(rid)
                ok = (self.remote_pool is not None
                      and self.remote_pool.adopt_slot(rid, mapping))
                if req is None:
                    if ok:  # finished/cancelled while the snapshot sat
                        self.remote_pool.release_slot(rid)
                    continue
                if not ok and req.spill_len:
                    req.spill_len = 0
                    self.stats["resume_fallbacks"] += 1
            if self.remote_pool is None:
                for req in self.requests.values():
                    req.spill_len = 0
