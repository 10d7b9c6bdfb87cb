"""Paged KV cache: fixed-size pages, free-list allocator, block tables.

The dense serving cache is one (B, max_len, ...) buffer per layer: every
slot pays max_len whether it holds an 8-token or an 8k-token request, so
one long request pins the memory of the whole batch.  The paged layout
(vLLM-style) breaks each layer's cache into a shared pool of fixed-size
**pages**:

    k_pages / v_pages : (Hkv, num_pages, page_size, D)    (GQA)
    kv_pages          : (1,   num_pages, page_size, r+dr) (MLA latent)

A sequence owns an ordered **block table** of pool-page indices; logical
position ``t`` lives at ``(block_table[t // page_size], t % page_size)``.
Memory is allocated page-at-a-time from a host-side free list, so a
retiring request's pages are immediately reusable by the next admission
— what makes continuous batching (serve/engine.py) possible.

MLA stores keys and values out of ONE pool: a pool row is
``[c_kv | k_rope]`` (width r+dr); the paged kernel's ``dv=r`` reads the
value ``c_kv`` as the row's leading columns — no sliced copy.

Layer pools are kept as a python **list** (not stacked on a layer axis):
the paged decode path is an unrolled per-layer loop, and a list lets
each step update one layer's pool in place (donated buffers) without
restacking — restacking would copy every pool every token.

The allocator itself is plain python: page churn is request-rate work
(admission / retirement), not token-rate work, so it stays host-side
while the pools, block tables and lengths live on device inside the
jitted decode step.

**int8 pools** (``kv_dtype="int8"``): pages store int8 rows plus ONE
f32 scale per (kv-head, page) — GQA adds ``k_scales``/``v_scales``
``(Hkv, num_pages)``, MLA's shared pool keeps a single ``kv_scales``
``(1, num_pages)`` row.  Quantization happens at write time
(:func:`write_prompt_pages` per page, :func:`quant_page_update` per
decode token) with the shared ``optim.quant`` convention; the paged
decode kernel dequantizes right after the page DMA (the scales ride
the scalar-prefetch channel next to the block table), so the f32
working set never exists in HBM.  At ~4x fewer bytes per page, the
same pool byte budget (:func:`pool_pages_for_bytes`) admits ~4x the
concurrent sequences.
"""

from __future__ import annotations

from collections import Counter

import jax.numpy as jnp
import numpy as np

from repro.optim.quant import quant_with_scale, scale_for, scale_from_amax

#: serving pool dtypes: per-page-per-head f32 scales appear iff int8
KV_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


class PoolAuditError(RuntimeError):
    """The page pool's bookkeeping is inconsistent (leak, double
    ownership, free/live overlap, ...) — serving on it would hand one
    sequence's KV to another or strand capacity forever."""


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` cache rows."""
    return -(-n_tokens // page_size)


class PageAllocator:
    """Free-list page allocator with refcounted sharing.

    Pages are recycled LIFO so a retire-then-admit reuses hot pages.
    ``alloc`` is all-or-nothing (raises before handing out a partial
    set) and hands pages out at refcount 1.  Sharing is explicit:
    ``ref`` pins a live page for another reader (the prefix cache, a
    second sequence sharing a prompt prefix), ``release`` drops one
    reference and recycles the page only when the LAST reader lets go.
    ``free`` is the strict single-owner API: it rejects double-frees,
    foreign pages AND pages other readers still hold — a shared page
    must be ``release``d, never hard-freed out from under its readers.
    """

    def __init__(self, num_pages: int):
        if num_pages <= 0:
            raise ValueError(f"num_pages must be positive, got {num_pages}")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, -1, -1))
        self._refs: dict[int, int] = {}
        self._quarantined: set[int] = set()

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        return len(self._refs)

    @property
    def num_shared(self) -> int:
        """Pages currently held by more than one reader."""
        return sum(1 for r in self._refs.values() if r >= 2)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> list[int]:
        if n < 0:
            raise ValueError(f"cannot alloc {n} pages")
        if n > len(self._free):
            raise MemoryError(
                f"requested {n} pages, {len(self._free)} free "
                f"of {self.num_pages}")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def ref(self, pages) -> None:
        """Pin live pages for an additional reader (refcount++)."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"cannot ref page {p}: not allocated")
        for p in pages:
            self._refs[p] += 1

    def release(self, pages) -> None:
        """Drop one reference per page; recycle at refcount zero."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"page {p} is not allocated (double free?)")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)

    def free(self, pages) -> None:
        """Single-owner free: rejects pages with live co-readers."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"page {p} is not allocated (double free?)")
            if self._refs[p] > 1:
                raise ValueError(
                    f"page {p} has {self._refs[p] - 1} live reader(s) — "
                    "release() shared pages instead of free()")
        self.release(pages)

    # -- fault containment --------------------------------------------------

    @property
    def num_quarantined(self) -> int:
        return len(self._quarantined)

    def quarantine(self, pages) -> int:
        """Remove pages from circulation entirely: a poisoned page (NaN
        rows, a lost board's HBM slice) must never be handed to a future
        admission.  Accepts free OR live pages — a live page loses ALL
        its references, so callers must tear down (or have already torn
        down) every owner first; the serving supervisor drops radix
        nodes and victim slots before quarantining.  Idempotent per
        page.  Returns the number newly quarantined."""
        n = 0
        for p in pages:
            p = int(p)
            if not 0 <= p < self.num_pages:
                raise ValueError(f"page {p} out of range "
                                 f"[0, {self.num_pages})")
            if p in self._quarantined:
                continue
            if p in self._refs:
                del self._refs[p]
            else:
                self._free.remove(p)
            self._quarantined.add(p)
            n += 1
        return n

    def audit(self, owners: dict | None = None) -> dict:
        """Cross-check the pool's bookkeeping; raise
        :class:`PoolAuditError` listing every violation, else return a
        summary ``{"free", "live", "shared", "quarantined"}``.

        Internal invariants (always checked): the free list holds no
        duplicates, no page is simultaneously free and live (the
        double-ownership a ``pool_corrupt`` fault injects: the next
        alloc would hand a live slot's page to a new sequence), no page
        is quarantined AND circulating, every page is accounted for
        (free + live + quarantined == num_pages — a vanished page is a
        leak), and every live refcount is positive.

        ``owners`` optionally cross-checks CLAIMED ownership: a mapping
        of claimant name -> list of pages it believes it holds one
        reference on (engine slots, the radix tree).  Every live page's
        refcount must equal its total claim count — an excess claim is
        double ownership (two owners will both write the page), a
        missing claim is a leak (a reference nobody will ever release).
        """
        problems = []
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            dupes = sorted(p for p, c in Counter(self._free).items()
                           if c > 1)
            problems.append(f"free list holds duplicates: {dupes}")
        overlap = sorted(free_set & self._refs.keys())
        if overlap:
            problems.append(f"pages both free and live: {overlap}")
        qlap = sorted(self._quarantined
                      & (free_set | self._refs.keys()))
        if qlap:
            problems.append(f"quarantined pages still circulating: {qlap}")
        known = free_set | self._refs.keys() | self._quarantined
        missing = sorted(set(range(self.num_pages)) - known)
        if missing:
            problems.append(f"pages vanished (leaked): {missing}")
        alien = sorted(p for p in known
                       if not 0 <= p < self.num_pages)
        if alien:
            problems.append(f"out-of-range pages tracked: {alien}")
        badref = sorted(p for p, r in self._refs.items() if r <= 0)
        if badref:
            problems.append(f"non-positive refcounts: {badref}")
        if owners is not None:
            claims: Counter = Counter()
            holders: dict[int, list] = {}
            for name, pages in owners.items():
                for p in pages:
                    claims[int(p)] += 1
                    holders.setdefault(int(p), []).append(name)
            for p, c in sorted(claims.items()):
                r = self._refs.get(p, 0)
                if c > r:
                    problems.append(
                        f"page {p}: {c} claims > refcount {r} "
                        f"(double ownership by {holders[p]})")
            for p, r in sorted(self._refs.items()):
                c = claims.get(p, 0)
                if c < r:
                    problems.append(
                        f"page {p}: refcount {r} > {c} claim(s) "
                        f"(leaked reference)")
        if problems:
            raise PoolAuditError("; ".join(problems))
        return {"free": len(self._free), "live": len(self._refs),
                "shared": self.num_shared,
                "quarantined": len(self._quarantined)}


# ---------------------------------------------------------------------------
# radix prefix cache
# ---------------------------------------------------------------------------


def _common_prefix(a, b) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


class _RadixNode:
    __slots__ = ("chunk", "page", "children", "last_used")

    def __init__(self, chunk=(), page=-1):
        self.chunk = chunk  # the <= page_size tokens this page holds
        self.page = page    # pool page id (tree holds ONE allocator ref)
        self.children = {}  # chunk tuple -> _RadixNode
        self.last_used = 0


class RadixPrefixCache:
    """Radix tree over PAGE-GRANULAR token chunks → pool pages.

    Classic radix trees split edges at arbitrary token offsets; here a
    node IS one pool page, so edges can only be ≤ ``page_size`` tokens
    and never split — the tree mirrors the physical page layout exactly
    and a lookup's answer is directly a block-table prefix.  The tree
    holds one allocator reference per adopted page; ``lookup`` pins a
    second reference per returned page for the caller (the admitting
    slot), so a hot prefix stays resident however many sequences read
    it and however often eviction runs.

    Partial-overlap matches are allowed (a node whose chunk shares only
    its first ``o`` tokens with the query still contributes ``o``
    tokens + its page): rows past the match are masked by the reader's
    cache ``len`` and a reader never writes a shared page (the engine
    COW-forks partially-filled tails), so stale tail rows are exactly
    as harmless as a recycled page's garbage.  Lookup semantics are
    therefore the max common prefix over all inserted sequences — the
    brute-force oracle the tests check against.

    ``full_pages_only`` (int8 pools) stops insertion at the last FULL
    page: a partially-filled int8 page requantizes on every decode
    write by its owner, which would silently re-round rows a sharing
    reader already attends — full pages are immutable, so only they
    may be shared.
    """

    def __init__(self, allocator: PageAllocator, page_size: int, *,
                 full_pages_only: bool = False):
        self.allocator = allocator
        self.page_size = page_size
        self.full_pages_only = full_pages_only
        self.root = _RadixNode()
        self.hit_tokens = 0   # cumulative prefill tokens served from cache
        self.lookups = 0
        self.hits = 0
        self.evicted_pages = 0
        self.inserted_pages = 0  # pages the tree newly adopted
        self._tick = 0        # monotonic LRU clock

    # -- introspection ------------------------------------------------------

    def _walk(self):
        stack = [self.root]
        while stack:
            node = stack.pop()
            for c in node.children.values():
                yield node, c
                stack.append(c)

    @property
    def num_nodes(self) -> int:
        return sum(1 for _ in self._walk())

    @property
    def num_pages(self) -> int:
        """Pages the tree currently holds a reference on."""
        return self.num_nodes

    def pages(self) -> list[int]:
        """Every page the tree holds a reference on (one per node) —
        the tree's ownership claim for :meth:`PageAllocator.audit`."""
        return [c.page for _, c in self._walk()]

    # -- lookup -------------------------------------------------------------

    def lookup(self, tokens):
        """Longest cached prefix of ``tokens``.

        Returns ``(match_len, pages)`` where ``pages`` maps positions
        ``[0, match_len)`` page-by-page.  Every returned page is PINNED
        (allocator refcount++) — the caller owns one reference per page
        and must ``release`` them (retirement / trimming).
        """
        self._tick += 1
        self.lookups += 1
        pg = self.page_size
        toks = [int(t) for t in tokens]
        node, i, pages, match = self.root, 0, [], 0
        while i < len(toks):
            rem = tuple(toks[i:i + pg])
            best = node.children.get(rem)  # exact fast path
            best_o = len(rem) if best is not None else 0
            if best is None:
                for c in node.children.values():
                    o = _common_prefix(c.chunk, rem)
                    if o > best_o:
                        best, best_o = c, o
            if best is None or best_o == 0:
                break
            best.last_used = self._tick
            pages.append(best.page)
            match += best_o
            if best_o < pg or best_o < len(best.chunk):
                break  # partial overlap / partial chunk: path ends here
            node, i = best, i + pg
        if self.full_pages_only and match % pg:
            # int8: a partially-matched page would have to be COW-forked
            # and then REQUANTIZED by its new owner's writes — round the
            # hit down so only whole immutable pages are ever served
            match -= match % pg
            pages = pages[:match // pg]
        self.allocator.ref(pages)
        if match:
            self.hits += 1
            self.hit_tokens += match
        return match, pages

    # -- insert -------------------------------------------------------------

    def insert(self, tokens, pages) -> int:
        """Record ``tokens`` (whose KV rows live in ``pages``, in page
        order) in the tree.  Adopted pages gain a tree-owned reference;
        the caller's references are untouched (a slot still releases
        its own pages at retirement — preemption relies on exactly
        this: insert then release keeps the tree's reference as the
        page's ONLY holder, so the KV survives, resident but
        evictable, until re-admission looks it up).  Duplicate chunks
        dedup onto the existing node; a partial leaf overtaken by a
        longer chunk upgrades in place (partial chunks are always
        leaves, so the swap can't orphan descendants).  Returns the
        number of pages the tree NEWLY adopted (0 when the sequence
        was already fully covered) — the engine's preemption
        accounting reports it as work preserved across the evict."""
        self._tick += 1
        pg = self.page_size
        toks = [int(t) for t in tokens]
        chunks = [tuple(toks[i:i + pg]) for i in range(0, len(toks), pg)]
        assert len(chunks) <= len(pages), (len(chunks), len(pages))
        node, adopted = self.root, 0
        for ci, chunk in enumerate(chunks):
            page = pages[ci]
            if len(chunk) < pg and self.full_pages_only:
                break  # int8: the partial tail requantizes — don't share
            child = node.children.get(chunk)
            if child is None:
                for key, c in list(node.children.items()):
                    o = _common_prefix(c.chunk, chunk)
                    if o == len(chunk):
                        # existing chunk extends ours: already covered
                        c.last_used = self._tick
                        return adopted
                    if o == len(c.chunk) and o < len(chunk):
                        # partial leaf upgraded by this longer chunk
                        if c.page != page:
                            self.allocator.ref([page])
                            self.allocator.release([c.page])
                            c.page = page
                            adopted += 1
                            self.inserted_pages += 1
                        del node.children[key]
                        c.chunk = chunk
                        node.children[chunk] = c
                        child = c
                        break
                if child is None:
                    child = _RadixNode(chunk, page)
                    self.allocator.ref([page])
                    adopted += 1
                    self.inserted_pages += 1
                    node.children[chunk] = child
            child.last_used = self._tick
            if len(chunk) < pg:
                break  # partial tail: nothing descends past it
            node = child
        return adopted

    # -- eviction -----------------------------------------------------------

    def evict(self, n_pages: int) -> int:
        """Reclaim up to ``n_pages`` by dropping LRU LEAVES whose pages
        have no reader but the tree (allocator refcount == 1) — a
        pinned page is never evicted, an interior node never orphans
        its descendants.  Freeing a leaf can expose its parent, so the
        scan repeats until the quota is met or nothing is evictable.
        Returns the number of pages actually freed."""
        freed = 0
        while freed < n_pages:
            victims = [(c.last_used, parent, c) for parent, c in self._walk()
                       if not c.children
                       and self.allocator.refcount(c.page) == 1]
            if not victims:
                break
            victims.sort(key=lambda v: v[0])
            for _, parent, leaf in victims:
                if freed >= n_pages:
                    break
                del parent.children[leaf.chunk]
                self.allocator.release([leaf.page])
                freed += 1
                self.evicted_pages += 1
        return freed

    def drop_pages(self, pages) -> int:
        """Purge every node holding one of ``pages`` AND its whole
        subtree, releasing the tree's reference on each removed node's
        page.  Descendants must go too: their prefixes run *through*
        the dropped page's rows, so serving them would attend poisoned
        (or vanished) KV.  The serving supervisor calls this before
        quarantining pages a fault poisoned.  Returns nodes removed."""
        bad = {int(p) for p in pages}
        removed: list[_RadixNode] = []

        def _prune(node):
            for key, child in list(node.children.items()):
                if child.page in bad:
                    del node.children[key]
                    stack = [child]
                    while stack:
                        c = stack.pop()
                        removed.append(c)
                        stack.extend(c.children.values())
                else:
                    _prune(child)

        _prune(self.root)
        self.allocator.release([c.page for c in removed])
        self.evicted_pages += len(removed)
        return len(removed)

    def clear(self) -> int:
        """Drop every node (release all tree-held references)."""
        nodes = [c for _, c in self._walk()]
        self.allocator.release([c.page for c in nodes])
        self.root = _RadixNode()
        self.evicted_pages += len(nodes)
        return len(nodes)


# ---------------------------------------------------------------------------
# pool construction
# ---------------------------------------------------------------------------


def supports_paged(cfg) -> bool:
    """Paged serving covers the attention-cache families (GQA incl. SWA
    via in-kernel window masking, and MLA).  Recurrent state (SSM /
    hybrid) has O(1) per-sequence caches — nothing to page — and
    enc-dec cross-KV is per-request anyway."""
    return not (cfg.ssm_state or cfg.attn_every or cfg.is_enc_dec
                or cfg.frontend)


def _layer_pool(cfg, num_pages: int, page_size: int, dtype):
    quantized = dtype == jnp.int8
    if cfg.uses_mla:
        width = cfg.kv_lora_rank + cfg.rope_head_dim
        pool = {"kv_pages": jnp.zeros((1, num_pages, page_size, width), dtype)}
        if quantized:  # one scale row per page (shared [c_kv|k_rope] pool)
            pool["kv_scales"] = jnp.zeros((1, num_pages), jnp.float32)
        return pool
    pool = {
        "k_pages": jnp.zeros(
            (cfg.kv_heads, num_pages, page_size, cfg.head_dim), dtype),
        "v_pages": jnp.zeros(
            (cfg.kv_heads, num_pages, page_size, cfg.head_dim), dtype),
    }
    if quantized:  # per-page-per-head scales
        pool["k_scales"] = jnp.zeros((cfg.kv_heads, num_pages), jnp.float32)
        pool["v_scales"] = jnp.zeros((cfg.kv_heads, num_pages), jnp.float32)
    return pool


def init_paged_caches(cfg, batch: int, max_len: int, dtype=jnp.bfloat16, *,
                      page_size: int = 16, num_pages: int | None = None,
                      kv_dtype: str | None = None):
    """Paged serving caches for ``batch`` decode slots.

    Returns {"blocks": [per-layer pool dict], "block_tables":
    (B, pages_for(max_len)) int32 (-1 = unmapped), "lens": (B,) int32}.
    ``num_pages`` defaults to full backing (every slot can reach
    ``max_len``) — undersubscribe it to let the engine's admission
    control do its job.  ``kv_dtype`` ("f32"/"bf16"/"int8") overrides
    ``dtype`` for the pools; int8 pools carry per-page-per-head f32
    scales next to the pages.
    """
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"paged KV cache: unsupported family {cfg.family!r} "
            "(recurrent/enc-dec/frontend caches are not paged)")
    if kv_dtype is not None:
        dtype = KV_DTYPES[kv_dtype]
    max_pp = pages_for(max_len, page_size)
    if num_pages is None:
        num_pages = batch * max_pp
    return {
        "blocks": [_layer_pool(cfg, num_pages, page_size, dtype)
                   for _ in range(cfg.num_layers)],
        "block_tables": jnp.full((batch, max_pp), -1, jnp.int32),
        "lens": jnp.zeros((batch,), jnp.int32),
    }


def page_bytes(cfg, page_size: int, kv_dtype: str = "f32") -> int:
    """HBM bytes ONE logical page costs across all layers — the unit the
    engine's byte-budgeted pool sizing divides by.  A logical page maps
    to a (page_size, width) row block in EVERY layer's pool (the block
    table is shared), so the per-layer cost multiplies by num_layers;
    int8 pools add the 4 B/head/page scale metadata the same way the
    gradient-compression accounting counts its per-leaf scales."""
    item = jnp.dtype(KV_DTYPES[kv_dtype]).itemsize
    scales = 4 if KV_DTYPES[kv_dtype] == jnp.int8 else 0
    if cfg.uses_mla:
        width = cfg.kv_lora_rank + cfg.rope_head_dim
        per_layer = page_size * width * item + scales
    else:
        per_layer = cfg.kv_heads * (2 * page_size * cfg.head_dim * item
                                    + 2 * scales)
    return cfg.num_layers * per_layer


def pool_pages_for_bytes(cfg, pool_bytes: int, page_size: int,
                         kv_dtype: str = "f32") -> int:
    """Pages a byte budget buys — ``kv_dtype="int8"`` buys ~4x the pages
    of f32 for the same budget, which the engine converts directly into
    admission concurrency.  A budget below one page is an error, not a
    silent over-allocation: the engine's equal-byte comparisons depend
    on the pool never exceeding the stated budget."""
    pages = pool_bytes // page_bytes(cfg, page_size, kv_dtype)
    if pages < 1:
        raise ValueError(
            f"pool_bytes={pool_bytes} buys zero {kv_dtype} pages "
            f"(page_bytes={page_bytes(cfg, page_size, kv_dtype)})")
    return pages


def page_size_of(caches) -> int:
    pool = caches["blocks"][0]
    return next(iter(pool.values())).shape[2]


def find_nonfinite_pages(paged_blocks) -> list[int]:
    """Pool pages holding a non-finite value in ANY layer — the serving
    supervisor's poisoned-KV probe (a ``decode_nan`` fault writes NaN
    rows into a victim's pages; every page of every layer sharing that
    pool index is then suspect, because the block table maps one
    logical page to the same index in all layers).  int8 page rows
    cannot hold a NaN, but their per-page f32 scales can — and a NaN
    scale poisons every row it dequantizes — so quantized pools are
    probed via their scale leaves.  All leaves keep the page on axis 1.
    """
    first = next(iter(paged_blocks[0].values()))
    bad = np.zeros((first.shape[1],), bool)
    for pool in paged_blocks:
        for leaf in pool.values():
            if leaf.dtype == jnp.int8:
                continue  # integer codes are always finite
            axes = tuple(i for i in range(leaf.ndim) if i != 1)
            ok = np.asarray(jnp.all(jnp.isfinite(leaf), axis=axes))
            bad |= ~ok
    return [int(p) for p in np.nonzero(bad)[0]]


# ---------------------------------------------------------------------------
# prefix sharing: COW fork + prefix gather
# ---------------------------------------------------------------------------


def fork_page(paged_blocks, src, dst):
    """Copy-on-write fork: duplicate pool page ``src`` into ``dst``
    across every layer and every pool leaf (page rows AND int8 scales —
    both have the page on axis 1).  The engine calls this when a new
    reader's block table would otherwise point its WRITE position into
    a shared, partially-filled tail page: the reader gets a private
    copy to fill, the original stays byte-identical for its other
    readers.  Pure function; the engine jits it with the pools donated.
    """
    return [{k: v.at[:, dst].set(v[:, src]) for k, v in pool.items()}
            for pool in paged_blocks]


def seed_prefix_dense(dense_caches, paged_blocks, block_row, n_prefix):
    """Gather a cached prefix's page rows into a fresh batch-1 dense
    cache so chunked ragged prefill can RESUME at ``n_prefix``.

    The engine's prefill runs against a dense (1, T, ...) cache; a
    prefix hit means rows [0, n_prefix) already exist in shared pool
    pages.  This scatters them (dequantized for int8 pools) into the
    dense buffers and sets every layer ``len`` to ``n_prefix`` — the
    suffix's queries then attend the prefix exactly as if it had been
    prefilled in this slot, at an O(n_prefix) copy instead of an
    O(n_prefix) forward pass.  ``dense_caches`` must be freshly
    initialized (rows at/past ``n_prefix`` stay zero and are masked by
    ``len``).  Pure; jit with the dense caches donated.
    """
    blocks = dense_caches["blocks"]
    mla = "kv_pages" in paged_blocks[0]
    first = next(iter(paged_blocks[0].values()))
    num_pages, pg = first.shape[1], first.shape[2]
    quantized = first.dtype == jnp.int8
    max_pp = block_row.shape[0]
    t = (blocks["ckv"] if mla else blocks["k"]).shape[2]
    pos = jnp.arange(t)
    local = jnp.clip(pos // pg, 0, max_pp - 1)
    page = block_row[local]
    valid = (pos < n_prefix) & (page >= 0)
    pagec = jnp.where(valid, page, 0)  # gather page 0, mask rows after
    slot = pos % pg

    def gather(pool, pages_key, scales_key, cols=None):
        rows = pool[pages_key][:, pagec, slot]  # (Hkv|1, T, W)
        if cols is not None:
            rows = rows[..., cols[0]:cols[1]]
        rows = rows.astype(jnp.float32)
        if quantized:
            rows = rows * pool[scales_key][:, pagec][..., None]
        return rows * valid[None, :, None]

    if mla:
        r = blocks["ckv"].shape[-1]
        ckv, krope = [], []
        for pool in paged_blocks:
            row = gather(pool, "kv_pages", "kv_scales")[0]  # (T, r+dr)
            ckv.append(row[:, :r])
            krope.append(row[:, r:])
        new = {
            "ckv": jnp.stack(ckv)[:, None].astype(blocks["ckv"].dtype),
            "k_rope": jnp.stack(krope)[:, None].astype(
                blocks["k_rope"].dtype),
        }
    else:
        ks = [gather(pool, "k_pages", "k_scales").transpose(1, 0, 2)
              for pool in paged_blocks]
        vs = [gather(pool, "v_pages", "v_scales").transpose(1, 0, 2)
              for pool in paged_blocks]
        new = {
            "k": jnp.stack(ks)[:, None].astype(blocks["k"].dtype),
            "v": jnp.stack(vs)[:, None].astype(blocks["v"].dtype),
        }
    new["len"] = jnp.full_like(blocks["len"], n_prefix)
    return {"blocks": new}


# ---------------------------------------------------------------------------
# prefill copy-in
# ---------------------------------------------------------------------------


def pool_write_rows(pool, rows, page, slot):
    """Write KV rows into a float ``(Hkv, num_pages, page_size, D)`` pool.

    rows: ``(Hkv, *idx, D)``; page, slot: ``idx``-shaped int32 write
    coordinates, slot in ``[0, page_size)``.  A row whose page lies
    outside ``[0, num_pages)`` (``num_pages`` marks an inactive slot, a
    pad row or a position past the block table) is dropped.

    The rows are scattered into the flat ``(Hkv*num_pages*page_size, D)``
    view of the pool, a free reshape of its row-major layout, so the
    pool stays where it is.  The 4-D ``pool.at[:, page, slot]`` scatter
    makes XLA on TPU relayout the whole pool for the scatter and back
    again, two pool-sized copies per call.  In the flat view an
    out-of-range page would land inside the next head's rows, so dropped
    rows are sent past the end explicitly (all to the same index: no
    promise of unique indices).  Pure; jit with the pool donated.
    """
    hkv, num_pages, pg, d = pool.shape
    n_rows = hkv * num_pages * pg
    head = jnp.arange(hkv).reshape((hkv,) + (1,) * page.ndim)
    idx = (head * num_pages + page) * pg + slot
    idx = jnp.where((page >= 0) & (page < num_pages), idx, n_rows)
    flat = pool.reshape(n_rows, d).at[idx.reshape(-1)].set(
        rows.reshape(-1, d).astype(pool.dtype), mode="drop")
    return flat.reshape(pool.shape)


def write_prompt_pages(paged_blocks, dense_blocks, block_row, n_tokens,
                       row0_pos=0, row_lo=0):
    """Scatter one request's dense-prefill cache rows into its pages.

    paged_blocks: the per-layer pool list from :func:`init_paged_caches`;
    dense_blocks: the ``caches["blocks"]`` tree of a **batch-1** dense
    cache after prefill — GQA {"k"/"v": (L, 1, T, Hkv, D)} or MLA
    {"ckv": (L, 1, T, r), "k_rope": (L, 1, T, dr)}; block_row:
    (pages_per_seq,) int32 page ids for this request; n_tokens: live
    prompt length (traced ok).  ``row0_pos`` is the logical position of
    dense row 0 — 0 for plain buffers, ``n_tokens - buffer_len`` for an
    SWA rolling buffer (ordered snapshot: slot j holds position
    ``len - t + j``).  Rows mapping outside [0, n_tokens) — pad rows,
    unwritten rolling slots, -1 table tails — scatter out of bounds and
    are dropped.

    ``row_lo`` (traced ok) additionally drops rows BELOW a position: a
    prefix-cache hit means positions [0, row_lo) live in SHARED pages
    that must not be rewritten — only the freshly-prefilled suffix
    scatters, and int8 scale rows stay untouched for pages wholly below
    ``row_lo`` (the engine page-aligns ``row_lo`` on int8 pools, so a
    scale-scattered page never holds shared rows).  Pure function; the
    engine jits it with the pools donated.
    """
    first = next(iter(paged_blocks[0].values()))
    num_pages, pg = first.shape[1], first.shape[2]
    mla = "kv_pages" in paged_blocks[0]
    quantized = first.dtype == jnp.int8
    max_pp = block_row.shape[0]
    if mla:
        dense_rows = jnp.concatenate(
            [dense_blocks["ckv"], dense_blocks["k_rope"]], axis=-1
        )[:, 0]  # (L, T, r+dr)
        t = dense_rows.shape[1]
    else:
        t = dense_blocks["k"].shape[2]

    pos = jnp.arange(t) + row0_pos  # logical position of each dense row
    local = jnp.clip(pos // pg, 0, max_pp - 1)
    page = block_row[local]
    valid = (pos >= 0) & (pos >= row_lo) & (pos < n_tokens) & (page >= 0)
    page = jnp.where(valid, page, num_pages)
    slot = pos % pg
    # scale scatter targets: every MAPPED page of this request from the
    # first non-shared page on — pages reserved beyond the prompt get
    # the eps scale (their recycled int8 garbage dequantizes to ~0
    # until the decode write overwrites them); pages below row_lo are
    # shared prefix pages and keep their existing scales
    owned = jnp.arange(max_pp) >= row_lo // pg
    spage = jnp.where((block_row >= 0) & owned, block_row, num_pages)

    def _page_quant(rows):
        """rows: (T, ..., W) f32 -> (q rows, per-page scales (max_pp, ...))
        — one scale per (page, head) over the page's VALID rows."""
        amax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1)
        amax = jnp.where(valid.reshape(t, *([1] * (amax.ndim - 1))), amax, 0.0)
        seg = jnp.zeros((max_pp,) + amax.shape[1:], jnp.float32)
        scales = scale_from_amax(seg.at[local].max(amax))
        return quant_with_scale(rows, scales[local][..., None]), scales

    out = []
    for li, pool in enumerate(paged_blocks):
        if mla:
            if quantized:
                q, s = _page_quant(dense_rows[li])  # (T, W), (max_pp,)
                out.append({
                    "kv_pages": pool["kv_pages"].at[0, page, slot].set(
                        q, mode="drop"),
                    "kv_scales": pool["kv_scales"].at[0, spage].set(
                        s, mode="drop"),
                })
            else:
                out.append({
                    "kv_pages": pool["kv_pages"].at[0, page, slot].set(
                        dense_rows[li], mode="drop"),
                })
        elif quantized:
            qk, sk = _page_quant(dense_blocks["k"][li, 0])  # (T,Hkv,D)
            qv, sv = _page_quant(dense_blocks["v"][li, 0])
            out.append({
                "k_pages": pool["k_pages"].at[:, page, slot].set(
                    qk.transpose(1, 0, 2), mode="drop"),
                "v_pages": pool["v_pages"].at[:, page, slot].set(
                    qv.transpose(1, 0, 2), mode="drop"),
                "k_scales": pool["k_scales"].at[:, spage].set(
                    sk.T, mode="drop"),
                "v_scales": pool["v_scales"].at[:, spage].set(
                    sv.T, mode="drop"),
            })
        else:
            out.append({
                "k_pages": pool_write_rows(
                    pool["k_pages"], dense_blocks["k"][li, 0].transpose(1, 0, 2),
                    page, slot),
                "v_pages": pool_write_rows(
                    pool["v_pages"], dense_blocks["v"][li, 0].transpose(1, 0, 2),
                    page, slot),
            })
    return out


def quant_page_update(pages, scales, page, slot, row):
    """Insert one decode token's row per sequence into its int8 page,
    requantizing the page under the (possibly grown) scale.

    pages: (Hkv, P, pg, W) int8 pool; scales: (Hkv, P) f32; page/slot:
    (B,) int32 write coordinates from ``_paged_token_coords`` (page == P
    for inactive slots -> scatter dropped); row: (Hkv, B, W) f32.
    Returns (pages, scales).

    The page is gathered, dequantized, the new row inserted, and the
    whole page requantized at its new max: if the new row fits the old
    range the old rows requantize EXACTLY (same scale, int8 codes
    unchanged); a range-growing row re-rounds the page's rows once.
    Rows past the write slot are recycled-page garbage — masked out of
    the max and zeroed on the write, so a retired request's large
    values can never inflate (or corrupt) a new request's scale.
    """
    hkv, num_pages, pg, w = pages.shape
    b = page.shape[0]
    pcl = jnp.clip(page, 0, num_pages - 1)
    cur = pages[:, pcl].astype(jnp.float32) * scales[:, pcl][..., None, None]
    cur = cur.at[:, jnp.arange(b), slot].set(row.astype(jnp.float32))
    live = jnp.arange(pg)[None, :] <= slot[:, None]  # (B, pg)
    cur = cur * live[None, :, :, None]
    new_scale = scale_for(cur, axes=(2, 3))  # (Hkv, B)
    new_q = quant_with_scale(cur, new_scale[..., None, None])
    return (pages.at[:, page].set(new_q, mode="drop"),
            scales.at[:, page].set(new_scale, mode="drop"))
