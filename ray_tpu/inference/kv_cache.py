"""Paged KV cache for continuous-batching decode.

The cache is two preallocated device arrays per model —
``[n_layers, pages, kv_heads, head_dim, page_size]`` K and V, the page
offset minor (a page of one layer is ``kv_heads`` lane-dense
``[head_dim, page_size]`` tiles: the block the decode kernel reads in
place) — plus a *host-side* page table: each decode slot owns a row of
page indices covering its reserved context.  Sequences of wildly different lengths
then share one fixed allocation (the vLLM paged-attention idea, here
XLA-functional): admission reserves ``ceil((prompt + max_new) / page)``
pages from a free list, retirement returns them, and the device arrays
never reallocate — the compiled decode step donates them in and gets
them back, so steady-state decode allocates nothing.

Page 0 is reserved as a garbage page: free slots' page-table rows (and
the padded tail of short rows) point at it, so the fixed-shape decode
step can scatter "writes" for inactive slots and prefill can write its
padded bucket tail without corrupting live pages.  Reads of garbage are
masked by per-slot lengths in ``decode_attention``.

Device-side update/gather helpers are plain functional jnp ops (scatter
via ``.at[]``, gather via advanced indexing) so they trace into the
engine's compiled steps, and a decode attends over the pool where it
lies (:func:`attend`); the host-side :class:`PageAllocator` owns the
refcounts, free structures and the leak invariants
(``tests/test_inference.py``).

**Prefix sharing (r12).**  Full pages are immutable — decode appends
only ever land in the private tail page past the prompt — so a full
prompt page can be *shared* across requests byte-for-byte.
:class:`PrefixIndex` registers full pages under chained content hashes
and :class:`PageAllocator` refcounts every reference; refcount-0
registered pages park in an LRU idle pool that ``alloc`` evicts from
only after the free list runs dry, so the idle cache is reusable
prefix storage rather than dead HBM.  Sharing is pure host-side page-
table metadata: the compiled steps never see it, and ``int8`` caches
share bit-identically because cache writes use deterministic
rounding.

**Disaggregated handoff (r20).**  Because pages are content-addressed
and refcounted, moving a request from a prefill replica to a decode
replica is a transfer of page *ownership*, not a copy protocol:
:func:`export_pages` reads a retired-but-held request's page contents
host-side into a :class:`KVHandoff` (context tokens + chained hashes +
raw K/V; int8 codes and scales ride the same arrays, halving the
bytes vs bf16), and :func:`import_pages` writes only the pages the
importing engine does *not* already hold by chain hash into its own
allocator's fresh pages — a warm importer installs the whole context
as prefix hits and the handoff moves no contents at all.

``kv_dtype="int8"`` stores the K/V arrays block-scale-quantized
(``ray_tpu.quant``): codes in int8, one f32 scale per (page, position,
head) lane vector riding in per-page scale arrays
``[n_layers, pages, kv_heads, page_size]``.  The write/gather helpers
are shape-generic (they address ``[L, P, ..., page_size]`` storage by
(layer, page)), so the same scatter/gather moves codes and scales;
:func:`append` quantizes post-RoPE on write and ``decode_attention``
dequantizes inside its page blocks (:func:`attend` hands it the
scales).  At head_dim 64 that is 68 bytes per cached
vector (64 codes + one f32 scale) vs 128 in bf16 — :meth:`KVCache.bytes`
counts both arrays, so the ~2x capacity-per-HBM-byte claim is
asserted, not assumed.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

GARBAGE_PAGE = 0
# the cache's arrays in :attr:`KVCache.state` order, as a handoff and
# an :func:`export_pages` gather name them
_NAMES = ("k", "v", "k_scale", "v_scale")


class HandoffContentMissing(RuntimeError):
    """Typed import failure: a metadata-only (warm) KV handoff reached
    admission but the resident pages it counted on were no longer in
    the prefix index (evicted between the router's digest check and
    the import's admission walk).  Everything the admission touched is
    released before this surfaces — the disagg router treats it as a
    re-prefill-from-prompt signal, never a user-facing error."""

    def __init__(self, rid: int, missing_pages: int):
        super().__init__(
            f"request {rid}: metadata-only KV handoff is missing "
            f"{missing_pages} page(s) no longer resident — re-prefill "
            "from the prompt")
        self.rid = rid
        self.missing_pages = missing_pages

    def __reduce__(self):
        # rebuild from constructor args (the event's error channel can
        # cross the object store on serve streams)
        return (HandoffContentMissing, (self.rid, self.missing_pages))


@dataclasses.dataclass
class KVHandoff:
    """One request's KV-page ownership transfer (disaggregated
    prefill -> decode, r20).

    The payload a prefill replica exports after emitting the first
    sampled token: the cached context's token ids, the chained content
    hashes of its full pages (the importer's skip-transfer key — a
    decode replica already holding a page by hash installs it with a
    refcount bump and never touches the contents), and the raw per-page
    K/V contents host-side — int8 codes + scales ride the same arrays
    when the fleet runs a quantized cache, which is what halves the
    handoff bytes on the wire.  ``k``/``v`` are ``None`` for a
    *metadata-only* (warm) handoff: the router verified every context
    page resident on the importer by digest, so no contents move at
    all.

    Shapes: ``k``/``v`` are ``[n_layers, n_pages, page_size, kv_heads,
    head_dim]`` in the cache's storage dtype; ``k_scale``/``v_scale``
    (int8 caches only) are ``[n_layers, n_pages, page_size, kv_heads]``
    f32.  That is the format of what leaves this file, whatever the
    device pool's own layout: :func:`export_pages` and
    :func:`import_pages` convert at the boundary, so contents written
    by an older replica still install.  Page order
    matches :func:`pages_needed` over ``context``: full pages first,
    then the partial tail (whose positions past ``len(context) %
    page_size`` are garbage the decode attention masks, exactly as on
    the exporter).
    """

    context: List[int]              # token ids whose K/V are cached
    page_size: int
    kv_dtype: str                   # "model" | "int8"
    dtype: str                      # storage dtype name (drift check)
    chain_hashes: List[bytes]       # one per FULL context page
    next_token: int                 # first sampled token (emitted)
    next_logprob: float
    k: Optional[np.ndarray] = None
    v: Optional[np.ndarray] = None
    k_scale: Optional[np.ndarray] = None
    v_scale: Optional[np.ndarray] = None
    # which ABSOLUTE page indices the content arrays carry (None =
    # all of 0..n_pages): a stripped handoff ships only the pages its
    # target does not already hold by chain hash
    present: Optional[List[int]] = None
    # wire form of the request's TraceContext (r24) — the trace rides
    # the payload, so importer-side spans join the exporter's tree
    trace: Optional[dict] = None
    # multi-tenant serving (r25): the adapter the context was prefilled
    # under (None = base).  ``adapter_version`` pins the exact store
    # version — the decode side must attend under the same factors the
    # prefill used, even across a mid-traffic republish; a decode
    # replica lacking it fetches through the AdapterStore on import.
    # The handoff's chain_hashes are already salted by (model_id,
    # version), so prefix digests never alias tenants.
    model_id: Optional[str] = None
    adapter_version: int = 0

    @property
    def n_pages(self) -> int:
        return pages_needed(len(self.context), self.page_size)

    @property
    def n_full_pages(self) -> int:
        return len(self.context) // self.page_size

    @property
    def page_list(self) -> List[int]:
        """Absolute indices of the pages whose contents ride along."""
        if self.k is None:
            return []
        if self.present is None:
            return list(range(self.n_pages))
        return list(self.present)

    @property
    def nbytes(self) -> int:
        """Content bytes on the wire (0 for a metadata-only handoff)."""
        return sum(a.nbytes for a in (self.k, self.v, self.k_scale,
                                      self.v_scale) if a is not None)

    def strip_contents(self) -> "KVHandoff":
        """The metadata-only view (the warm-handoff wire form)."""
        return dataclasses.replace(self, k=None, v=None, k_scale=None,
                                   v_scale=None, present=[])

    def strip_to(self, pages: Sequence[int]) -> "KVHandoff":
        """The wire form carrying only ``pages`` (absolute indices) —
        the partial-residency handoff: pages the target already holds
        by chain hash are dropped from the payload instead of being
        serialized, shipped, and discarded."""
        pages = list(pages)
        if not pages:
            return self.strip_contents()
        have = self.page_list
        sel = [have.index(i) for i in pages]    # raises on a bad strip
        rep = {"present": pages}
        for name in ("k", "v", "k_scale", "v_scale"):
            a = getattr(self, name)
            rep[name] = a[:, sel] if a is not None else None
        return dataclasses.replace(self, **rep)


def handoff_page_bytes(*, n_layers: int, page_size: int, n_heads: int,
                       head_dim: int, itemsize: int,
                       quantized: bool) -> int:
    """Analytic content bytes one handoff page carries — K and V across
    all layers (+ their f32 scale lanes when quantized).  The figure
    the measured ``serve_handoff_bytes_total`` is checked against
    (``tests/test_disagg.py``): int8 caches move
    ``head_dim + 4`` bytes per cached vector vs ``head_dim * itemsize``
    for the model dtype — ~half of bf16, the disagg wire saving."""
    per_vector = head_dim * itemsize + (4 if quantized else 0)
    return 2 * n_layers * page_size * n_heads * per_vector


def export_pages(cache: "KVCache", pages: Sequence[int]
                 ) -> Dict[str, np.ndarray]:
    """Read ``pages``' K/V contents out of the device cache, host-side:
    ``{"k", "v"[, "k_scale", "v_scale"]}`` stacked ``[L, n_pages, ...]``
    in page order.  One gather per array (a DMA on a real device; the
    in-place object-store put is the on-chip follow-up)."""
    if cache.latent:
        refuse_latent("export_pages (a KVHandoff's contents)")
    idx = np.asarray(list(pages), np.int32)
    return {name: np.ascontiguousarray(
                np.moveaxis(np.asarray(a[:, idx]), -1, 2))
            for name, a in zip(_NAMES, cache.state)}


def import_pages(cache: "KVCache", pages: Sequence[int],
                 handoff: "KVHandoff", sel: Sequence[int]) -> None:
    """Write the handoff's pages ``sel`` into ``cache`` at page indices
    ``pages`` (aligned sequences).  Runs between engine ticks on the
    host — a functional ``.at[].set`` that the next compiled step's
    donated state picks up; pages the importer already holds by content
    hash are simply absent from ``sel`` (the skip-transfer path)."""
    if cache.latent:
        refuse_latent("import_pages (a KVHandoff's contents)")
    if not len(pages):
        return
    idx = np.asarray(list(pages), np.int32)
    sel = np.asarray(list(sel), np.int64)
    cache.state = tuple(
        a.at[:, idx].set(np.moveaxis(getattr(handoff, name)[:, sel], 2, -1))
        for name, a in zip(_NAMES, cache.state))


class PrefixIndex:
    """Content-addressed index over *full, immutable* KV pages.

    A page is registered under its chained hash
    ``h = H(parent_h, page_tokens)`` — the hash covers the page's own
    tokens *and* (through the parent link) every token before it, so a
    hash hit means the whole prefix up to and including this page is
    byte-identical.  Admission walks a prompt's full pages through
    :meth:`lookup` front-to-back and stops at the first miss; every hit
    is installed into the slot's page-table row with a refcount bump
    and zero prefill compute.

    Pure host metadata: hash -> page and page -> hash maps.  Lifecycle
    (refcounts, the idle-LRU pool, eviction) lives in
    :class:`PageAllocator`, which calls :meth:`forget` when it evicts a
    registered page to reuse its storage.
    """

    ROOT = b""

    def __init__(self):
        self._by_hash: Dict[bytes, int] = {}
        self._by_page: Dict[int, bytes] = {}

    @staticmethod
    def chain(parent: bytes, tokens: Sequence[int]) -> bytes:
        """``H(parent_h, page_tokens)`` — 128-bit blake2b keeps token-
        collision risk negligible while the digest stays dict-cheap."""
        h = hashlib.blake2b(digest_size=16)
        h.update(parent)
        h.update(np.asarray(tokens, np.int64).tobytes())
        return h.digest()

    @classmethod
    def chain_hashes(cls, tokens: Sequence[int],
                     page_size: int, salt: bytes = b"") -> List[bytes]:
        """Chained hashes of every *full* page of ``tokens`` — the one
        walk both the scheduler (registration/hit lookup) and the
        fleet router (affinity matching) must agree on byte-for-byte,
        so it lives here.

        ``salt`` overrides the chain root (r25 multi-tenant serving:
        ``adapters.lora.salt_bytes(model_id, version)``).  Adapter K/V
        differs from base K/V for identical token prefixes, so salted
        chains keep tenants from ever aliasing in the prefix index;
        base traffic keeps the unsalted root, so its
        hashes — and every pre-r25 digest — are unchanged."""
        h = salt or cls.ROOT
        out = []
        for i in range(len(tokens) // page_size):
            h = cls.chain(h, tokens[i * page_size:(i + 1) * page_size])
            out.append(h)
        return out

    @staticmethod
    def hit_eligible(n_tokens: int, page_size: int) -> int:
        """How many leading full pages of an ``n_tokens`` prompt may
        be taken as hits: the page holding the final prompt token is
        excluded even when full — its last token's logits seed the
        first sampled token, so at least one suffix token must always
        prefill."""
        return (n_tokens - 1) // page_size

    def lookup(self, chain_hash: bytes) -> Optional[int]:
        return self._by_hash.get(chain_hash)

    def register(self, chain_hash: bytes, page: int) -> bool:
        """Map ``chain_hash -> page``; refuses (returns False) if either
        side is already registered — first registration wins, so two
        copies of the same content never alias in the index."""
        if chain_hash in self._by_hash or page in self._by_page:
            return False
        self._by_hash[chain_hash] = page
        self._by_page[page] = chain_hash
        return True

    def has(self, page: int) -> bool:
        return page in self._by_page

    def forget(self, page: int) -> None:
        h = self._by_page.pop(page, None)
        if h is not None:
            del self._by_hash[h]

    def clear(self) -> int:
        """Forget every registration (prefix-cache invalidation: the
        cached K/V no longer matches the params after a weight swap).
        Returns how many entries were dropped."""
        n = len(self._by_hash)
        self._by_hash.clear()
        self._by_page.clear()
        return n

    def digest(self) -> frozenset:
        """Snapshot of every registered chain hash — the fleet
        router's prefix-affinity signal: a prompt whose chained page
        hashes appear here would hit this engine's cache.  A frozen
        copy (the router holds it across its own bookkeeping; the
        live dicts keep mutating under admissions), cheap at the
        page-pool sizes a replica runs (hundreds of entries)."""
        return frozenset(self._by_hash)

    def __len__(self) -> int:
        return len(self._by_hash)


class PageAllocator:
    """Refcounted acquire/release allocator over the page pool (page 0
    never handed out).

    Every allocated page carries a refcount: :meth:`alloc` hands out
    pages at refcount 1, a prefix hit :meth:`acquire`\\ s an extra
    reference, and :meth:`release` drops one — storage only becomes
    reusable at refcount 0.  A refcount-0 page *registered in the
    prefix index* is not freed: it parks in an LRU idle pool, its KV
    content intact, so the whole idle cache doubles as prefix storage.
    ``alloc`` takes truly-free pages first and only then evicts idle
    pages oldest-first (unregistering them via ``index.forget``), so
    allocation never fails while idle capacity remains.

    Free/double-free checks are O(1): the free list keeps a companion
    set, and refcounts live in a dict — a retire burst of R requests
    costs O(pages), not the O(R * pages^2) the old ``p in list`` scan
    paid.
    """

    def __init__(self, num_pages: int,
                 index: Optional[PrefixIndex] = None):
        if num_pages < 2:
            raise ValueError(f"need >= 2 pages (1 garbage + 1 usable), "
                             f"got {num_pages}")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._free_set = set(self._free)
        self._refcount: Dict[int, int] = {}
        # refcount-0 registered pages, insertion order = LRU -> MRU
        self._idle: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self._index = index
        self.evictions = 0

    @property
    def free_count(self) -> int:
        """Pages available to ``alloc``: truly free + evictable idle."""
        return len(self._free) + len(self._idle)

    @property
    def idle_count(self) -> int:
        return len(self._idle)

    def refcount(self, page: int) -> int:
        return self._refcount.get(page, 0)

    def is_idle(self, page: int) -> bool:
        """Registered at refcount 0 (parked in the LRU pool)."""
        return page in self._idle

    def flush_idle(self) -> int:
        """Return every idle page to the free list, forgetting its
        index entry — the bulk invalidation path (a weight swap makes
        all cached K/V stale at once; piecemeal LRU eviction would
        keep serving it until pressure happened to evict)."""
        n = len(self._idle)
        for page in self._idle:
            if self._index is not None:
                self._index.forget(page)
            self._free.append(page)
            self._free_set.add(page)
        self._idle.clear()
        return n

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` pages at refcount 1, or None (caller keeps the request
        waiting).  Prefers the free list; evicts idle prefix pages
        LRU-first only once it runs dry."""
        if n > self.free_count:
            return None
        pages = []
        for _ in range(n):
            if self._free:
                p = self._free.pop()
                self._free_set.discard(p)
            else:
                p, _ = self._idle.popitem(last=False)   # oldest idle
                self.evictions += 1
                if self._index is not None:
                    self._index.forget(p)
            self._refcount[p] = 1
            pages.append(p)
        return pages

    def acquire(self, page: int) -> None:
        """Take one more reference on a live or idle page (prefix hit).

        An idle page revives — leaves the LRU pool with its content
        still valid — which is exactly why admission acquires its hits
        *before* allocating fresh pages: the fresh allocation's own
        eviction must not grab a page we are about to share."""
        if page == GARBAGE_PAGE:
            raise ValueError("acquiring the reserved garbage page")
        if page in self._idle:
            del self._idle[page]
            self._refcount[page] = 1
            return
        if page not in self._refcount:
            raise ValueError(f"acquiring unallocated page {page}")
        self._refcount[page] += 1

    def release(self, pages: List[int]) -> None:
        """Drop one reference per page.  At refcount 0 a registered
        page parks in the idle pool (MRU end); an unregistered one
        returns to the free list."""
        for p in pages:
            if p == GARBAGE_PAGE:
                raise ValueError("freeing the reserved garbage page")
            rc = self._refcount.get(p)
            if rc is None:
                raise ValueError(f"double free of page {p}")
            if rc > 1:
                self._refcount[p] = rc - 1
                continue
            del self._refcount[p]
            if self._index is not None and self._index.has(p):
                self._idle[p] = None
            else:
                self._free.append(p)
                self._free_set.add(p)

    # r10-compatible spelling; refcounted release is the real semantics
    free = release


class KVCache:
    """The preallocated paged K/V arrays plus their static geometry.

    K and V are ``[n_layers, pages, kv_heads, head_dim, page_size]``:
    the page offset is the minor dimension, which is how a TPU lays a
    head_dim-64 cache out whatever it is declared as (head_dim on the
    lanes would waste half of them), and declared so the decode kernel
    reads a page's ``[H, D, page_size]`` block where it lies.
    ``kv_dtype``: ``"model"`` stores ``dtype`` K/V; ``"int8"`` stores
    int8 codes plus per-(page, head, position) f32 scale arrays
    ``[n_layers, pages, kv_heads, page_size]``.  The
    engine threads :attr:`state` — ``(k, v)`` or
    ``(k, v, k_scale, v_scale)`` — through its donated compiled steps,
    so decode allocates nothing in either mode.

    ``latent=(rank, rope)`` declares the other row this file knows: one
    latent vector of ``rank`` values plus a rotary part of ``rope``
    values a token a layer (latent attention: every head shares the
    row, and K and V are projections of it).  The pool is then one
    array ``[n_layers, pages, rank + rope, page_size]``, the page offset
    minor as above (a page is a lane-dense ``[rank + rope, page_size]``
    block with no padding: 576 values are 36 bfloat16 sublane tiles),
    :attr:`state` is ``(rows,)``, and ``n_heads`` / ``head_dim`` are not
    read.  What is written over K and V and has not been ported refuses
    such a cache by name (:func:`refuse_latent`)."""

    def __init__(self, *, n_layers: int, num_pages: int, page_size: int,
                 n_heads: int = 0, head_dim: int = 0, dtype=None,
                 kv_dtype: str = "model",
                 latent: Optional[Tuple[int, int]] = None):
        if kv_dtype not in ("model", "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}; "
                             "expected 'model' or 'int8'")
        self.num_pages = num_pages
        self.page_size = page_size
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        self.latent = tuple(latent) if latent else None
        if self.latent:
            if self.quantized:
                refuse_latent("an int8 KV cache (kv_dtype='int8') and "
                              "its scales")
            self.k = jnp.zeros((n_layers, num_pages, sum(self.latent),
                                page_size), dtype)
            self.v = None
            return
        shape = (n_layers, num_pages, n_heads, head_dim, page_size)
        store = jnp.int8 if self.quantized else dtype
        self.k = jnp.zeros(shape, store)
        self.v = jnp.zeros(shape, store)
        if self.quantized:
            # scales start at 0 (fresh garbage dequantizes to zeros),
            # but writes routed to the garbage page overwrite them with
            # real values — its harmlessness rests on decode_attention
            # masking positions >= length, same as the unquantized cache
            self.k_scale = jnp.zeros(shape[:3] + shape[4:], jnp.float32)
            self.v_scale = jnp.zeros(shape[:3] + shape[4:], jnp.float32)

    @property
    def dtype(self):
        """Storage dtype of K and V: int8 codes when quantized."""
        return self.k.dtype

    @property
    def state(self) -> Tuple:
        """The donated device arrays, in step-argument order."""
        if self.latent:
            return (self.k,)
        if self.quantized:
            return (self.k, self.v, self.k_scale, self.v_scale)
        return (self.k, self.v)

    @state.setter
    def state(self, arrays: Tuple) -> None:
        if self.latent:
            self.k, = arrays
        elif self.quantized:
            self.k, self.v, self.k_scale, self.v_scale = arrays
        else:
            self.k, self.v = arrays

    @property
    def bytes(self) -> int:
        """True cache footprint — K/V *and* (when quantized) the scale
        arrays; the r10 figure omitted nothing only because there were
        no scales yet."""
        return sum(a.size * a.dtype.itemsize for a in self.state)

    def bytes_per_slot(self, pages_per_slot: int) -> int:
        """HBM bytes one fully-reserved decode slot pins (codes +
        scales across all layers) — the capacity-planning figure the
        telemetry summary reports."""
        return pages_per_slot * (self.bytes // self.num_pages)

    @property
    def reads_in_place(self) -> bool:
        """Whether a decode attends over this pool through a kernel
        (:func:`attend`'s own decision, from the backend and the pool's
        shape and dtype)."""
        from ray_tpu.ops import attention as ops
        if self.latent:
            return ops.latent_decode_uses_pallas(
                self.k.shape[-2], self.page_size, self.dtype)
        return ops.decode_uses_pallas(self.k.shape[-2], self.page_size,
                                      quantized=self.quantized)

    @property
    def writes_in_place(self) -> bool:
        """Whether a decode lays its rows into the live slots' tail
        pages in place (the write kernel) or blends every slot's tail
        page whole: :func:`append_decode`'s own decision."""
        from ray_tpu.ops import attention as ops
        if self.latent:
            return ops.latent_decode_uses_pallas(
                self.k.shape[-2], self.page_size, self.dtype)
        return ops.decode_write_uses_pallas(self.k.shape[-2],
                                            self.page_size, self.dtype)


def refuse_latent(feature: str):
    """What is written over K and V rows ``[H, D]`` and has not been
    ported to a latent row says so, by name, where it is asked for."""
    raise NotImplementedError(
        f"{feature}: not supported over a latent cache row (it is "
        "written over K and V rows [heads, head_dim]; see "
        "inference/kv_cache.py:KVCache)")


def _blend_pages(pages, layer, page, rows, hit):
    """Write ``rows`` where ``hit`` into pages ``page`` of ``layer`` and
    return the updated stacked array.

    pages: [L, P, *rest, page_size]; page: [n] int32; rows:
    [n, page_size, *rest] (or [n, 1, *rest], one row laid wherever
    ``hit``); hit: [n, page_size] bool.  Read the n pages, lay the new
    rows over them, scatter the n *whole* pages back at ``(layer,
    page)``: only those pages cross HBM, never a layer's pool, and the
    rows are turned offset-minor n pages at a time, never a pool.
    Whole pages and not single rows because the page offset is on the
    lanes, where a row is one lane of every tile of its page: XLA
    answers a row scatter by re-laying the whole cache out on the way
    in and back on the way out of every step; the same compiler keeps
    a whole-page scatter, like the page gather, in place in the layout
    the array came in (``tests/test_tpu_aot.py``)."""
    hit = hit.reshape(hit.shape[:1] + (1,) * (rows.ndim - 2)
                      + hit.shape[1:])
    old = pages[layer, page]
    return pages.at[layer, page].set(
        jnp.where(hit, jnp.moveaxis(rows, 1, -1).astype(pages.dtype), old))


def write_prefill(pages, new, layer, page_row):
    """Write a prompt's K (or V) into one slot's pages of one layer —
    the cold (start-0, whole-bucket) case of :func:`write_prefill_at`.

    pages: [L, P, H, D, page_size] (the whole stacked array); new:
    [S, H, D] (bucket-padded — with ``valid_len = S`` tail positions
    land in whatever ``page_row`` maps them to, the garbage page for
    unreserved tail entries); layer: traced scalar; page_row:
    [max_pages] int32.  Returns the updated stacked array."""
    return write_prefill_at(pages, new, layer, page_row, 0, new.shape[0])


def write_prefill_at(pages, new, layer, page_row, start, valid_len):
    """Write a *suffix*'s K (or V) at absolute positions
    ``start .. start+valid_len`` of one slot's pages of one layer (the
    cached-context prefill: positions below ``start`` are prefix-cache
    hits that must not be touched).

    pages: [L, P, *rest, page_size] (the whole stacked array — the
    layer is one more coordinate of the write, never a slice); new:
    [S, *rest] (bucket-padded suffix); layer/start/valid_len: traced
    scalars; page_row: [max_pages] int32.  The suffix touches at most
    ``ceil(S / page_size) + 1`` of the slot's pages; each is rewritten
    whole with the valid rows laid over what it held
    (:func:`_blend_pages`).  Rows past ``valid_len`` are written
    nowhere, and a candidate page that holds no valid row routes to
    the garbage page *explicitly* — a suffix bucket can overhang the
    slot's reserved pages (start + bucket > max_pages * page_size),
    where the cold prefill's garbage-padded ``page_row`` tail no longer
    covers it.  Returns the updated stacked array."""
    S, page_size = new.shape[0], pages.shape[-1]
    n = pages_needed(S, page_size) + 1
    cand = start // page_size + jnp.arange(n)   # indices into page_row
    # row r of candidate page j holds suffix row idx[j, r]
    idx = (cand[:, None] * page_size
           + jnp.arange(page_size)[None, :] - start)
    hit = (idx >= 0) & (idx < valid_len)
    page = jnp.where(
        hit.any(axis=1),
        page_row[jnp.clip(cand, 0, page_row.shape[0] - 1)],
        GARBAGE_PAGE)
    return _blend_pages(pages, layer, page,
                        new[jnp.clip(idx, 0, S - 1)], hit)


def write_decode(pages, new, layer, page_table, lengths):
    """Write one new token per slot into its page of one layer.

    pages: [L, P, H, D, page_size] (the whole stacked array); new:
    [B, H, D]; layer: traced scalar; page_table: [B, max_pages] int32;
    lengths: [B] int32 — the token's absolute position (inactive slots
    point at the garbage page).  Each slot's tail page is rewritten
    whole with the token laid over it (:func:`_blend_pages`): the
    decode's writer where the write kernel does not run, and of an
    int8 cache's scales everywhere (:func:`append_decode`).  Returns
    the updated stacked array."""
    page_size = pages.shape[-1]
    page = jnp.take_along_axis(page_table,
                               (lengths // page_size)[:, None], 1)[:, 0]
    hit = jnp.arange(page_size)[None, :] == (lengths % page_size)[:, None]
    return _blend_pages(pages, layer, page, new[:, None], hit)


# ------------------------------------------------- what a step needs --
# A compiled step sees the cache as ``cache = (layer, arrays)``: the
# scan's layer index and :attr:`KVCache.state`.  What a row is — K and V
# ``[H, D]``, plus an f32 scale per head when the arrays are int8 codes,
# or one latent vector and its rotary part side by side in one pool
# (``len(arrays) == 1``) — is decided here and nowhere else.

def _quantize_rows(kv):
    """[..., H, D] post-RoPE K or V -> (int8 codes, [..., H] f32
    scales): one scale per head_dim lane vector (deterministic
    rounding — cache entries are weights-like, read many times)."""
    from ray_tpu.quant import quantize_block
    q, s = quantize_block(kv, block=kv.shape[-1], axis=-1)
    return q, s[..., 0]


def append(write, cache, k, v, *where):
    """Write the new tokens' post-RoPE K and V rows ``[..., H, D]``
    into one layer of every cache array — codes and scales when the
    cache is int8 — with the writer ``write`` at ``where``:
    :func:`write_prefill` at ``page_row`` (a whole prompt),
    :func:`write_prefill_at` at ``page_row, start, valid_len`` (a
    suffix), :func:`write_decode` at ``page_table, lengths`` (one row
    per slot).  For a latent cache ``k`` and ``v`` are the row's two
    parts, the latent vector ``[..., rank]`` and the rotary part
    ``[..., rope]``.  -> ``(layer, updated arrays)``."""
    layer, arrays = cache
    rows = (k, v)
    if len(arrays) == 1:
        rows = (jnp.concatenate([k, v], -1),)
    if len(arrays) == 4:
        (kq, ks), (vq, vs) = _quantize_rows(k), _quantize_rows(v)
        rows = (kq, vq, ks, vs)
    return layer, tuple(write(a, r, layer, *where)
                        for a, r in zip(arrays, rows))


def append_decode(cache, k, v, page_table, lengths):
    """A decode's :func:`append`: one new row ``[B, H, D]`` of K and of
    V per slot at ``page_table, lengths``.  Where the pools block for
    the write kernel (``ops/attention.py:decode_write_uses_pallas``, the
    one decision, from the backend and the pool's shape and dtype) the
    rows of the slots that hold a sequence are laid into their tail
    pages in place, K and V in one kernel; a slot whose tail page is
    the garbage page writes nothing, nothing reads that page below a
    length mask.  Everywhere else, and for an int8 cache's rank-4
    scale pools, :func:`write_decode` blends whole pages as before."""
    from ray_tpu.ops.attention import decode_write, decode_write_uses_pallas
    layer, arrays = cache
    if len(arrays) == 1:
        from ray_tpu.ops.attention import (latent_decode_uses_pallas,
                                           latent_decode_write)
        pool, = arrays
        if not latent_decode_uses_pallas(pool.shape[-2], pool.shape[-1],
                                         pool.dtype):
            return append(write_decode, cache, k, v, page_table, lengths)
        return layer, (latent_decode_write(
            pool, jnp.concatenate([k, v], -1), lengths, page_table, layer,
            skip_page=GARBAGE_PAGE),)
    if not decode_write_uses_pallas(arrays[0].shape[-2],
                                    arrays[0].shape[-1], arrays[0].dtype):
        return append(write_decode, cache, k, v, page_table, lengths)
    scales = ()
    if len(arrays) == 4:
        (k, ks), (v, vs) = _quantize_rows(k), _quantize_rows(v)
        scales = tuple(write_decode(a, r, layer, page_table, lengths)
                       for a, r in zip(arrays[2:], (ks, vs)))
    return layer, tuple(decode_write(
        arrays[0], arrays[1], k, v, lengths, page_table, layer,
        skip_page=GARBAGE_PAGE)) + scales


def attend(q, cache, page_table, lengths, *, scale=None, value_dim=None):
    """One query row per slot, ``q`` [B, H, D], over the first
    ``lengths`` [B] positions of each slot's pages (``page_table``
    [B, max_pages]) in one layer of the cache -> [B, H, D].  The pool
    is read where it lies: ``ops/attention.py:decode_attention`` gets
    the whole stacked arrays, the layer and the table (an int8 cache's
    scales with them), and no context is gathered.  Rows of no
    sequence come back as zeros.

    Over a latent cache ``q`` is the absorbed query ``[B, H, rank +
    rope]`` (it meets a row as it is stored), ``scale`` the softmax
    scale of the unabsorbed product and ``value_dim`` the row's latent
    part (``KVCache.latent[0]``): the values are the rows' first
    ``value_dim`` entries, and what comes back is ``[B, H, value_dim]``,
    still to be taken through the value half of the projection
    (``ops/attention.py:latent_decode_attention``)."""
    from ray_tpu.ops.attention import decode_attention
    layer, arrays = cache
    # a row whose table starts at the garbage page is no sequence (a
    # free slot, or a held one done but for delivery): nothing of the
    # pool is read for it
    lengths = jnp.where(page_table[:, 0] == GARBAGE_PAGE, 0, lengths)
    if len(arrays) == 1:
        from ray_tpu.ops.attention import latent_decode_attention
        return latent_decode_attention(q, arrays[0], lengths, page_table,
                                       layer, scale=scale,
                                       value_dim=value_dim)
    k, v, *scales = arrays
    return decode_attention(q, k, v, lengths, page_table, layer,
                            **dict(zip(_NAMES[2:], scales)))


def context_dense(cache, page_table, dtype, *, value_dim=None):
    """Gather one layer's pages for ``page_table`` [B, max_pages] ->
    ``(K, V)``, each ``[B, max_pages * page, H, D]``: a model-dtype
    cache's as stored, an int8 cache's dequantised to ``dtype``.  What
    a cached-suffix prefill attends over (one slot's row);
    a decode reads the pool in place (:func:`attend`).  A latent
    cache's: the rows' two parts, ``([B, C, value_dim], [B, C, rope])``,
    for the caller to project K and V from."""
    layer, arrays = cache
    if len(arrays) == 1:
        rows = arrays[0][layer, page_table]       # [B, max_pages, R, page]
        B, max_pages, R, page_size = rows.shape
        rows = jnp.moveaxis(rows, -1, 2).reshape(B, max_pages * page_size,
                                                 R)
        return rows[..., :value_dim], rows[..., value_dim:]
    k, v, *scales = (a[layer, page_table] for a in arrays)
    if scales:
        k, v = ((a.astype(jnp.float32) * s[:, :, :, None]).astype(dtype)
                for a, s in zip((k, v), scales))
    B, max_pages, H, D, page_size = k.shape
    return tuple(jnp.moveaxis(a, -1, 2).reshape(
        B, max_pages * page_size, H, D) for a in (k, v))


def pages_needed(tokens: int, page_size: int) -> int:
    return -(-tokens // page_size)
