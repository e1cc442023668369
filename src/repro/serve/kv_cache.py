"""Slot-based paged KV-cache pool (vLLM-style, pure JAX).

Physical storage is one tensor per K/V of shape

    (n_layers, n_pages, n_kv_heads, page_size, head_dim)

head-major within a page, so the paged-attention kernels read one head's
``(page_size, head_dim)`` slice of a page as one whole tile (the TPU
lowering's (8, 128) block rule; see ``kernels/paged_attention/kernel.py``).

and each admitted sequence owns a *slot*: a row of a block table mapping
logical page index -> physical page.  Pages are claimed lazily as the
sequence grows (``extend``) and returned on ``release``, so the pool can
overcommit: ``n_slots * max_pages_per_seq`` may exceed ``n_pages``.  The
engine resolves page exhaustion by evicting a victim sequence.

Physical page 0 is reserved as a scratch page: padded batch lanes and
padded prefill tokens scatter their (ignored) writes there, which keeps
every device op shape-static — one compile for gather, one for scatter.

Keys are stored post-RoPE, matching ``models.layers.cache_store``.

Pages may be stored int8 (``dtype=jnp.int8``): values are quantized
per-(token, head) on scatter (symmetric, scale = max|x|/127, matching
``models.layers._quantize_kv``) with fp32 scales in parallel
``(L, P, KV, ps)`` tensors.  ``gather`` dequantizes; the paged-attention
kernel reads the int8 pages + scales directly (1 byte/elem of KV traffic).

Prefix caching (``prefix_cache=True``): every physical page carries a
refcount, and a hash trie over FULL pages of prompt tokens maps token
blocks to pages already holding their K/V.  ``admit(tokens=...)`` walks
the trie and maps matched pages into the new slot (refcount + 1) instead
of claiming fresh ones, so identical prompt prefixes (system prompts,
few-shot headers) are never recomputed; the engine starts prefill at
``length(slot)``.  Shared pages are immutable: any write resolving into a
page with refcount > 1 copies it first (copy-on-write), and a full-prefix
hit maps a private copy of its last page at admission so the engine can
recompute the final prompt token (its logits seed generation) in place.
The trie holds its own refcount on cached pages, so they survive the
owner's release and are reclaimed LRU-first only under page pressure
(DESIGN.md §9).
"""
from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels.kv_write import write_pages
from repro.serve.faults import NO_FAULTS

__all__ = ["PagedKVPool", "pages_needed", "quantize_kv_int8", "write_kv"]


def pages_needed(n_tokens: int, page_size: int) -> int:
    return -(-n_tokens // page_size)


def page_bucket(n_pages: int, cap: int) -> int:
    """Round a page count up to a power of two, clamped to ``cap``.

    The paged decode dispatch is shape-static per block-table width; both
    the engine and the decode micro-benchmark bucket through here so the
    benchmark always measures the dispatch shape production uses.
    """
    b = 1
    while b < max(1, n_pages):
        b *= 2
    return min(b, cap)


def write_kv(k, v, k_scale, v_scale, pages, offs, k_new, v_new,
             interpret=False):
    """Write new K/V (L, *idx, KV, hd) into pool arrays in place; int8
    pools (``k_scale`` not None) quantize per-(token, head) on the way in.
    ``interpret`` runs the Pallas write kernels off-TPU (tests).  Returns
    the updated ``(k, v, k_scale, v_scale)``."""
    if k_scale is None:
        k, v = write_pages((k, v), pages, offs, (k_new, v_new),
                           interpret=interpret)
        return k, v, None, None
    kq, ks = quantize_kv_int8(k_new)
    vq, vs = quantize_kv_int8(v_new)
    return write_pages((k, v, k_scale, v_scale), pages, offs,
                       (kq, vq, ks, vs), interpret=interpret)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write(kv: tuple, pages: jax.Array, offs: jax.Array, k_new: jax.Array,
           v_new: jax.Array) -> tuple:
    """Host-side write of (L, T, KV, hd) K/V at (T,) addresses."""
    return write_kv(*kv, pages, offs, k_new, v_new)


def quantize_kv_int8(vals: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(token, head) symmetric int8 for (..., hd) values.

    Shared by the pool's scatter and the adapter's fused paged-decode step,
    so both write bit-identical pages; delegates to the dense cache path's
    quantizer so the two KV representations can never drift apart.
    """
    from repro.models.layers import _quantize_kv

    return _quantize_kv(vals)


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(phys: jax.Array, src: jax.Array, dst: jax.Array) -> jax.Array:
    """Copy one physical page across all layers: phys (L, P, ...)."""
    return phys.at[:, dst].set(phys[:, src])


def _token_major(g: jax.Array) -> jax.Array:
    """(L, B, Pmax, KV, ps, hd) -> (L, B, Pmax*ps, KV, hd)."""
    L, B, _, KV, _, hd = g.shape
    return g.transpose(0, 1, 2, 4, 3, 5).reshape(L, B, -1, KV, hd)


@jax.jit
def _gather(phys: jax.Array, block_tables: jax.Array) -> jax.Array:
    """phys (L, P, KV, ps, hd), block_tables (B, Pmax) ->
    (L, B, Pmax*ps, KV, hd) contiguous per-sequence windows."""
    return _token_major(phys[:, block_tables])


@functools.partial(jax.jit, static_argnames=("dtype",))
def _gather_q(phys: jax.Array, scales: jax.Array, block_tables: jax.Array,
              dtype) -> jax.Array:
    """int8 variant of :func:`_gather`: dequantize to ``dtype``."""
    g = phys[:, block_tables].astype(jnp.float32)
    s = scales[:, block_tables]
    return _token_major((g * s[..., None]).astype(dtype))


@dataclasses.dataclass
class _Slot:
    pages: list  # physical page ids, logical order
    length: int  # valid tokens written


@dataclasses.dataclass
class _PrefixNode:
    """One full page of cached prompt tokens in the prefix trie."""

    key: tuple  # (parent node id, token-block bytes) — the trie dict key
    page: int  # physical page holding this block's K/V
    parent: int  # parent node id (0 = root)
    children: set = dataclasses.field(default_factory=set)  # child node ids


class PagedKVPool:
    """Page accounting (host) + paged K/V storage (device).

    ``admit(n_tokens)`` -> slot id or None (not enough free pages/slots);
    ``extend(slot, new_len)`` -> bool (claims pages to cover ``new_len``);
    ``release(slot)`` returns all pages.  ``gather``/``write`` move data.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        *,
        n_pages: int,
        page_size: int,
        n_slots: int,
        max_pages_per_seq: int,
        dtype=None,
        prefix_cache: bool = False,
    ):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved scratch)")
        self.cfg = cfg
        self.page_size = page_size
        self.n_pages = n_pages
        self.n_slots = n_slots
        self.max_pages_per_seq = max_pages_per_seq
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.dtype)
        # fp dtype handed out by gather (and used for dequantized int8 reads)
        self._fp_dtype = jnp.dtype(cfg.dtype) if dt == jnp.int8 else dt
        shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
        self.k = jnp.zeros(shape, dt)
        self.v = jnp.zeros(shape, dt)
        if dt == jnp.int8:
            self.k_scale = jnp.zeros(shape[:-1], jnp.float32)
            self.v_scale = jnp.zeros(shape[:-1], jnp.float32)
        else:
            self.k_scale = self.v_scale = None
        self._free_pages = list(range(n_pages - 1, 0, -1))  # pop() -> low ids
        self._free_slots = list(range(n_slots - 1, -1, -1))
        self._slots: dict[int, _Slot] = {}
        self.peak_pages_in_use = 0
        # ---- prefix cache state (inert when prefix_cache is False) ----
        self.prefix_cache = bool(prefix_cache)
        self._page_ref = np.zeros(n_pages, np.int32)  # 0 = free/scratch
        self._trie: OrderedDict[tuple, int] = OrderedDict()  # key -> node id
        self._nodes: dict[int, _PrefixNode] = {
            0: _PrefixNode(key=(), page=0, parent=0)  # root (no page)
        }
        self._next_node = 1
        self.cow_copies = 0  # pages copied before a write (COW)
        self.prefix_hit_pages = 0  # pages mapped from the trie at admit
        # fault-injection hooks (serve/faults.py); the engine points this
        # at its plan — the inert default iterates an empty rule list
        self.faults = NO_FAULTS
        # device write of new K/V at host-resolved addresses, the pool
        # donated; an adapter's make_pool swaps in its own jitted
        # ``_write_kv`` (under shard_map for a pool sharded over KV heads:
        # a Pallas call cannot be partitioned by GSPMD)
        self.writer = _write

    # ---- accounting -----------------------------------------------------

    @property
    def pages_in_use(self) -> int:
        return (self.n_pages - 1) - len(self._free_pages)

    @property
    def occupancy(self) -> float:
        return self.pages_in_use / (self.n_pages - 1)

    def seq_capacity_tokens(self) -> int:
        return self.max_pages_per_seq * self.page_size

    def fits(self, n_tokens: int) -> bool:
        """Whether a sequence of n_tokens can EVER be resident."""
        return (
            n_tokens <= self.seq_capacity_tokens()
            and pages_needed(n_tokens, self.page_size) <= self.n_pages - 1
        )

    def _claim(self) -> int:
        page = self._free_pages.pop()
        self._page_ref[page] = 1
        return page

    def _decref(self, page: int) -> None:
        self._page_ref[page] -= 1
        if self._page_ref[page] == 0:
            self._free_pages.append(page)

    def _available(self, need: int) -> bool:
        """Whether ``need`` pages can be produced, reclaiming cache-only
        pages (LRU-first) if the free list alone cannot cover it."""
        if need <= len(self._free_pages):
            return True
        return self._reclaim(need - len(self._free_pages))

    def admit(self, n_tokens: int, tokens=None) -> Optional[int]:
        """Claim a slot + pages for a sequence of ``n_tokens``.

        With the prefix cache enabled and ``tokens`` (the request's prompt
        prefix, int32) provided, full leading pages found in the trie are
        mapped shared (refcount + 1) instead of claimed, and the slot's
        ``length`` starts at the cached token count — the caller resumes
        prefill there.  A hit covering the WHOLE sequence maps a private
        copy of its last page and caps ``length`` at ``n_tokens - 1``: the
        engine must still compute (and rewrite, in place of the copy) the
        final token, whose logits seed generation.
        """
        need_total = max(1, pages_needed(n_tokens, self.page_size))
        if not self._free_slots or need_total > self.max_pages_per_seq:
            return None
        if self.faults.rules and self.faults.fire("pool_exhausted"):
            return None  # injected transient exhaustion (admission defers)
        shared: list[int] = []
        if self.prefix_cache and tokens is not None:
            shared = [
                self._nodes[nid].page
                for nid in self._prefix_lookup(np.asarray(tokens, np.int32))
            ]
            shared = shared[:need_total]
        full_hit = len(shared) * self.page_size >= n_tokens
        fresh = need_total - len(shared) + (1 if full_hit else 0)
        pages = []
        for pg in shared:  # pin BEFORE any reclaim can free cache-only pages
            self._page_ref[pg] += 1
            pages.append(pg)
        if not self._available(fresh):
            for pg in shared:
                self._decref(pg)  # trie still holds one ref -> never frees
            return None
        slot = self._free_slots.pop()
        if full_hit:
            # copy-on-admit: the engine will rewrite this page's final
            # token, and shared pages are immutable
            last = pages.pop()
            pages.append(self._copy_into_fresh(last))
            self._page_ref[last] -= 1
        while len(pages) < need_total:
            pages.append(self._claim())
        cached_len = min(len(shared) * self.page_size, n_tokens - 1)
        self._slots[slot] = _Slot(pages=pages, length=cached_len)
        self.prefix_hit_pages += len(shared)
        self.peak_pages_in_use = max(self.peak_pages_in_use, self.pages_in_use)
        return slot

    def extend(self, slot: int, new_len: int) -> bool:
        """Claim pages so the slot can hold ``new_len`` tokens."""
        st = self._slots[slot]
        need = pages_needed(new_len, self.page_size) - len(st.pages)
        if need <= 0:
            return True
        if self.faults.rules and self.faults.fire("pool_exhausted"):
            return False  # injected transient exhaustion (evict/requeue path)
        if len(st.pages) + need > self.max_pages_per_seq:
            return False
        if not self._available(need):
            return False
        for _ in range(need):
            st.pages.append(self._claim())
        self.peak_pages_in_use = max(self.peak_pages_in_use, self.pages_in_use)
        return True

    def release(self, slot: int) -> None:
        st = self._slots.pop(slot)
        for page in st.pages:
            self._decref(page)
        self._free_slots.append(slot)

    def truncate(self, slot: int, new_len: int) -> int:
        """Roll back a slot to ``new_len`` valid tokens, un-writing the
        tail: wholly-invalid trailing pages are unmapped (refcount
        decrement — a page the prefix trie or a COW sibling still holds
        survives for them) and the slot's valid length drops.  Page
        CONTENTS are never mutated here: a partially-valid tail page keeps
        its stale K/V above ``new_len``, which every reader masks via
        ``ctx_len`` and the next write overwrites.  Writes into shared
        pages were already copy-on-write resolved by the address paths, so
        rollback can only ever drop this slot's private view, never damage
        a cached page.  Speculative decode uses this to un-write rejected
        draft tokens.  Returns the number of pages unmapped."""
        st = self._slots[slot]
        if new_len < 0 or new_len > st.length:
            raise ValueError(
                f"truncate to {new_len} outside [0, {st.length}] "
                f"(slot {slot})"
            )
        keep = max(1, pages_needed(new_len, self.page_size))
        dropped = 0
        while len(st.pages) > keep:
            self._decref(st.pages.pop())
            dropped += 1
        st.length = new_len
        return dropped

    def length(self, slot: int) -> int:
        return self._slots[slot].length

    # ---- prefix cache ---------------------------------------------------

    def _page_key(self, parent: int, tokens: np.ndarray, i: int) -> tuple:
        ps = self.page_size
        return (parent, tokens[i * ps : (i + 1) * ps].tobytes())

    def cached_prefix_pages(self, tokens) -> int:
        """How many full leading pages of ``tokens`` the trie holds right
        now — the pages an admission would map shared instead of claiming.
        Admission-capacity estimates use this so a cached prompt is not
        rejected for pages it will never claim.  (Walking the trie
        refreshes the chain's LRU position, which is what we want: a
        prompt being sized up for admission is about to be served.)"""
        if not self.prefix_cache:
            return 0
        return len(self._prefix_lookup(np.asarray(tokens, np.int32)))

    def _prefix_lookup(self, tokens: np.ndarray) -> list[int]:
        """Longest chain of cached full pages matching ``tokens``; returns
        trie node ids (root excluded) and refreshes their LRU position."""
        out: list[int] = []
        parent = 0
        for i in range(len(tokens) // self.page_size):
            key = self._page_key(parent, tokens, i)
            nid = self._trie.get(key)
            if nid is None:
                break
            self._trie.move_to_end(key)
            out.append(nid)
            parent = nid
        return out

    def register_prefix(self, slot: int, tokens) -> None:
        """Insert the slot's fully-written leading pages of ``tokens`` into
        the trie.  Each inserted node takes its own refcount on the page,
        so cached K/V outlives the owning sequence; re-registering an
        already-cached chain is a cheap no-op walk."""
        if not self.prefix_cache:
            return
        tokens = np.asarray(tokens, np.int32)
        st = self._slots[slot]
        parent = 0
        for i in range(min(len(tokens), st.length) // self.page_size):
            key = self._page_key(parent, tokens, i)
            nid = self._trie.get(key)
            if nid is None:
                page = st.pages[i]
                nid = self._next_node
                self._next_node += 1
                self._trie[key] = nid
                self._nodes[nid] = _PrefixNode(
                    key=key, page=page, parent=parent
                )
                self._nodes[parent].children.add(nid)
                self._page_ref[page] += 1
            parent = nid

    def _remove_node(self, nid: int) -> None:
        node = self._nodes.pop(nid)
        del self._trie[node.key]
        self._nodes[node.parent].children.discard(nid)
        self._decref(node.page)

    def _reclaim(self, need: int) -> bool:
        """Free ``need`` pages by dropping cache-only trie leaves —
        entries whose page no live slot maps (refcount 1) and that have no
        children — oldest (LRU) first.  Dropping a leaf may expose its
        parent; loop until satisfied or stuck."""
        if not self.prefix_cache or need <= 0:
            return need <= 0
        freed = 0
        progress = True
        while freed < need and progress:
            progress = False
            for key, nid in list(self._trie.items()):
                node = self._nodes[nid]
                if node.children or self._page_ref[node.page] != 1:
                    continue
                self._remove_node(nid)
                freed += 1
                progress = True
                if freed >= need:
                    break
        return freed >= need

    def _copy_into_fresh(self, src: int) -> int:
        """Claim a free page and device-copy ``src`` into it (all layers)."""
        dst = self._claim()
        s, d = jnp.int32(src), jnp.int32(dst)
        self.k = _copy_page(self.k, s, d)
        self.v = _copy_page(self.v, s, d)
        if self.is_int8:
            self.k_scale = _copy_page(self.k_scale, s, d)
            self.v_scale = _copy_page(self.v_scale, s, d)
        self.cow_copies += 1
        return dst

    def _ensure_private(self, slot: int, logical_page: int) -> int:
        """Copy-on-write guard: writes never mutate a shared page.  If the
        slot's logical page is mapped by anyone else (refcount > 1), swap
        in a private copy first."""
        st = self._slots[slot]
        page = st.pages[logical_page]
        if self._page_ref[page] <= 1:
            return page
        if not self._available(1):
            raise RuntimeError(
                "copy-on-write needs a free page but the pool is exhausted "
                "(evict a sequence or grow n_pages)"
            )
        dst = self._copy_into_fresh(page)
        st.pages[logical_page] = dst
        self._page_ref[page] -= 1
        return dst

    # ---- gauges ---------------------------------------------------------

    @property
    def shared_pages(self) -> int:
        """Physical pages currently mapped by more than one owner."""
        return int(np.sum(self._page_ref > 1))

    @property
    def cached_pages(self) -> int:
        """Full prompt pages resident in the prefix trie."""
        return len(self._trie)

    @property
    def max_page_ref(self) -> int:
        return int(self._page_ref.max())

    @property
    def is_int8(self) -> bool:
        return self.k_scale is not None

    def metrics_gauges(self) -> dict:
        """Name -> zero-arg callback for every pool gauge, in the form
        :class:`repro.serve.telemetry.MetricsRegistry` registers (callback
        gauges are evaluated at snapshot time, so the registry always
        reports live pool state without the pool knowing about telemetry).
        The engine merges these into its registry; ``summary()`` and the
        periodic ``--metrics-every`` snapshots read them from there."""
        return {
            "pages_in_use": lambda: self.pages_in_use,
            "peak_pages_in_use": lambda: self.peak_pages_in_use,
            "occupancy": lambda: self.occupancy,
            "peak_occupancy": (
                lambda: self.peak_pages_in_use / max(1, self.n_pages - 1)
            ),
            "shared_pages": lambda: self.shared_pages,
            "cached_pages": lambda: self.cached_pages,
            "max_page_ref": lambda: self.max_page_ref,
            "cow_copies": lambda: self.cow_copies,
            "prefix_hit_pages": lambda: self.prefix_hit_pages,
        }

    @property
    def arrays(self) -> tuple:
        """``(k, v, k_scale, v_scale)`` — the device state a fused serving
        program takes (donated) and returns updated; scales are None for
        fp pages."""
        return self.k, self.v, self.k_scale, self.v_scale

    @arrays.setter
    def arrays(self, kv: tuple) -> None:
        self.k, self.v, self.k_scale, self.v_scale = kv

    def _storage(self) -> list:
        arrs = [self.k, self.v]
        if self.is_int8:
            arrs += [self.k_scale, self.v_scale]
        return arrs

    def total_bytes(self) -> int:
        """Logical bytes of KV page storage (all shards together)."""
        return sum(a.nbytes for a in self._storage())

    def device_bytes(self) -> int:
        """Bytes of KV page storage resident on ONE device.  For a pool
        sharded over kv heads this is ~1/mp of :meth:`total_bytes`."""
        return sum(
            a.addressable_shards[0].data.nbytes for a in self._storage()
        )

    # ---- device ops -----------------------------------------------------

    def block_table(self, slot_ids: list[Optional[int]]) -> np.ndarray:
        """(B, max_pages_per_seq) int32; missing slots/pages -> scratch 0."""
        bt = np.zeros((len(slot_ids), self.max_pages_per_seq), np.int32)
        for b, s in enumerate(slot_ids):
            if s is None:
                continue
            pages = self._slots[s].pages
            bt[b, : len(pages)] = pages
        return bt

    def gather(self, slot_ids: list[Optional[int]]):
        """-> (k, v) each (L, B, max_pages_per_seq*page_size, KV, hd).

        int8 pools dequantize on the way out, so callers always see fp.
        """
        bt = jnp.asarray(self.block_table(slot_ids))
        if self.is_int8:
            return (
                _gather_q(self.k, self.k_scale, bt, self._fp_dtype),
                _gather_q(self.v, self.v_scale, bt, self._fp_dtype),
            )
        return _gather(self.k, bt), _gather(self.v, bt)

    def _addr(self, slot: Optional[int], pos: int) -> tuple[int, int]:
        if slot is None:
            return 0, 0  # scratch
        st = self._slots[slot]
        page = st.pages[pos // self.page_size]
        return page, pos % self.page_size

    def addresses(
        self, slot_ids: list[Optional[int]], positions: list[int]
    ) -> tuple[np.ndarray, np.ndarray]:
        """Physical (pages, offsets) int32 for one token per lane; ``None``
        lanes resolve to the scratch page.  Feeds the fused decode dispatch
        (adapter scatters in place) — pair with :meth:`note_written`.
        Write-intent: shared target pages are copy-on-write resolved."""
        pages = np.zeros(len(slot_ids), np.int32)
        offs = np.zeros(len(slot_ids), np.int32)
        for b, (s, p) in enumerate(zip(slot_ids, positions)):
            if s is not None:
                self._ensure_private(s, p // self.page_size)
            pages[b], offs[b] = self._addr(s, p)
        return pages, offs

    def span_addresses(
        self,
        slot_ids: list[Optional[int]],
        starts: list[int],
        n_valids: list[int],
        width: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Physical (pages, offsets), each (B, width) int32, for one prefill
        chunk per lane: lane b's tokens land at absolute positions
        ``starts[b] .. starts[b] + n_valids[b] - 1``; the padded tail (and
        ``None`` lanes) resolves to the scratch page.  Feeds the fused
        batched-prefill dispatch — pair with :meth:`note_span_written`.
        Write-intent: shared target pages are copy-on-write resolved."""
        B = len(slot_ids)
        pages = np.zeros((B, width), np.int32)
        offs = np.zeros((B, width), np.int32)
        for b, (s, start, n) in enumerate(zip(slot_ids, starts, n_valids)):
            if s is None or n <= 0:
                continue
            for lp in range(
                start // self.page_size, (start + n - 1) // self.page_size + 1
            ):
                self._ensure_private(s, lp)
            for t in range(n):
                pages[b, t], offs[b, t] = self._addr(s, start + t)
        return pages, offs

    def note_span_written(
        self, slot_ids: list[Optional[int]], starts: list[int],
        n_valids: list[int],
    ) -> None:
        """Host-side length accounting for prefill chunks a fused device
        step already scattered into the pool."""
        for s, start, n in zip(slot_ids, starts, n_valids):
            if s is not None and n > 0:
                st = self._slots[s]
                st.length = max(st.length, start + n)

    def note_written(
        self, slot_ids: list[Optional[int]], positions: list[int]
    ) -> None:
        """Host-side length accounting for tokens a fused device step
        already scattered into the pool."""
        for s, p in zip(slot_ids, positions):
            if s is not None:
                self._slots[s].length = max(self._slots[s].length, p + 1)

    def _scatter_kv(self, pages: np.ndarray, offs: np.ndarray,
                    k_new: jax.Array, v_new: jax.Array) -> None:
        self.arrays = self.writer(self.arrays, jnp.asarray(pages),
                                  jnp.asarray(offs), k_new, v_new)

    def write(
        self,
        slot_ids: list[Optional[int]],
        positions: list[int],
        k_new: jax.Array,
        v_new: jax.Array,
    ) -> None:
        """Scatter one token per lane: k_new/v_new (L, B, KV, hd).

        Lane b writes at absolute position ``positions[b]`` of slot
        ``slot_ids[b]``; ``None`` lanes go to the scratch page.  Also
        advances each written slot's valid length to ``positions[b]+1``.
        """
        pages, offs = self.addresses(slot_ids, positions)
        self._scatter_kv(pages, offs, k_new, v_new)
        self.note_written(slot_ids, positions)

    def write_span(
        self, slot: int, start: int, n_valid: int, k_new: jax.Array,
        v_new: jax.Array,
    ) -> None:
        """Scatter a prefill chunk: k_new/v_new (L, T, KV, hd); the first
        ``n_valid`` tokens land at positions start..start+n_valid-1, the
        padded tail goes to the scratch page.  Shared target pages are
        copy-on-write resolved."""
        T = k_new.shape[1]
        pages, offs = self.span_addresses([slot], [start], [n_valid], T)
        self._scatter_kv(pages[0], offs[0], k_new, v_new)
        self._slots[slot].length = max(self._slots[slot].length, start + n_valid)
