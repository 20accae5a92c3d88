"""Paged KV cache: fixed-size pages + per-slot page tables (graftpage).

The serving engine's ONE cache pool. A dense ``[layers, max_slots,
s_max, heads, head_dim]`` block would reserve ``s_max`` columns for a
16-token request; here K/V live in **pages**, ``[layers, num_pages,
page_size, heads * head_dim]`` arrays, and each slot maps its logical
columns onto pages through an ``[max_slots, pages_per_slot]`` int32
page table. A request holding ``L + g`` tokens pins ``ceil((L + g) /
page_size)`` pages — so ``num_pages`` (the real HBM commitment) can be
sized to the *expected* length distribution while ``max_slots``
(concurrency) grows past the dense worst case: the capacity multiplier
graftmeter's ``per_slot_kv_bytes`` ledger exists to measure.

The pool also owns the per-slot scalars (position counter, last
sampled token, active flag, remaining decode budget, stop id — the
last two arm the fused horizon's on-device finish gating); the
engine's jitted decode step runs over ALL slots every step with an
active-mask — occupancy changes the mask's *values*, never any shape.

Slot invariants (the correctness contract the engine's
equivalence-with-``generate()`` pin rests on):

- an ACTIVE slot holding a request with prompt length ``L`` that has
  emitted ``g`` tokens has valid cache columns ``[0, L + g - 1)`` and
  ``position == L + g - 1`` (the column its pending last token's K/V
  will be written to by the next decode step);
- attention in the decode step masks columns ``> position``, so stale
  columns from a previous tenant are never read before the column is
  overwritten: the step at position ``p`` writes column ``p`` *before*
  attending to ``[0, p]``, exactly like ``inference.generate``'s
  ``_block_decode``;
- inactive rows keep a frozen position (the masked step re-writes the
  same column each step), so no index ever grows past ``s_max``.

The pool mirrors each ACTIVE slot's position counter on the host
(``note_insert``/``note_advance_slots``, read via ``max_active_pos``):
the engine's length-bucketed decode picks its attention window from
the longest *active* sequence BEFORE launching the step, and a device
read-back of the position vector there would serialize every step on a
host sync. The mirror applies the same two updates the jitted step
applies (set on insert, + realized steps per drained horizon), and
inactive slots are excluded, so a long-finished tenant never inflates
the window.

The two pools are shaped by the model family's ``cache_rows``
(``inference.generate.serving_family``), by ONE rule for every family:
``[layers, num_pages, page_size, prod(row)]`` — a token's row flat in
the lanes. K and V rows of ``(heads, head_dim)`` for GPT (head ``h`` in
lanes ``h * head_dim .. (h + 1) * head_dim``); for a latent-attention
family one row all heads share, and a zero-width placeholder.

**Two kinds of layer** (a family whose ``cache_rows`` say so,
``inference.generate.cache_pools``: sliding-window layers beside full
ones). Each pool then holds ITS layers only, at its own row (the two
kinds may keep rows of different widths: MiMo-V2's full layers 1,280
values a token, its window layers 2,560). The pool of the layers
that attend the whole context is the paged pool above, under the page
table, the free list and the refcounts. The pool of the layers that
attend a window is a **ring**: ``ring_pages = ceil(window / page_size)
+ 1`` pages a slot (``[layers, max_slots * ring_pages, page_size,
row]``), token ``t`` of slot ``s`` at page ``s * ring_pages + (t //
page_size) % ring_pages`` — at most ``ring_pages`` pages hold a column
in reach, and the page that falls out of it is the one written over.
Why a ring and not a second class in the allocator: its page ids follow
from the slot and the position, so the decode programs need no second
table operand and an admission no second reservation; its bytes are
the window's whatever the context (the bound this kind of layer
exists for); and nothing of it can leak (``release`` has nothing to
return that the next tenant's splice does not overwrite). What it
gives up: a short request still owns a whole ring (window-bounded, so
at most ``ring_pages`` pages), and rings cannot be shared by a prefix
cache (refused for such a family). Admission reasons over both kinds:
the paged pool through ``free_pages``, the rings through the slot
itself (a free slot IS a free ring). ``pages_in_use`` is then a share
of what both kinds can hold, by bytes (see there).

Layout note: a page is ``page_size`` whole rows, so it is lane-dense
(``heads * head_dim`` is a multiple of 128 for the registry's serving
sizes; a 64-wide minor dimension would be padded to 128 lanes and
copied), one contiguous DMA, and the layer is the pool's LEADING axis:
the decode program carries the whole pool through its layers, each
writing its new rows at ``[layer, page, offset]`` and its Pallas kernel
reading ``(1, 1, page_size, heads * head_dim)`` blocks of it in place
(:mod:`...ops.pallas.decode_attention`) — so the donated pool is never
copied.

Allocation is **host-mirrored**: the free list, refcounts and the page
table live in host numpy; alloc/free never touch the device. The
device copy of the table is uploaded lazily — only when the mirror
changed since the last dispatch (an admission/release boundary where
the host already synchronizes), so the armed-sentinel steady state
stays at 0 transfers. All allocation happens PRE-jit (graftfault-safe:
never on donated buffers mid-flight).

Page 0 is the **scratch page**, never allocated: released slots' table
rows are reset to 0, so a frozen (inactive) row's idempotent re-write
of its pinned column lands in scratch instead of poisoning a page that
has since been re-allocated to another tenant. Garbage in scratch is
never read — the decode attention masks columns beyond each slot's
position, and no live table entry points at page 0.

**Shared-prefix reuse** (:class:`PrefixCache`): pages are refcounted,
so N requests with a common page-aligned prompt prefix can all map
their leading table entries at ONE set of pages, prefilled once. The
pages are referenced read-only by construction — a joiner's first
divergent write (its first decode column, ``L``) lands either in a
fresh page or in a **copy-on-write fork** of the prefix's partial last
page; shared pages are only ever written by the request that first
filled them, before they were shared.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..inference.generate import cache_pools
from ..ops.kv_quant import KV_DTYPES, QuantizedKV
from ..runtime import hbm, life
from ..runtime import scope as graftscope


# where the ``model`` mesh axis shards a page pool ``[L, P, ps, H *
# Dh]`` (and an int8 pool's ``[L, P, ps, H]`` scales): the last axis,
# in contiguous head groups
PAGE_SPEC = P(None, None, None, "model")


# the counters of the attention families' pools, which metrics and
# traces read by these names; any other pool a family declares holds
# state that is not a key or a value, and is counted as such
_KV_LIVE_COUNTERS = {"full": "kv_{}_live_full", "sliding": "kv_{}_live_window"}


def live_counter(pool_name: str) -> str:
    """The name pattern (``{}``: ``pages`` or ``bytes``) of a declared
    pool's live counters: ``kv_{}_live_full`` / ``kv_{}_live_window``
    for the attention pools, ``state_{}_live_<name>`` for any other
    (the LFM2 family's ``conv`` ring: ``state_bytes_live_conv``)."""
    return _KV_LIVE_COUNTERS.get(pool_name, f"state_{{}}_live_{pool_name}")


def _ring_window(model) -> Optional[int]:
    """The columns a slot's ring has to hold: the widest window among
    the family's cache rows that hold a window only; None where every
    row holds the whole context (no ring)."""
    windows = [columns for *_, columns in cache_pools(model)
               if columns is not None]
    return max(windows) if windows else None


class PagePoolExhausted(RuntimeError):
    """Raised when an allocation asks for more free pages than the
    pool holds. The ENGINE never lets this escape admission for a
    request that could eventually fit: it holds the FIFO head queued
    (backpressure — running requests free pages as they finish, and
    the prefix cache sheds LRU entries first) and only fails a request
    named with this error when nothing in flight could ever free
    enough pages for it."""


class PagePool:
    """Paged KV storage + per-slot decode state for the serving engine.

    The engine's surface: ``k_pages``/``v_pages`` and the page table,
    ``positions``/``last_tokens``/``active``/``budgets``/``eos_ids``,
    ``acquire``/``release``, the host position mirror.

    Args:
      model: the model whose family (``inference.generate.
        serving_family``) shapes the caches: its ``cache_rows``.
      max_slots: concurrent requests decoded per step (the decode
        batch dimension: every step pays ``max_slots`` rows of compute
        regardless of occupancy — the static-shape trade).
      s_max: per-slot LOGICAL column capacity (admission bound).
      page_size: columns per page. Every request pins
        ``ceil(total_tokens / page_size)`` pages. On a real TPU keep
        it a multiple of 8 (the Pallas block's sublane tiling); CPU
        interpret mode takes any value >= 1.
      num_pages: total pages allocated, INCLUDING the reserved scratch
        page 0. Default: ``max_slots * pages_per_slot + 1`` — dense
        worst-case parity. The capacity win comes from passing LESS
        than worst case while raising ``max_slots``.
      mesh: optional ``Mesh`` with a ``model`` axis — pages are then
        resident head-sharded (``[L, P, ps, (H/tp) * Dh]`` per chip:
        contiguous head groups of the lanes).
      kv_dtype: ``"model"`` or ``"int8"`` (graftquant: pages become a
        :class:`...ops.kv_quant.QuantizedKV` pair — int8 data + a
        ``[L, P, ps, H]`` f32 scale sidecar beside the page table).
    """

    def __init__(self, model, max_slots: int, s_max: Optional[int] = None,
                 mesh: Optional[Mesh] = None, *, page_size: int,
                 num_pages: Optional[int] = None,
                 kv_dtype: str = "model"):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        s_max = int(s_max or model.max_seq_len)
        if not 2 <= s_max <= model.max_seq_len:
            raise ValueError(
                f"s_max must be in [2, max_seq_len={model.max_seq_len}], "
                f"got {s_max}")
        page_size = int(page_size)
        if page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {page_size}")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
        self.model = model
        self.max_slots = int(max_slots)
        self.s_max = s_max
        self.mesh = mesh
        self.kv_dtype = kv_dtype
        self.page_size = page_size
        self.pages_per_slot = -(-s_max // page_size)
        worst = self.max_slots * self.pages_per_slot + 1
        self.num_pages = int(num_pages) if num_pages is not None else worst
        if self.num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (scratch + 1), got "
                f"{self.num_pages}")
        # the family's two cache rows (K and V; the latent and the
        # position key; the full and the sliding layers' rows), each
        # one pool of pages: ``num_pages`` under the page table, or a
        # ring a slot where the row holds a window of columns only
        self.ring_window = _ring_window(model)
        self.ring_pages = (
            0 if self.ring_window is None
            else min(self.pages_per_slot,
                     -(-self.ring_window // page_size) + 1))
        self.ring_pages_overwritten = 0
        # bytes of one page of ONE layer, by kind (held as a ring or
        # not): what a layer's kernel copies a page
        self._layer_page_bytes = {
            columns is not None: page_size * int(np.prod(row, dtype=int))
            * jnp.dtype(dtype).itemsize
            for _, row, dtype, _, columns in cache_pools(model)}
        # the counters each pool's live pages and bytes go under
        self._live_names = {columns is not None: live_counter(name)
                            for name, _, _, _, columns in cache_pools(model)}
        self.k_pages, self.v_pages = (
            self._cache_sharded(self._empty_pages(
                row, layers, self.num_pages if columns is None
                else self.max_slots * self.ring_pages))
            for _, row, _, layers, columns in cache_pools(model))
        # per-slot decode state: next write column, pending token,
        # live?, and the on-device finish gates (remaining budget, stop
        # id) that freeze a finished row mid-scan. Mesh runs commit
        # these replicated from the START — the jitted step returns
        # them mesh-committed, and a first call with uncommitted arrays
        # would be a second compile signature
        self.positions = self._replicated(
            jnp.zeros((self.max_slots,), jnp.int32))
        self.last_tokens = self._replicated(
            jnp.zeros((self.max_slots,), jnp.int32))
        self.active = self._replicated(jnp.zeros((self.max_slots,), bool))
        self.budgets = self._replicated(
            jnp.zeros((self.max_slots,), jnp.int32))
        self.eos_ids = self._replicated(
            jnp.full((self.max_slots,), -1, jnp.int32))
        # host-mirrored page bookkeeping: table, free list, refcounts.
        # Page 0 is scratch (never allocated, permanently "referenced")
        self._table = np.zeros((self.max_slots, self.pages_per_slot),
                               np.int32)
        self._free: List[int] = list(range(1, self.num_pages))
        self._refs = np.zeros((self.num_pages,), np.int64)
        self._refs[0] = 1  # scratch: never freed
        self._table_dev = None  # uploaded lazily, see device_table()
        self._table_dirty = True
        # slot free list + host position mirror (module docstring)
        self._free_slots: List[int] = list(range(self.max_slots))
        self._positions_host: List[int] = [0] * self.max_slots
        self._active_host: List[bool] = [False] * self.max_slots
        # graftmeter: the pool's REAL HBM commitment (num_pages x
        # page_bytes — the number the dense pool's worst-case
        # per_slot_kv_bytes shrinks to) + live pages-in-use gauges.
        # Disarmed: one global read.
        if hbm.active_ledger() is not None:
            hbm.register("serving.kv_pages",
                         hbm.nbytes_of(self.k_pages)
                         + hbm.nbytes_of(self.v_pages),
                         category="kv_pages", slots=self.max_slots,
                         s_max=s_max, page_size=page_size,
                         num_pages=self.num_pages,
                         hbm_page_bytes=self.page_bytes)
            hbm.set_gauge("page_bytes", self.page_bytes)
            hbm.register("serving.slot_state",
                         sum(hbm.nbytes_of(a) for a in (
                             self.positions, self.last_tokens,
                             self.active, self.budgets, self.eos_ids))
                         + self._table.nbytes,
                         category="kv")
            self._note_pages_ledger()

    def _empty_pages(self, row, layers, num_pages):
        """Zeroed pages for one cache row in the pool's element
        layout: model dtype, or the graftquant ``(int8 data, f32
        scale)`` pair — one scale per trailing-dimension group, ``[L,
        P, ps, H]`` (scale = ones — untouched pages dequantize to the
        zeros dense pages hold)."""
        shape = self.page_shape(row, num_pages, self.page_size, layers)
        if self.kv_dtype == "int8":
            groups = int(np.prod(row[:-1], dtype=int))
            return QuantizedKV(
                jnp.zeros(shape, jnp.int8),
                jnp.ones(shape[:-1] + (groups,), jnp.float32))
        return jnp.zeros(shape, self.model.dtype)

    def _cache_sharded(self, c):
        if self.mesh is None:
            return c
        # heads are contiguous groups of the LAST axis — in both leaves
        # of a quantized pair (H * Dh lanes of data, H scales)
        return jax.device_put(
            c, NamedSharding(self.mesh, PAGE_SPEC))

    def _replicated(self, a):
        if self.mesh is None:
            return a
        return jax.device_put(a, NamedSharding(self.mesh, P()))

    @staticmethod
    def page_shape(row, num_pages: int, page_size: int, layers: int):
        """One pool's shape for a cache row, the same rule for every
        family: ``[L, P, ps, prod(row)]`` — a token's row flat in the
        lanes (a per-head row ``(H, Dh)``: the heads side by side; a
        row all heads share ``(R,)``: itself)."""
        return (layers, num_pages, int(page_size),
                int(np.prod(tuple(row), dtype=int)))

    # ---- capacity accounting (graftmeter) ------------------------------
    @staticmethod
    def page_kv_bytes(model, page_size: int,
                      kv_dtype: str = "model") -> int:
        """Bytes of ONE page of the page table over the cache rows it
        maps — the exact shape x dtype product ``__init__`` allocates
        per page (GPT: ``2 x layers x heads x page_size x head_dim x
        itemsize``; graftquant int8 charges 1 byte per element PLUS
        one f32 scale per trailing-dimension group), the planner's
        paged-mode unit (:func:`...analysis.meter.plan_capacity`),
        byte-exact in BOTH modes. A row held as a ring a slot is not
        under the table: :meth:`ring_page_kv_bytes`."""
        return PagePool._page_bytes(model, page_size, kv_dtype, ring=False)

    @staticmethod
    def ring_page_kv_bytes(model, page_size: int) -> int:
        """Bytes of ONE ring page over the rows held as a ring a slot
        (a family with sliding-window layers; 0 for every other)."""
        return PagePool._page_bytes(model, page_size, "model", ring=True)

    @staticmethod
    def _page_bytes(model, page_size, kv_dtype, ring: bool) -> int:
        total = 0
        for _, row, dtype, layers, columns in cache_pools(model):
            if (columns is not None) != ring:
                continue
            width = int(row[-1])
            if kv_dtype == "int8":
                group_bytes = width * 1 + 4  # int8 lanes + f32 scale
            else:
                group_bytes = width * jnp.dtype(dtype).itemsize
            total += (layers * int(np.prod(row[:-1], dtype=int))
                      * int(page_size) * group_bytes)
        return total

    @staticmethod
    def per_slot_kv_bytes(model, s_max: int,
                          kv_dtype: str = "model") -> int:
        """Cache bytes ONE slot reserves for ``s_max`` tokens (no page
        rounding: a page of ``s_max`` rows; a row held as a ring: of
        its window's columns where that is fewer) — the unit
        :func:`...analysis.meter.plan_capacity` inverts and
        :func:`...inference.generate.kv_cache_bytes` multiplies."""
        window = _ring_window(model)
        return (PagePool.page_kv_bytes(model, s_max, kv_dtype)
                + (0 if window is None else PagePool.ring_page_kv_bytes(
                    model, min(int(s_max), window))))

    @staticmethod
    def per_slot_state_bytes() -> int:
        """Per-slot scalar decode state: four int32 rows (position,
        last token, budget, eos id) + one bool (active)."""
        return 4 * 4 + 1

    @staticmethod
    def pages_for(total_tokens: int, page_size: int) -> int:
        """Pages a request holding ``total_tokens`` columns pins."""
        return -(-int(total_tokens) // int(page_size))

    @property
    def page_bytes(self) -> int:
        return self.page_kv_bytes(self.model, self.page_size,
                                  self.kv_dtype)

    @property
    def ring_page_bytes(self) -> int:
        return self.ring_page_kv_bytes(self.model, self.page_size)

    @property
    def per_slot_bytes(self) -> int:
        """WORST-CASE resident bytes one slot can pin
        (``pages_per_slot`` pages + scalar state) — the dense-parity
        upper bound. Actual residency is ``pages_in_use x
        page_bytes``; the gap between the two is the capacity win the
        ledger gauges record."""
        return (self.pages_per_slot * self.page_bytes
                + self.ring_pages * self.ring_page_bytes
                + self.per_slot_state_bytes())

    @property
    def hbm_bytes(self) -> int:
        """Total device bytes resident (host metadata only)."""
        return (hbm.nbytes_of(self.k_pages)
                + hbm.nbytes_of(self.v_pages)
                + sum(hbm.nbytes_of(a) for a in (
                    self.positions, self.last_tokens, self.active,
                    self.budgets, self.eos_ids))
                + int(self._table.nbytes))

    def _note_pages_ledger(self) -> None:
        """Refresh the live utilization gauges on the armed ledger
        (disarmed: one global read — callers gate, this re-checks for
        safety). Gauge-only: the pool's CAPACITY entry already counts
        these bytes resident; ``pages_in_use`` must never be summed a
        second time into ``hbm_total_bytes``."""
        if hbm.active_ledger() is None:
            return
        hbm.set_gauge("pages_in_use", self.pages_in_use)
        hbm.set_gauge("kv_pages_in_use_bytes", self.kv_bytes_held)

    # ---- page allocation (host-only) -----------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self):
        """Pages held, as a count of the ``num_pages - 1`` allocatable
        ones: ``pages_in_use / (num_pages - 1)`` is the share of the
        pool's allocatable BYTES that requests hold. One kind of layer:
        the pages off the free list, an integer. Two kinds: the bytes
        held in both (the paged pool's pages and the ring pages of
        every bound slot) over the bytes both can hold, scaled to the
        same count — a share by bytes, not by pages, since a page of
        the two pools spans different numbers of layers."""
        used = self.num_pages - 1 - len(self._free)
        if not self.ring_pages:
            return used
        return (self.kv_bytes_held / self.kv_bytes_allocatable
                * (self.num_pages - 1))

    def kv_usage(self) -> Dict[str, int]:
        """What requests hold now, in ONE pass over the host mirror.
        ``full``: pages of the page table off the free list (a
        request's whole context, reserved at admission). ``sliding``:
        ring pages of the bound slots — a slot whose request spans
        ``n`` columns holds ``min(ceil(n / page_size), ring_pages)`` of
        its ring, the window's worth at most whatever ``n`` is.
        ``bytes_held``: both, in bytes. ``bytes_undivided``: what ONE
        pool of every layer under the page table would hold for the
        same requests (every held page of the table across all the
        model's layers; with one kind of layer ``bytes_held``
        itself)."""
        full, sliding = self.num_pages - 1 - len(self._free), 0
        page, ring_page = self.page_bytes, 0
        if self.ring_pages:
            bound = np.count_nonzero(self._table, axis=1)
            sliding = int(np.minimum(bound, self.ring_pages).sum())
            ring_page = self.ring_page_bytes
        return {"full": full, "sliding": sliding,
                "bytes_held": full * page + sliding * ring_page,
                "bytes_undivided": full * (page + ring_page)}

    def pages_held(self) -> Dict[str, int]:
        """Pages held by kind (:meth:`kv_usage`)."""
        usage = self.kv_usage()
        return {"full": usage["full"], "sliding": usage["sliding"]}

    @property
    def kv_bytes_held(self) -> int:
        """Cache bytes requests hold now, over both kinds of pool."""
        return self.kv_usage()["bytes_held"]

    @property
    def kv_bytes_undivided(self) -> int:
        return self.kv_usage()["bytes_undivided"]

    @property
    def kv_bytes_allocatable(self) -> int:
        return ((self.num_pages - 1) * self.page_bytes
                + self.max_slots * self.ring_pages * self.ring_page_bytes)

    def alloc_pages(self, n: int) -> List[int]:
        """Claim ``n`` free pages (refcount 1 each; lowest-numbered
        first so tests can pin recycling). Raises
        :class:`PagePoolExhausted` when fewer are free — the engine's
        admission gate checks ``free_pages`` first and holds the
        request instead."""
        if n > len(self._free):
            raise PagePoolExhausted(
                f"asked for {n} page(s), only {len(self._free)} free "
                f"of {self.num_pages - 1} (admission should hold the "
                "request until running work frees pages)")
        ids = self._free[:n]
        del self._free[:n]
        for p in ids:
            self._refs[p] = 1
        if hbm.active_ledger() is not None:
            self._note_pages_ledger()
        led = life.active_ledger()
        if led is not None:
            for p in ids:
                led.acquire("page", (id(self), p))
        return ids

    def incref(self, ids: Sequence[int]) -> None:
        for p in ids:
            if p == 0:
                continue
            if self._refs[p] <= 0:
                raise ValueError(f"incref of free page {p}")
            self._refs[p] += 1

    def decref(self, ids: Sequence[int]) -> None:
        """Drop one reference per page; a page at zero returns to the
        free list (sorted — deterministic reuse)."""
        freed = False
        led = life.active_ledger()
        for p in ids:
            if p == 0:
                continue
            if self._refs[p] <= 0:
                raise ValueError(f"decref of free page {p}")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)
                freed = True
                if led is not None:
                    led.release("page", (id(self), p))
        if freed:
            self._free.sort()
            if hbm.active_ledger() is not None:
                self._note_pages_ledger()

    def page_refcount(self, page: int) -> int:
        return int(self._refs[page])

    # ---- page table (host mirror + lazy device copy) -------------------
    def bind_slot(self, slot: int, page_ids: Sequence[int]) -> None:
        """Point ``slot``'s table row at ``page_ids`` (padded with
        scratch 0). OWNERSHIP TRANSFER: the row now holds the one
        reference per real page the caller allocated/increfed —
        ``release`` drops them."""
        if len(page_ids) > self.pages_per_slot:
            raise ValueError(
                f"{len(page_ids)} pages exceed pages_per_slot="
                f"{self.pages_per_slot}")
        row = np.zeros((self.pages_per_slot,), np.int32)
        row[:len(page_ids)] = page_ids
        self._table[slot] = row
        self._table_dirty = True

    def slot_pages(self, slot: int) -> List[int]:
        """The slot's REAL (non-scratch) table entries, in column
        order."""
        return [int(p) for p in self._table[slot] if p != 0]

    def device_table(self):
        """The page table as a device operand for the jitted decode —
        re-uploaded ONLY when the host mirror changed (admission/
        release boundaries), so the steady state makes zero transfers.
        The upload carries its own ``expected_transfer`` annotation —
        the dirty condition and the sentinel exemption live in ONE
        place, so they cannot drift."""
        if self._table_dirty or self._table_dev is None:
            from ..analysis.sentinels import expected_transfer

            with graftscope.span("pages.table_upload", cat="serving"), \
                    expected_transfer("page-table upload after "
                                      "admission/release (host-mirrored "
                                      "page alloc)"):
                self._table_dev = self._replicated(
                    jnp.asarray(self._table))
            self._table_dirty = False
        return self._table_dev

    # ---- host-side slot accounting -------------------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free_slots)

    @property
    def occupancy(self) -> int:
        return self.max_slots - len(self._free_slots)

    def acquire(self) -> int:
        """Claim a free slot index (lowest-numbered first, so re-use is
        deterministic and tests can pin recycling)."""
        if not self._free_slots:
            raise RuntimeError("no free slots (acquire() without "
                               "checking free_slots)")
        slot = self._free_slots.pop(0)
        led = life.active_ledger()
        if led is not None:
            led.acquire("slot", (id(self), slot))
        return slot

    def release(self, slot: int) -> None:
        """Return ``slot`` to the free list AND drop its page
        references (shared prefix pages survive while the cache or
        other slots still hold them). The row resets to scratch so
        the frozen row's masked re-writes land in page 0 from the NEXT
        dispatch on. A block already in flight (the engine's step is
        pipelined one block deep) still carries the old row: its
        frozen re-write of the pinned column lands in a page this call
        frees, before, in device order, any insert or decode write of
        that page's next owner — which writes every column before
        reading it, so the stale row is never read. The device-side
        active flag is already False by then: the fused decode scan
        clears it when the row's EOS or budget gate fires, and the
        engine's quarantine/deadline eviction scrubs it
        (``ServingEngine._evict_fn``) BEFORE releasing."""
        if slot in self._free_slots or not 0 <= slot < self.max_slots:
            raise ValueError(f"bad release of slot {slot}")
        self.decref(self.slot_pages(slot))
        self._table[slot] = 0
        self._table_dirty = True
        self._free_slots.append(slot)
        self._free_slots.sort()
        self._active_host[slot] = False
        led = life.active_ledger()
        if led is not None:
            led.release("slot", (id(self), slot))

    # ---- host position mirror (decode-window tracking) -----------------
    def note_insert(self, slot: int, position: int) -> None:
        """Record a freshly spliced tenant: its next decode write lands
        at ``position`` (= prompt length, per the slot invariants)."""
        self._positions_host[slot] = int(position)
        self._active_host[slot] = True

    def note_advance_slots(self, realized) -> None:
        """Mirror one drained decode horizon: slot ``s`` advanced by
        ``realized[s]`` device steps — the REALIZED count, not the
        dispatched horizon length (rows the device froze mid-scan
        advanced only up to their freeze, and the mirror must agree
        with the device's frozen position exactly)."""
        ps, ring = self.page_size, self.ring_pages
        for slot, steps in realized.items():
            before = self._positions_host[slot]
            self._positions_host[slot] = after = before + int(steps)
            if ring:
                # a page begun at or beyond the ring's length lands on
                # an entry that held a page now out of reach
                self.ring_pages_overwritten += max(
                    0, after // ps - max(before // ps, ring - 1))

    @property
    def max_active_pos(self) -> int:
        """Highest position any ACTIVE slot will write this step — the
        high-water mark the decode window must cover. -1 when idle."""
        return max(
            (p for p, live in zip(self._positions_host,
                                  self._active_host) if live),
            default=-1)

    @property
    def live_pages(self) -> int:
        """Pages the active slots' positions reach — what one layer's
        paged decode kernel has to read — off the host mirror."""
        return sum(p // self.page_size + 1
                   for p, live in zip(self._positions_host,
                                      self._active_host) if live)

    def live_pages_by_kind(self) -> Dict[str, int]:
        """``live_pages`` for two kinds of layer, under each pool's
        counter name (:func:`live_counter`): what ONE layer of the paged
        pool reads (every page up to the position) and what one layer of
        the ring reads (the pages that hold a column in the window's
        reach), off the host mirror."""
        ps, window = self.page_size, self.ring_window
        live = [p for p, on in zip(self._positions_host,
                                   self._active_host) if on]
        names = self._live_names
        return {names[False].format("pages"): sum(p // ps + 1 for p in live),
                names[True].format("pages"): sum(
                    p // ps - max(p - window + 1, 0) // ps + 1
                    for p in live)}

    def live_bytes_by_kind(self, pages: Dict[str, int]) -> Dict[str, int]:
        """:meth:`live_pages_by_kind`'s ``pages`` in bytes, each kind's
        pages at its own row: the rows of the two kinds may differ, so
        pages alone no longer say what a layer's kernel reads."""
        return {name.format("bytes"): pages[name.format("pages")]
                * self._layer_page_bytes[ring]
                for ring, name in self._live_names.items()}


class PrefixEntry:
    """One cached shared prefix: ``n_full`` full pages covering
    ``tokens[: n_full * page_size]`` plus (when the registered prompt
    was not page-aligned) a cache-OWNED frozen copy of the partial
    last page, so an identical prompt is a FULL hit — no prefill
    compute at all. ``tok0`` is the greedy first token the creator
    sampled (host int): a full hit's TTFT is a state splice plus at
    most one page copy."""

    __slots__ = ("tokens", "n_full", "shared_ids", "partial_id", "tok0",
                 "hits")

    def __init__(self, tokens: Tuple[int, ...], n_full: int,
                 shared_ids: List[int], partial_id: Optional[int],
                 tok0: Optional[int]):
        self.tokens = tokens
        self.n_full = n_full
        self.shared_ids = shared_ids
        self.partial_id = partial_id
        self.tok0 = tok0
        self.hits = 0

    @property
    def covered(self) -> int:
        """Cached K/V columns: the full prompt when the partial page
        was copied (or the prompt was page-aligned), else the aligned
        prefix only."""
        return len(self.tokens)


class PrefixCache:
    """Host-side index of prefilled prompt prefixes over a
    :class:`PagePool`, keyed on prompt-token hash.

    An entry is registered after a MISS finishes its prefill: the
    slot's leading full pages are increfed (shared read-only from then
    on — the creator's decode writes only columns ``>= L``, which live
    past them) and the partial last page, if any, is copied into a
    cache-owned page. Lookups walk page-aligned prefixes longest-first
    and verify tokens (hashes only route). LRU-bounded
    (``max_entries``); eviction — explicit, LRU under page pressure
    (the engine sheds cache before holding admission), or
    ``clear()`` — drops the cache's page references.
    """

    def __init__(self, pool: PagePool, max_entries: int = 64):
        if max_entries < 1:
            raise ValueError(
                f"max_entries must be >= 1, got {max_entries}")
        self.pool = pool
        self.max_entries = int(max_entries)
        self._lru: "OrderedDict[int, PrefixEntry]" = OrderedDict()
        self._by_prefix: Dict[Tuple[int, int], PrefixEntry] = {}
        self._full: Dict[int, PrefixEntry] = {}
        # longest registered prefix (in pages): bounds lookup's
        # longest-first walk so a long miss prompt pays O(max
        # registered) prefix hashes, not O(its own length)
        self._max_full = 0

    def __len__(self) -> int:
        return len(self._lru)

    @staticmethod
    def _key(tokens: Sequence[int]) -> int:
        return hash(tuple(tokens))

    def lookup(self, prompt: Sequence[int]
               ) -> Tuple[Optional[PrefixEntry], int]:
        """Longest usable cached prefix of ``prompt``: ``(entry, k)``
        with ``k`` full shared pages, or ``(None, 0)``. A FULL hit
        (the entry covers the entire prompt and carries ``tok0``) is
        recognized by ``entry.tokens == tuple(prompt)``."""
        ps = self.pool.page_size
        n = len(prompt)
        if not self._lru:
            return None, 0
        entry = self._full.get(self._key(prompt))
        if (entry is not None and entry.tokens == tuple(prompt)
                and entry.tok0 is not None):
            self._touch(entry)
            return entry, entry.n_full
        for k in range(min(n // ps, self._max_full), 0, -1):
            entry = self._by_prefix.get((k, self._key(prompt[:k * ps])))
            if (entry is not None
                    and entry.tokens[:k * ps] == tuple(prompt[:k * ps])):
                self._touch(entry)
                return entry, k
        return None, 0

    def _touch(self, entry: PrefixEntry) -> None:
        entry.hits += 1
        self._lru.move_to_end(id(entry))

    def has_prefix(self, prompt: Sequence[int]) -> bool:
        """Would :meth:`register` be a no-op for this prompt? True
        when an entry already covers its maximal aligned prefix (or
        the whole prompt)."""
        entry, k = self.lookup(prompt)
        if entry is None:
            return False
        if entry.tokens == tuple(prompt):
            return True
        return k >= len(prompt) // self.pool.page_size

    def register(self, prompt: Sequence[int], page_ids: Sequence[int],
                 tok0: Optional[int], copy_page) -> Optional[PrefixEntry]:
        """Cache ``prompt``'s prefix off a freshly spliced slot whose
        table maps ``page_ids`` (column order). Increfs the leading
        ``len(prompt) // page_size`` full pages; when the prompt is
        not page-aligned AND a free page exists, allocates a cache-
        owned destination page and fills it via ``copy_page(src_page,
        dst_page)`` (a device page copy, no return value; else the
        entry covers the aligned prefix only and drops ``tok0``).
        No-op when nothing would be cached or the prefix is already
        covered. Evicts LRU past ``max_entries``."""
        ps = self.pool.page_size
        n = len(prompt)
        n_full = n // ps
        if n_full < 1 or self.has_prefix(prompt):
            return None
        shared = [int(p) for p in page_ids[:n_full]]
        if len(shared) < n_full:
            raise ValueError(
                f"slot maps {len(page_ids)} page(s); prompt needs "
                f"{n_full} full page(s)")
        partial_id = None
        tokens = tuple(int(t) for t in prompt)
        if n % ps:
            if self.pool.free_pages >= 1:
                (partial_id,) = self.pool.alloc_pages(1)
                try:
                    copy_page(int(page_ids[n_full]), partial_id)
                except BaseException:
                    self.pool.decref([partial_id])  # no orphaned page
                    raise
            else:
                # best-effort: cache the aligned prefix only
                tokens = tokens[:n_full * ps]
                tok0 = None
        self.pool.incref(shared)
        entry = PrefixEntry(tokens, n_full, shared, partial_id, tok0)
        self._lru[id(entry)] = entry
        self._max_full = max(self._max_full, n_full)
        for k in range(1, n_full + 1):
            self._by_prefix.setdefault(
                (k, self._key(tokens[:k * ps])), entry)
        if entry.tok0 is not None:
            self._full.setdefault(self._key(tokens), entry)
        while len(self._lru) > self.max_entries:
            self.evict_lru()
        return entry

    def _drop(self, entry: PrefixEntry) -> None:
        self._lru.pop(id(entry), None)
        # rebuild the indexes from the survivors: a key the dropped
        # entry owned may be coverable by a LATER entry sharing the
        # same prefix (registration's setdefault kept the older one) —
        # deleting the key outright would orphan the survivor's pages
        self._by_prefix.clear()
        self._full.clear()
        ps = self.pool.page_size
        self._max_full = 0
        for live in self._lru.values():
            for k in range(1, live.n_full + 1):
                self._by_prefix.setdefault(
                    (k, self._key(live.tokens[:k * ps])), live)
            if live.tok0 is not None:
                self._full.setdefault(self._key(live.tokens), live)
            self._max_full = max(self._max_full, live.n_full)
        self.pool.decref(entry.shared_ids)
        if entry.partial_id is not None:
            self.pool.decref([entry.partial_id])

    def evict_lru(self) -> bool:
        """Drop the least-recently-hit entry (False when empty) —
        the engine's page-pressure relief valve: cache pages yield to
        admission before any request is held."""
        if not self._lru:
            return False
        _, entry = next(iter(self._lru.items()))
        self._drop(entry)
        return True

    def clear(self) -> None:
        """Drop everything — without _drop's per-eviction survivor
        reindex (there are no survivors to reindex)."""
        entries = list(self._lru.values())
        self._lru.clear()
        self._by_prefix.clear()
        self._full.clear()
        self._max_full = 0
        for entry in entries:
            self.pool.decref(entry.shared_ids)
            if entry.partial_id is not None:
                self.pool.decref([entry.partial_id])
