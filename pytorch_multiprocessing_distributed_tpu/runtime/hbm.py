"""graftmeter live HBM ledger: who owns how many device bytes, now.

graftscope (``runtime/scope.py``) made the stack observable in *time*;
this module is its sibling in *space*: a host-side ledger of every
long-lived device allocation the framework makes — parameters,
optimizer state, the serving KV :class:`~..serving.kv_pages.PagePool`
(``num_pages`` x page bytes, with the pages in use as live gauges),
per-bucket decode-program temporaries — registered AT the allocation
site and exposed as ``hbm_*`` gauges beside the serving/training
metrics on ``/metrics`` and ``snapshot.json``.

The ledger never touches the device: every entry is computed from
shapes and dtypes the host already holds (``nbytes_of`` reads the
``.nbytes``/aval metadata jax keeps host-side — no transfer, no sync),
and per-program temp bytes come from the graftmeter static model
(``analysis/meter.py``: XLA's own compiled memory analysis via AOT
lowering, which never executes and never enters the jit trace cache —
the recompile/transfer sentinels stay green with the ledger armed).

Arming discipline is ``runtime.faults``'s / ``runtime.scope``'s: one
module global. Disarmed (the default), every registration helper is a
single global read + ``is None`` check — hot paths pay nothing and
nothing is retained. The CLIs arm a ledger when ``--stats_port`` asks
for live gauges; tests arm one with :class:`scoped_ledger`.

Stdlib-only by design (``tree_nbytes`` lazily imports jax): importable
from the schedulers and the fault layer without dragging a runtime in.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, Optional

__all__ = [
    "HbmLedger", "arm", "disarm", "active_ledger", "scoped_ledger",
    "register", "update", "release", "set_gauge", "nbytes_of",
    "tree_nbytes", "shard_nbytes", "tree_shard_nbytes",
]


def nbytes_of(x) -> int:
    """Device bytes of one array-like, from HOST-side metadata only:
    ``.nbytes`` when present (jax arrays, ShapeDtypeStructs and numpy
    all keep it without a device read), else ``prod(shape) *
    dtype.itemsize``. Raises TypeError on something that is not
    array-shaped — a ledger entry of unknowable size is a bug, not a
    zero."""
    n = getattr(x, "nbytes", None)
    if n is not None:
        return int(n)
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        raise TypeError(
            f"nbytes_of wants an array-like (shape+dtype), got "
            f"{type(x).__name__}")
    return int(math.prod(shape)) * int(dtype.itemsize)


def tree_nbytes(tree) -> int:
    """Total device bytes of a pytree of arrays (params, optimizer
    state) — host metadata only, no device touch."""
    import jax

    return sum(nbytes_of(leaf) for leaf in jax.tree.leaves(tree))


def shard_nbytes(x) -> int:
    """PER-CHIP device bytes of one array: a sharded leaf charges the
    slice one device holds (``sharding.shard_shape`` — pure host
    metadata, no device read), a replicated/unplaced leaf its full
    size. The graftzero/FSDP ledger truth: ``hbm_*`` gauges describe
    ONE chip's HBM, so a ``P(data)``-sharded moment bucket must count
    ``1/data`` of itself.

    A graftquant ``QuantizedKV`` pair (duck-typed: ``.data`` +
    ``.scale`` attributes) charges per leaf — each side carries its
    OWN sharding, and the pair's aggregate ``.nbytes`` would miscount
    a head-sharded cache."""
    data = getattr(x, "data", None)
    scale = getattr(x, "scale", None)
    if (scale is not None and data is not None
            and hasattr(scale, "dtype")):
        return shard_nbytes(data) + shard_nbytes(scale)
    sharding = getattr(x, "sharding", None)
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if sharding is not None and shape is not None and dtype is not None:
        try:
            shard_shape = sharding.shard_shape(tuple(shape))
        except Exception:  # noqa: BLE001  # graftlint: disable=GL111 exotic shardings fall back to global bytes
            return nbytes_of(x)
        return int(math.prod(shard_shape)) * int(dtype.itemsize)
    return nbytes_of(x)


def tree_shard_nbytes(tree) -> int:
    """Per-chip total of a pytree (:func:`shard_nbytes` per leaf)."""
    import jax

    return sum(shard_nbytes(leaf) for leaf in jax.tree.leaves(tree))


class HbmLedger:
    """Named device-byte entries grouped by category.

    Entries are ``name -> (category, bytes, attrs)``; re-registering a
    name replaces it (an allocation site that re-allocates — a resized
    pool, a re-sharded state — keeps ONE truthful row). ``snapshot()``
    flattens to the gauge dict the stats endpoints merge in: a total,
    one gauge per category, one per entry — all prefixed ``hbm_`` so
    a Prometheus exposition under the ``pmdt`` prefix reads
    ``pmdt_hbm_total_bytes`` etc.
    """

    def __init__(self):
        self._entries: Dict[str, tuple] = {}
        self._gauges: Dict[str, int] = {}
        self._mu = threading.Lock()

    def register(self, name: str, nbytes: int, category: str = "other",
                 **attrs) -> None:
        if nbytes < 0:
            raise ValueError(
                f"hbm entry {name!r}: bytes must be >= 0, got {nbytes}")
        with self._mu:
            self._entries[name] = (str(category), int(nbytes),
                                   dict(attrs))

    def update(self, name: str, nbytes: int) -> None:
        """Resize an existing entry (unknown names raise — a typo'd
        update must not silently create a second row)."""
        with self._mu:
            if name not in self._entries:
                raise KeyError(f"no hbm entry {name!r} to update")
            cat, _, attrs = self._entries[name]
            self._entries[name] = (cat, int(nbytes), attrs)

    def release(self, name: str) -> None:
        """Drop an entry (idempotent: releasing twice — or an entry a
        disarmed phase never registered — is not an error)."""
        with self._mu:
            self._entries.pop(name, None)

    def set_gauge(self, name: str, value: int) -> None:
        """A UTILIZATION gauge riding beside the byte entries
        (graftpage's ``pages_in_use`` etc.): exported verbatim by
        ``snapshot()`` but NEVER summed into ``hbm_total_bytes`` — a
        page in use is already counted by the pool's capacity entry,
        and a ledger that double-counts is worse than none."""
        with self._mu:
            self._gauges[name] = int(value)

    def entries(self) -> Dict[str, tuple]:
        with self._mu:
            return dict(self._entries)

    @property
    def total_bytes(self) -> int:
        with self._mu:
            return sum(b for _, b, _ in self._entries.values())

    def breakdown(self) -> Dict[str, Dict[str, int]]:
        """``{category: {entry name: bytes}}`` — the stacked-bar input
        (``utils.plotting.draw_hbm_breakdown``)."""
        out: Dict[str, Dict[str, int]] = {}
        for name, (cat, nbytes, _attrs) in sorted(self.entries().items()):
            out.setdefault(cat, {})[name] = nbytes
        return out

    def snapshot(self) -> Dict[str, int]:
        """Flat gauges: ``hbm_total_bytes``, ``hbm_<category>_bytes``,
        ``hbm_<category>_<entry>_bytes`` (entry names sanitized to
        metric-safe characters)."""
        def safe(s: str) -> str:
            return "".join(c if (c.isalnum() or c == "_") else "_"
                           for c in s)

        snap: Dict[str, int] = {}
        total = 0
        for cat, rows in self.breakdown().items():
            cat_total = sum(rows.values())
            total += cat_total
            snap[f"hbm_{safe(cat)}_bytes"] = cat_total
            for name, nbytes in rows.items():
                snap[f"hbm_{safe(cat)}_{safe(name)}_bytes"] = nbytes
        snap["hbm_total_bytes"] = total
        snap["hbm_entries"] = len(self.entries())
        with self._mu:
            for name, value in self._gauges.items():
                snap[f"hbm_{safe(name)}"] = value
        return snap


_LEDGER: Optional[HbmLedger] = None


def arm(ledger: Optional[HbmLedger] = None) -> HbmLedger:
    global _LEDGER
    _LEDGER = ledger if ledger is not None else HbmLedger()
    return _LEDGER


def disarm() -> None:
    global _LEDGER
    _LEDGER = None


def active_ledger() -> Optional[HbmLedger]:
    return _LEDGER


class scoped_ledger:
    """``with scoped_ledger() as l: ...`` — arm for the block, always
    disarm (test/bench hygiene, mirrors ``scope.scoped``)."""

    def __init__(self, ledger: Optional[HbmLedger] = None):
        self.ledger = ledger if ledger is not None else HbmLedger()

    def __enter__(self) -> HbmLedger:
        return arm(self.ledger)

    def __exit__(self, *exc) -> None:
        disarm()


# ---- module-level registration against the armed ledger ------------
# Disarmed cost: one global read + `is None` — the faults/scope
# discipline. Allocation sites call these unconditionally.

def register(name: str, nbytes: int, category: str = "other",
             **attrs) -> None:
    ledger = _LEDGER
    if ledger is None:
        return
    ledger.register(name, nbytes, category, **attrs)


def update(name: str, nbytes: int) -> None:
    ledger = _LEDGER
    if ledger is None:
        return
    ledger.update(name, nbytes)


def release(name: str) -> None:
    ledger = _LEDGER
    if ledger is None:
        return
    ledger.release(name)


def set_gauge(name: str, value: int) -> None:
    ledger = _LEDGER
    if ledger is None:
        return
    ledger.set_gauge(name, value)
