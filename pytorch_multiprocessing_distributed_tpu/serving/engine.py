"""Continuous-batching serving engine over the shared KV-cache decode.

``inference.generate`` is a one-shot, fixed-batch program: B prompts in,
B continuations out, everything retired together. A serving workload is
the opposite shape — requests arrive whenever, finish whenever — and
the naive answer (re-invoke ``generate`` per batch composition) would
recompile or at best re-prefill constantly. This engine converts the
same ``_prefill``/cached-attention machinery into a persistent loop
whose compiled-program set is SMALL and FIXED, and whose per-step cost
tracks the work actually resident:

- the KV cache is ONE :class:`~.kv_pages.PagePool` — fixed-size
  pages ``[layers, num_pages, page_size, heads * head_dim]`` mapped
  per slot through a page table, per-slot position counters, an
  active mask;
- **length-bucketed decode**: each step attends over the cache prefix
  ``[0, W)`` where ``W`` is the smallest configured bucket covering the
  longest ACTIVE sequence (tracked host-side by the pool, no device
  sync). ``W`` is a jit-static, so the decode step compiles once per
  bucket — a bounded ladder (``decode_buckets``), pinned via
  ``utils.compile_cache.jit_cache_size``/``jit_cache_keys`` — and a
  pool full of short sequences no longer pays ``s_max`` attention
  reads per token. Token-exact with the full-window step: the windowed
  columns are exactly the unmasked ones;
- **prefill-on-join**, whole-prompt or chunked. Whole-prompt: the
  shared ``inference.generate._prefill`` on one right-padded prompt
  (compiles per power-of-two bucket), its caches spliced into a free
  slot, first token sampled from the prefill logits — exactly
  ``generate``'s ``tok0`` path. **Chunked** (``prefill_chunk=N``): the
  prompt runs through a fixed-shape ``[1, N]`` incremental-prefill
  program, ONE chunk per engine step, interleaved with the resident
  decode — no resident request ever stalls longer than one chunk's
  latency for its next token (the TTFT head-of-line fix), and the
  chunk program compiles once per ``(chunk, width)`` pair
  (:class:`~.scheduler.PrefillPlan`);
- decode attention runs through the fused flash-decode kernel
  (:mod:`...ops.pallas.decode_attention` — bf16 MXU matmuls, f32
  online-softmax accumulation, per-slot position gate) on TPU, the
  bit-identical XLA reference elsewhere; CPU tests pin the kernel in
  interpret mode;
- **decode horizon** (``decode_horizon=H``): when no admission work is
  pending, H decode steps run as ONE jitted ``lax.scan``
  (:func:`...inference.generate._decode_horizon`, the same core
  ``generate`` decodes on) emitting an ``[H, slots]`` token block with
  ONE host readback — steady-state throughput stops being bounded by
  per-step dispatch + sync latency (the reference's per-iteration
  ``.item()`` sin, re-shaped). EOS/budget gating runs ON DEVICE
  (per-slot ``eos_ids``/``budgets`` in the pool), freezing finished
  rows mid-horizon, so an H-step block is token-exact with H single
  steps. The scheduler picks the horizon adaptively
  (:func:`~.scheduler.pick_horizon`: bucket-boundary distance,
  shortest remaining budget, queue pressure) and snaps it to the
  ``{1, H}`` ladder, bounding decode compiles by
  ``|buckets touched| x 2``;
- **the step is a software pipeline one block deep**, at every horizon
  (``H == 1`` included) and with admission work queued or pending:
  ``step()`` admits, dispatches block ``k``, and only then reads block
  ``k - 1`` back (double-buffered pending blocks, the trainer's
  deferred-metrics pattern), so a decode program is queued on the
  device while the host reads tokens, runs its per-token loop, returns
  to its caller, schedules, reserves pages and dispatches. Admission
  is two stages a step apart for the same reason: a prefill is
  dispatched behind the block in flight and NOT waited for; the next
  step reads its first token (long computed, the device already in
  the next block) and queues the insert ahead of that step's dispatch.
  The price: a finish is seen one drain later (the slot holds one
  frozen row more), and a first token comes one step after its
  prefill was dispatched, its slot idle meanwhile. What keeps it
  exact is in ``_step_inner``'s note;
- finished slots (EOS / ``max_new_tokens``) are recycled in place —
  stale cache columns are masked until the next tenant overwrites them
  (see ``kv_pages`` invariants). Finish detection is on-device; the
  host replays the same rules on the drained block (the mirror the
  realized per-slot position advances come from).

Greedy decode through the engine is token-for-token identical to
per-request ``generate`` calls (test-pinned, dense and MoE, bucketed
and chunked): same helpers, same dtype/eps conventions, per-slot
positions in place of the scan counter. With ``mesh`` the caches and
attention shard over the ``model`` axis exactly like TP ``generate`` —
single-host TP serving (XLA attention path; the Pallas kernel is
single-shard).

**Fault domains (graftfault).** Every host-side hazard point registers
a named injection site (``runtime.faults``) and runs under bounded
retry: transient failures of per-request work (prefill, chunk, insert)
quarantine JUST that request — evicted as FAILED with its error, its
slot's device gates scrubbed and the slot recycled — while engine-wide
work (decode dispatch, readback) fails fast with a named
``GraftFaultError`` once retries exhaust. A recovered fault opens a
cooldown during which the adaptive horizon collapses to 1 (smaller
blast radius), the bounded queue sheds load under pressure, and every
absorbed fault is visible in ``ServingMetrics`` (``dispatch_retries``,
``requests_failed``, ``requests_shed``, ``watchdog_trips``,
``horizon_collapses``). The headline invariant is the fault matrix's:
under any single injected fault, every unaffected request's tokens are
byte-identical to the fault-free run (``tests/test_graftfault.py``).
Disarmed cost is one module-global read per hazard point — no extra
compiles, transfers, or host syncs (sentinel-pinned).

**Observability (graftscope).** Every request's lifecycle — submit →
queued → admit → prefill (whole or chunked) → first token → horizon
blocks → EOS/FAILED/shed — and every engine phase (dispatch, drain,
insert) emits structured events through ``runtime.scope``, ALWAYS at
boundaries where the host already synchronizes; arming the scope adds
zero compiles, transfers or host syncs (the same sentinel pin as
graftfault's disarmed cost, now tested with the scope ARMED). Fault
handling is on the same timeline: injections, retries, watchdog trips,
horizon collapses, quarantines. Engine-fatal paths
(``PoolPoisonedError``, watchdog fail-fast, an unhandled error in
``step()``) dump the flight-recorder ring before propagating — the
postmortem starts from the last seconds of events, not a bare stack
trace.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Deque, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis.sentinels import expected_transfer
from ..inference.generate import (
    _decode_horizon, _make_cs, _prefill, _sample, pref_cache_shapes,
    serving_family)
from ..ops.kv_quant import (KV_DTYPES, QuantizedKV, dequantize_kv,
                            quantize_kv, quantize_kv_np)
from ..runtime import hbm
from ..runtime import heal
from ..runtime import life
from ..runtime import scope as graftscope
from ..runtime.faults import (DeadlineExceeded, FaultInjected,
                              FaultTimeout, GraftFaultError,
                              PoolPoisonedError, maybe_fault,
                              register_site, retry_with_backoff,
                              run_with_timeout)
from ..utils.compile_cache import (jit_cache_keys, jit_cache_size,
                                   record_jit_key)
from ..utils.metrics import ServingMetrics
from ..utils import profiler  # noqa: F401 (sets graftscope's annotator)
from .kv_pages import (PAGE_SPEC, PagePool, PagePoolExhausted,
                       PrefixCache)
from .scheduler import (DONE, FAILED, RUNNING, FIFOScheduler,
                        PrefillPlan, QueueFull, Request,
                        RequestWithdrawn, bucket_length, pick_draft_k,
                        pick_horizon)
from .spec import NgramDrafter

__all__ = ["ServingEngine", "Request"]

# graftfault injection sites: the serving engine's hazard points, one
# per distinct failure domain the fault-matrix suite must prove
# recoverable (or fail-fast). Registered next to the code that calls
# maybe_fault — an unregistered hazard is invisible to the sweep.
_SITE_DISPATCH = register_site(
    "serving.decode_dispatch",
    "fused decode-horizon dispatch (the engine's hot XLA launch)")
_SITE_READBACK = register_site(
    "serving.horizon_readback",
    "token-block readback sync at horizon drain (the step's ONE host "
    "sync; watchdog-bounded when readback_timeout_s is set)")
_SITE_PREFILL = register_site(
    "serving.prefill",
    "whole-prompt prefill-on-join + first-token readback")
_SITE_CHUNK = register_site(
    "serving.prefill_chunk",
    "one [1, chunk] incremental-prefill step of a joining prompt")
_SITE_TOK0 = register_site(
    "serving.prefill_tok0",
    "first-token readback, one step after the prefill that computed it "
    "was dispatched (the TTFT boundary of both admission paths)")
_SITE_INSERT = register_site(
    "serving.slot_insert",
    "slot splice of a prefilled request (cache columns + finish gates)")


class _TokenBlock:
    """One dispatched decode horizon awaiting readback: the device
    ``[rows, slots]`` token block plus the host snapshot needed to
    attribute it at drain time (which request held each slot when the
    horizon launched, how many steps it ran, at which window).
    ``rows == h`` for plain decode; a speculative horizon (``k > 0``,
    graftspec) drains ``h * (k + 1)`` rows — pass ``j``'s ``k + 1``
    verified-emission rows in order, ``-1`` holes where the device
    rejected or froze — through the SAME row-by-row attribution
    loop."""

    __slots__ = ("tokens", "h", "window", "slots", "k", "rows")

    def __init__(self, tokens, h, window, slots, k=0):
        self.tokens = tokens
        self.h = h
        self.window = window
        self.slots = slots  # slot -> Request at dispatch time
        self.k = k
        self.rows = h * (k + 1)


class _PendingPrefill:
    """Host-side state of one admission in flight. Mid-chunked-prefill
    (``engine._pending``, at most one): its chunk plan plus the
    standalone caches the chunks accumulate into. Prefill all
    dispatched (``engine._unread``, ``tok0`` set, ``plan`` None for a
    whole prompt): the caches and the first token are device values
    nobody has waited for; the NEXT step's admission reads the token
    and splices the caches into a pool slot. ``prep`` is its page
    reservation."""

    __slots__ = ("request", "plan", "k_pref", "v_pref", "prep", "length",
                 "tok0")

    def __init__(self, request, plan, k_pref, v_pref, prep, tok0=None):
        self.request = request
        self.plan = plan
        self.k_pref = k_pref
        self.v_pref = v_pref
        self.prep = prep
        self.length = len(request.prompt)
        self.tok0 = tok0


class _PagedPrep:
    """One paged admission's page reservation, made BEFORE the FIFO
    head is popped (host-only: free-list pops + refcount bumps — no
    device work, graftfault-safe). Holds one reference per page until
    the splice transfers ownership to the slot's table row
    (``bind_slot``) or the admission aborts (``ServingEngine.
    _abort_prep`` — quarantine, finished-at-first-token, failed
    prefill)."""

    __slots__ = ("mode", "entry", "k", "shared_ids", "fresh_ids",
                 "fork_src", "n_total")

    def __init__(self, mode, entry, k, shared_ids, fresh_ids, fork_src,
                 n_total):
        self.mode = mode            # "miss" | "partial" | "full"
        self.entry = entry          # PrefixEntry (hits only)
        self.k = k                  # shared full pages reused
        self.shared_ids = shared_ids
        self.fresh_ids = fresh_ids  # freshly allocated, column order
        self.fork_src = fork_src    # COW source (entry partial page)
        self.n_total = n_total      # pages the request pins in total

    @property
    def page_ids(self):
        """The slot's column-ordered table row."""
        return list(self.shared_ids) + list(self.fresh_ids)


class ServingEngine:
    """Slot-based continuous-batching driver.

    Args:
      model: dense-view ``GPT`` (pass ``model.clone(seq_axis=None)``
        for an SP-trained model — identical params). MoE models serve
        with dropless routing, like ``generate``.
      params: plain GPT param tree. For TP serving place it with
        :func:`..inference.shard_params_for_tp_decode` first.
      max_slots: concurrent requests decoded per step (the pool size).
      s_max: per-slot token capacity (default ``model.max_seq_len``).
      mesh: optional ``Mesh`` with a ``model`` axis — Megatron-style TP
        decode, same semantics/validation as ``generate(mesh=...)``.
      max_queue: bound on QUEUED requests (None = unbounded);
        ``submit`` raises :class:`~.scheduler.QueueFull` beyond it.
      temperature/top_k/top_p: sampling config, engine-wide statics
        (0/0/0 = greedy). NOTE: greedy is the mode pinned equivalent to
        ``generate``; sampled streams draw from a per-step key shared
        across slots, so they are reproducible per engine run (at fixed
        ``prefill_chunk``) but not comparable to per-request
        ``generate`` draws.
      rng: PRNGKey, required when ``temperature > 0``.
      eos_id: default stop token (per-request ``eos_id`` overrides).
      min_bucket: smallest prefill bucket AND the decode-bucket
        ladder's first rung (power of two).
      decode_buckets: attention-window ladder for bucketed decode.
        None (default) = powers of two from ``min_bucket`` up to
        ``s_max``; an explicit ascending sequence pins the ladder
        (``s_max`` is appended if absent); an EMPTY sequence disables
        bucketing — every step attends the full ``s_max`` window, the
        PR-1 behavior the bench uses as its baseline. The decode step
        compiles once per bucket the traffic actually touches, never
        more than ``len(decode_buckets)`` programs.
      prefill_chunk: admit prompts through fixed-size chunks of this
        many tokens, one chunk per engine step, instead of one
        whole-prompt call (None = whole-prompt). Bounds every resident
        request's between-token stall to one chunk's latency.
      decode_horizon: max decode steps fused into ONE dispatched
        ``lax.scan`` with ONE token-block readback (default 1 = the
        per-step engine). The realized horizon per dispatch is
        :func:`~.scheduler.pick_horizon`'s choice snapped to the
        ``{1, decode_horizon}`` ladder — H collapses to 1 while
        admission work is pending (bounded join latency), near a
        decode-bucket boundary, or when the shortest remaining budget
        would waste most of the horizon. H decides how many tokens a
        program emits per readback, NOT whether the readback is
        hidden: the next block dispatches before this one syncs at
        every H (``_overlap_ok``). Sampled (``temperature > 0``) streams stay
        reproducible per engine run but depend on the horizon schedule
        (per-step keys split inside the program); greedy output is
        horizon-invariant (test-pinned).
      decode_attn: ``"pallas"`` | ``"xla"`` | ``"auto"`` — decode-step
        attention implementation (auto: the fused kernel on single-
        shard TPU, XLA elsewhere; ``"pallas"`` with a mesh is
        rejected).
      decode_block_k: K/V block size the Pallas decode kernel streams.
      dispatch_retries: bounded attempts for transient (OSError-family,
        incl. injected) failures of the engine's host-side operations
        — decode dispatch, readback, prefill, chunk, insert. Engine-
        wide operations (dispatch/readback) that stay broken after the
        attempts fail fast with a named ``GraftFaultError``; per-
        request operations quarantine the request instead (evicted as
        FAILED with its error, slot scrubbed and recycled — the engine
        keeps serving everyone else). 1 = no retries.
      retry_backoff_s: first-retry delay (doubles per retry).
      readback_timeout_s: optional watchdog bound on ONE horizon
        token-block readback attempt (retry backoff between transient
        failures is never charged against it). None (default) = no
        watchdog thread on the hot path; set it to detect a HUNG
        readback (device/runtime wedge) and fail fast with a
        ``FaultTimeout`` instead of sitting forever. Counted in
        ``ServingMetrics.watchdog_trips``.
      fault_cooldown: decode dispatches for which the adaptive horizon
        collapses to 1 after a recovered transient fault (graceful
        degradation: smaller blast radius + faster drain while the
        fault domain is suspect); each forced collapse is counted in
        ``ServingMetrics.horizon_collapses``.
      kv_layout: only ``"paged"`` (the one layout: a
        :class:`~.kv_pages.PagePool` of fixed-size pages mapped per
        slot through an ``[max_slots, pages_per_slot]`` page table — a
        request pins ``ceil((L + max_new) / page_size)`` pages, so
        ``num_pages`` sizes HBM to the expected length distribution
        while ``max_slots`` raises concurrency past the dense worst
        case). Token-exact with ``generate()`` (test-pinned); the page
        table rides as ONE jit-traced operand, so the decode compile
        ladder is ``buckets x {1, H}``. ``"dense"`` (the slot pool
        removed in PR 31) is refused by name. The keyword itself stays
        only until ``perf/drivers/serve.py`` stops passing it
        (ROADMAP).
      page_size: columns per page (default: ``min_bucket``; multiples
        of 8 for the TPU Pallas kernel).
      num_pages: total page count INCLUDING the reserved scratch page
        (default: dense worst-case parity,
        ``max_slots * ceil(s_max / page_size) + 1``). When the FIFO
        head needs more free pages than exist, admission HOLDS it
        (``ServingMetrics.page_holds``; prefix-cache entries are shed
        LRU-first) until running work frees pages — it fails named
        (:class:`~.kv_pages.PagePoolExhausted`) only when nothing in
        flight could ever free enough.
      prefix_cache: > 0 arms the shared-prefix cache with that many
        LRU entries (greedy only — the cached first token is
        replayed, which only a deterministic stream allows). A
        prompt's page-aligned prefix is prefilled ONCE; identical
        prompts are FULL hits (no prefill compute — TTFT drops to a
        state splice plus at most one copy-on-write page fork), and
        prompts sharing a prefix re-use its pages read-only and
        chunk-prefill only their suffix.
      journal: optional :class:`~..runtime.heal.RequestJournal` — the
        redelivery WAL behind supervised restart: every admitted
        request and its emitted tokens are journaled (one fsync'd
        batch per drained step), so a restarted engine
        :meth:`redeliver`\\ s the unfinished ones token-exact
        (prefix-deduped against the already-emitted tokens). Greedy
        engines only: sampled streams are not replayable, so
        ``journal`` with ``temperature > 0`` is rejected.

      draft_k: > 0 arms **speculative decode** (graftspec): every
        decode pass proposes up to ``draft_k`` tokens per slot and
        verifies them with ONE batched (k+1)-query target pass
        through the same caches/page tables — the verify pass streams
        ~the same weight/KV bytes as one decode step (the committed
        costs.json budgets pin it) and emits 1..k+1 tokens per active
        slot, so the bandwidth-bound decode turns slack into tokens.
        Greedy engines only (``temperature > 0`` is rejected loudly —
        argmax matching cannot verify a sampled stream); accepted
        streams are token-identical to the non-speculative engine and
        ``generate()`` (test-pinned across the matrix). The realized
        k per dispatch is :func:`~.scheduler.pick_draft_k`'s choice
        on the ``{0, draft_k}`` ladder — collapsed under fault
        cooldown or sustained low acceptance (with periodic re-probe)
        — so the compile set is ``buckets x {1, H} x {k off, on}``;
        k=0 dispatches run the UNCHANGED non-speculative programs
        (disarmed spec is one host-side branch: zero extra compiles,
        transfers or syncs at steady state).
      draft_model / draft_params: optional small registry GPT (+ its
        params) proposing the k tokens autoregressively inside the
        scan instead of self-drafting; must share the target's vocab
        and cover ``s_max`` positions. Its private dense ``[L_d,
        slots, s_max, H_d, Dh_d]`` caches ride the engine (prefilled
        whole-prompt at every admission — also under chunked/prefix-
        hit admission: the draft model is the cheap side). Default
        (None with ``draft_k > 0``): self-drafting via per-slot
        n-gram tables over each request's own prompt + emitted
        tokens (:class:`~.spec.NgramDrafter`, host-mirrored, lazy
        dirty upload like the page table).
      draft_buckets: n-gram table buckets per slot (self-draft only).

    **Elastic lifecycle (graftheal).** The engine carries a
    :class:`~..runtime.heal.HealthState` machine (``STARTING`` during
    construction, ``READY`` when serving, ``DRAINING`` after
    :meth:`begin_drain` — SIGTERM via
    ``runtime.heal.install_drain_handler`` flips it — and ``DEAD``
    after :meth:`drain`): while DRAINING, admission raises
    ``QueueFull`` naming the drain, in-flight requests finish up to
    the drain deadline, and overdue ones are failed named —
    ``/healthz`` (``--stats_port``) serves 200 only in READY, so a
    replica router routes around the drain the moment it starts.
    """

    def __init__(self, model, params, *, max_slots: int,
                 s_max: Optional[int] = None, mesh: Optional[Mesh] = None,
                 max_queue: Optional[int] = None, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0,
                 rng: Optional[jax.Array] = None,
                 eos_id: Optional[int] = None, min_bucket: int = 16,
                 decode_buckets: Optional[Sequence[int]] = None,
                 prefill_chunk: Optional[int] = None,
                 decode_horizon: int = 1,
                 decode_attn: str = "auto", decode_block_k: int = 256,
                 dispatch_retries: int = 3,
                 retry_backoff_s: float = 0.02,
                 readback_timeout_s: Optional[float] = None,
                 fault_cooldown: int = 8,
                 journal=None,
                 kv_layout: str = "paged",
                 kv_dtype: str = "model",
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefix_cache: int = 0,
                 draft_k: int = 0,
                 draft_model=None,
                 draft_params=None,
                 draft_buckets: int = 64):
        # health first: an engine that dies mid-construction reports
        # STARTING on /healthz, never a stale READY
        self.health = heal.HealthState()
        if journal is not None and temperature > 0.0:
            raise ValueError(
                "journal redelivery requires deterministic (greedy) "
                "decode — a sampled stream cannot be replayed "
                "token-exact (temperature > 0 with a journal)")
        if getattr(model, "seq_axis", None) is not None:
            raise NotImplementedError(
                "the engine wants the dense view of an SP model — pass "
                "model.clone(seq_axis=None) (identical params)")
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"TP serving needs a 'model' mesh axis, got "
                    f"{mesh.axis_names}")
            tp = int(mesh.shape["model"])
            if model.num_heads % tp:
                raise ValueError(
                    f"num_heads={model.num_heads} not divisible by the "
                    f"model axis size {tp}")
        if temperature > 0.0 and rng is None:
            raise ValueError("sampling (temperature > 0) requires rng")
        if top_k < 0 or top_k > model.vocab_size:
            raise ValueError(
                f"top_k must be in [0, vocab_size={model.vocab_size}], "
                f"got {top_k}")
        if not 0.0 <= top_p <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got {top_p}")
        if min_bucket < 1:
            raise ValueError(
                f"min_bucket must be >= 1, got {min_bucket}")
        if decode_attn not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"decode_attn must be 'auto', 'xla' or 'pallas', got "
                f"{decode_attn!r}")
        if decode_attn == "pallas" and mesh is not None:
            raise ValueError(
                "decode_attn='pallas' is single-shard; TP serving "
                "(mesh) uses the XLA attention path")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if decode_horizon < 1:
            raise ValueError(
                f"decode_horizon must be >= 1, got {decode_horizon}")
        if dispatch_retries < 1:
            raise ValueError(
                f"dispatch_retries must be >= 1, got {dispatch_retries}")
        if readback_timeout_s is not None and readback_timeout_s <= 0:
            raise ValueError(
                f"readback_timeout_s must be > 0, got "
                f"{readback_timeout_s}")
        if fault_cooldown < 0:
            raise ValueError(
                f"fault_cooldown must be >= 0, got {fault_cooldown}")
        if kv_layout != "paged":
            raise ValueError(
                f"kv_layout must be 'paged', got {kv_layout!r}: the "
                "dense slot pool (kv_layout='dense') was removed — "
                "pages at the default num_pages hold the same "
                "capacity")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got "
                f"{kv_dtype!r}")
        if prefix_cache < 0:
            raise ValueError(
                f"prefix_cache must be >= 0, got {prefix_cache}")
        if prefix_cache and temperature > 0.0:
            raise ValueError(
                "prefix_cache requires deterministic (greedy) decode — "
                "a cached first token cannot be replayed into a "
                "sampled stream (temperature > 0)")
        if draft_k < 0:
            raise ValueError(f"draft_k must be >= 0, got {draft_k}")
        if draft_k and temperature > 0.0:
            # loud, at submission of the config: spec verification is
            # argmax matching — a sampled stream has no argmax to match
            raise ValueError(
                "speculative decode (draft_k > 0) is greedy-only: "
                "temperature > 0 cannot be verified by argmax "
                "matching — disarm spec or serve greedy")
        if (draft_model is not None or draft_params is not None):
            if not draft_k:
                raise ValueError(
                    "draft_model/draft_params need draft_k > 0")
            if draft_model is None or draft_params is None:
                raise ValueError(
                    "draft-model speculation needs BOTH draft_model "
                    "and draft_params")
            if draft_model.vocab_size != model.vocab_size:
                raise ValueError(
                    f"draft model vocab {draft_model.vocab_size} != "
                    f"target vocab {model.vocab_size} — drafts could "
                    "never verify")
        if draft_buckets < 1:
            raise ValueError(
                f"draft_buckets must be >= 1, got {draft_buckets}")
        # what this model's family does not support yet is refused
        # here, by the option's name: nothing falls back silently
        family = serving_family(model)
        for option, asked in (("kv_dtype=int8", kv_dtype == "int8"),
                              ("draft_k", draft_k > 0),
                              ("prefix_cache", prefix_cache > 0),
                              ("mesh", mesh is not None)):
            if asked and option in family.refuses:
                raise NotImplementedError(
                    f"{option} is not supported for the {family.name} "
                    f"family yet: {family.refuses[option]}")
        self._family = family
        # a family with routed experts returns their load behind every
        # token block, a column a held expert, one for the rest and
        # one for the rows the grouped matmuls were given; every
        # decode.dispatch event says how many it holds
        aux = family.aux_shape(model)
        self._expert_note = ({} if aux is None
                             else {"experts_held": aux[-1] - 2})
        self.model = model
        self.params = params
        self.mesh = mesh
        self.eos_id = eos_id
        self.min_bucket = int(min_bucket)
        # graftquant: int8 pool caches; prefill/transfer blocks stay
        # model dtype until the insert-time quantize (the ONE quantize
        # site, so local and transferred admissions share the formula)
        self._kv_quant = kv_dtype == "int8"
        self.pool = PagePool(
            model, max_slots, s_max, mesh,
            page_size=int(page_size if page_size is not None
                          else min_bucket),
            num_pages=num_pages, kv_dtype=kv_dtype)
        self._prefix_cache = (PrefixCache(self.pool, prefix_cache)
                              if prefix_cache else None)
        # graftspec state (all host-side; spec disarmed == draft_k 0)
        self._draft_k = int(draft_k)
        self._draft_model = draft_model
        self._draft_params = draft_params
        self._drafter = None
        self._draft_k_caches = None
        self._draft_v_caches = None
        if self._draft_k:
            if draft_model is not None:
                if draft_model.max_seq_len < self.pool.s_max:
                    raise ValueError(
                        f"draft model max_seq_len "
                        f"{draft_model.max_seq_len} < s_max="
                        f"{self.pool.s_max} — the draft cache could "
                        "not cover the slots")
                d_h = draft_model.num_heads
                dshape = (draft_model.num_layers, int(max_slots),
                          self.pool.s_max, d_h,
                          draft_model.hidden_size // d_h)
                self._draft_k_caches = self.pool._replicated(
                    jnp.zeros(dshape, draft_model.dtype))
                self._draft_v_caches = self.pool._replicated(
                    jnp.zeros(dshape, draft_model.dtype))
            else:
                self._drafter = NgramDrafter(
                    int(max_slots), self._draft_k, int(draft_buckets),
                    place=self.pool._replicated)
        # decayed mean of accepted/k per verify pass — pick_draft_k's
        # collapse signal; None until the first spec pass drains
        self._accept_ema: Optional[float] = None
        self._spec_dispatches = 0
        self._last_spec = None  # (drafted, accepted, passes) at drain
        self._held_uid = None  # FIFO head currently held for pages
        self.scheduler = FIFOScheduler(self.pool.s_max, max_queue)
        self.metrics = ServingMetrics()
        self._rng = (rng if rng is not None
                     else jnp.zeros((2,), jnp.uint32))
        self._sampling = (float(temperature), int(top_k), float(top_p))
        self._running: Dict[int, Request] = {}
        self._pending: Optional[_PendingPrefill] = None
        # prefills dispatched LAST step, first token unread: a free
        # slot is held back for each until this step's admission
        # reads the token and splices (``_finish_prefills``)
        self._unread: List[_PendingPrefill] = []
        self._prefill_chunk = (None if prefill_chunk is None
                               else int(prefill_chunk))
        self._horizon_max = int(decode_horizon)
        # dispatched-but-unsynced token blocks (<= 2 inside a step, 1
        # between steps in steady state: the pipeline depth that hides
        # the host's work without letting it run away from the device)
        self._blocks: Deque[_TokenBlock] = deque()
        self._buckets = self._build_buckets(decode_buckets)
        if decode_attn == "auto":
            decode_attn = ("pallas" if (mesh is None and
                                        jax.default_backend() == "tpu")
                           else "xla")
        self._attn_impl = decode_attn
        self._decode_block_k = int(decode_block_k)
        self._dispatch_retries = int(dispatch_retries)
        self._retry_backoff_s = float(retry_backoff_s)
        self._readback_timeout_s = (None if readback_timeout_s is None
                                    else float(readback_timeout_s))
        self._cooldown_steps = int(fault_cooldown)
        self._cooldown = 0  # dispatches left in the post-fault window
        # sticky: flips True at the first deadline-bearing submission,
        # so deadline-free serving (the default) never pays the
        # per-step queue + running scan in _expire_deadlines
        self._deadlines_seen = False
        self._step_idx = 0
        self._key_idx = 0  # one fresh fold per sampled program call
        # donation keeps one resident cache copy per step on TPU; the
        # CPU backend lacks donation and would warn every call
        donate_cache = (jax.default_backend() != "cpu")
        self._donate_cache = donate_cache
        # explicit out_shardings pin every program's outputs to the
        # pool's own placements — otherwise GSPMD's (normalized) output
        # sharding differs from the first call's input sharding and the
        # second call silently specializes a second executable,
        # breaking the bucketed compile budget on a mesh
        if mesh is not None:
            # pages shard at their last axis ([L, P, ps, H * Dh]:
            # contiguous head groups of the lanes; a graftquant page's
            # scales [L, P, ps, H] shard like its data, so the (data,
            # scale) pytree pair takes the matching pair); the
            # standalone prefill caches [L, 1, W, H, Dh] shard heads
            # at axis 3
            page_sh = NamedSharding(mesh, PAGE_SPEC)
            cache_sh = (QuantizedKV(page_sh, page_sh)
                        if self._kv_quant else page_sh)
            pref_sh = NamedSharding(
                mesh, P(None, None, None, "model", None))
            rep = NamedSharding(mesh, P())
            decode_out = (rep, cache_sh, cache_sh, rep, rep, rep, rep)
            insert_out = (cache_sh, cache_sh, rep, rep, rep, rep, rep)
            prefill_out = (rep, pref_sh, pref_sh)
            chunk_out = (rep, pref_sh, pref_sh)
            tok0_out = rep
            evict_out = (rep, rep)
            state_insert_out = (rep, rep, rep, rep, rep)
            copy_out = (cache_sh, cache_sh)
            gather_out = (pref_sh, pref_sh)
            # graftspec: same carry as decode (+ replicated draft
            # caches in draft-model mode — the draft is small, TP
            # shards only the target)
            spec_out = (decode_out + (rep, rep)
                        if draft_model is not None else decode_out)
            draft_prefill_out = (rep, rep)
            draft_insert_out = (rep, rep)
        else:
            decode_out = insert_out = prefill_out = None
            chunk_out = tok0_out = evict_out = None
            state_insert_out = copy_out = gather_out = None
            spec_out = draft_prefill_out = draft_insert_out = None
        self._decode = jax.jit(
            self._make_decode_horizon(), out_shardings=decode_out,
            static_argnames=("window", "horizon"),
            donate_argnums=(1, 2, 4, 5, 6, 7) if donate_cache else ())
        self._prefill_jit = jax.jit(self._make_prefill(),
                                    out_shardings=prefill_out)
        self._chunk_jit = jax.jit(
            self._make_chunk_prefill(), out_shardings=chunk_out,
            donate_argnums=(1, 2) if donate_cache else ())
        self._tok0_jit = jax.jit(self._make_tok0(),
                                 out_shardings=tok0_out)
        # a family with sliding-window layers holds them as a ring of
        # pages a slot (kv_pages): its splice cuts the prompt's last
        # window into the ring; every other family's is the paged one
        self._insert_jit = jax.jit(
            (self._ring_insert_fn if self.pool.ring_pages
             else self._paged_insert_fn), out_shardings=insert_out,
            donate_argnums=(0, 1, 2, 3, 4, 5, 6) if donate_cache
            else ())
        # graftquant: model-dtype standalone prefill block -> the
        # (int8, scale) pair, run ONCE per admission right before the
        # splice. Kept its own tiny program (not fused into the insert)
        # so a pre-quantized transferred block skips it entirely —
        # quantize-once across the prefill/decode split.
        self._quant_pref_jit = None
        if self._kv_quant:
            if mesh is not None:
                qp_sh = QuantizedKV(
                    pref_sh,
                    NamedSharding(mesh, P(None, None, None, "model")))
                quant_pref_out = (qp_sh, qp_sh)
            else:
                quant_pref_out = None
            self._quant_pref_jit = jax.jit(
                lambda kp, vp: (quantize_kv(kp), quantize_kv(vp)),
                out_shardings=quant_pref_out)
        # graftpage's three host-boundary helpers. State-only splice
        # (full prefix hits: the cached pages already hold every
        # prefill column); COW page fork (one page copy — compiles
        # once, traced src/dst); page gather (prefix pages -> the
        # standalone chunk-prefill cache on a partial hit; compiles
        # per (pages, width) pair, pages NOT donated — the shared
        # prefix must survive).
        self._state_insert_jit = jax.jit(
            self._state_insert_fn, out_shardings=state_insert_out,
            donate_argnums=(0, 1, 2, 3, 4) if donate_cache else ())
        self._copy_page_jit = jax.jit(
            self._copy_page_fn, out_shardings=copy_out,
            donate_argnums=(0, 1) if donate_cache else ())
        self._gather_jit = jax.jit(
            self._gather_pages_fn, out_shardings=gather_out,
            static_argnames=("width",))
        # quarantine/deadline eviction: clear a slot's on-device finish
        # gates so the frozen row stops advancing. Compiled lazily on
        # the FIRST eviction — the fault-free path never traces it
        # (disarmed-cost pin: the sentinel compile budgets don't move)
        self._evict_jit = jax.jit(
            self._evict_fn, out_shardings=evict_out,
            donate_argnums=(0, 1) if donate_cache else ())
        # graftspec: the draft+verify horizon is its OWN jitted
        # function — the k=0 dispatch path keeps calling the untouched
        # self._decode, so disarmed spec cannot move the committed
        # non-spec fingerprints, donation lists or compile ladder
        self._decode_spec = None
        self._draft_prefill_jit = None
        self._draft_insert_jit = None
        if self._draft_k:
            spec_donate = ((2, 3, 5, 6, 7, 8, 9, 10)
                           if self._draft_model is not None
                           else (1, 2, 4, 5, 6, 7))
            self._decode_spec = jax.jit(
                self._make_decode_spec(), out_shardings=spec_out,
                static_argnames=("window", "horizon", "draft_k"),
                donate_argnums=spec_donate if donate_cache else ())
            if self._draft_model is not None:
                self._draft_prefill_jit = jax.jit(
                    self._make_draft_prefill(),
                    out_shardings=draft_prefill_out)
                self._draft_insert_jit = jax.jit(
                    self._draft_insert_fn,
                    out_shardings=draft_insert_out,
                    donate_argnums=(0, 1) if donate_cache else ())
        # graftmeter: resident params on the ledger (disarmed: ONE
        # global read — the tree walk too stays behind the check;
        # bytes from host metadata, no device touch). The pool
        # registered its own KV residency at allocation.
        if hbm.active_ledger() is not None:
            hbm.register("serving.params", hbm.tree_nbytes(params),
                         category="params")
        # static cost/memory per compiled decode program, measured
        # lazily the step a (window, horizon) signature first compiles
        # (never on the steady-state path) — see _note_decode_program
        self._program_costs: Dict[Tuple[int, int], dict] = {}
        self.journal = journal
        self.health.to_ready()

    def _build_buckets(self, decode_buckets) -> Tuple[int, ...]:
        """Normalize the decode-window ladder: ascending, capped by and
        terminating at ``s_max`` (the fallback window every request
        fits by admission control)."""
        s_max = self.pool.s_max
        if decode_buckets is None:
            ladder = []
            b = self.min_bucket
            while b < s_max:
                ladder.append(b)
                b *= 2
            ladder.append(s_max)
            return tuple(ladder)
        ladder = sorted({int(b) for b in decode_buckets})
        if ladder and ladder[0] < 1:
            raise ValueError(
                f"decode_buckets must be >= 1, got {ladder[0]}")
        ladder = [b for b in ladder if b <= s_max]
        if not ladder or ladder[-1] != s_max:
            ladder.append(s_max)
        return tuple(ladder)

    # ---- jitted programs ----------------------------------------------
    def _make_decode_horizon(self):
        """``horizon`` masked decode steps over every slot as ONE
        ``lax.scan``, with on-device EOS/budget freezing. ``window``
        (attention prefix) and ``horizon`` (scan length) are the
        jit-statics — the ``buckets x {1, H}`` compile signature; the
        body is the SHARED :func:`...inference.generate._decode_horizon`
        core ``generate()`` decodes on, so the two cannot drift."""
        model = self.model
        cs = _make_cs(self.mesh)
        temperature, top_k, top_p = self._sampling
        attn_impl = self._attn_impl
        block_k = self._decode_block_k
        page_size = self.pool.page_size
        cs_cache = self._make_cs_cache(cs)

        def paged_horizon_step(params, k_pages, v_pages, page_table,
                               positions, last_tokens, active,
                               remaining, eos_ids, key, *, window,
                               horizon):
            # the table is ONE traced operand beside the (window,
            # horizon) statics, read-only inside the scan (allocation
            # is host-side, pre-jit)
            if temperature > 0.0:
                keys = jax.random.split(key, horizon)
            else:  # greedy ignores keys; keep ONE signature per ladder
                keys = jnp.zeros((horizon, 2), jnp.uint32)
            tokens, carry = _decode_horizon(
                model, params, k_pages, v_pages, positions,
                last_tokens, active, remaining, eos_ids, keys, cs=cs,
                cs_cache=cs_cache, window=window, attn_impl=attn_impl,
                block_k=block_k, temperature=temperature, top_k=top_k,
                top_p=top_p, page_table=page_table,
                page_size=page_size)
            return (tokens,) + carry

        return paged_horizon_step

    @staticmethod
    def _make_cs_cache(cs):
        """The sharding constraint that pins a decode program's cache
        operands to the pool's placement: pages ``[L, P, ps, H * Dh]``
        (and an int8 pool's ``[L, P, ps, H]`` scales) on their last
        axis."""
        def cs_cache(c):
            return jax.tree.map(lambda leaf: cs(leaf, *PAGE_SPEC), c)

        return cs_cache

    def _make_decode_spec(self):
        """The speculative twin of :func:`_make_decode_horizon`
        (graftspec): ``horizon`` draft-then-verify passes as ONE
        ``lax.scan`` on the SHARED
        :func:`...inference.generate._decode_horizon` core (its
        ``draft_k`` branch), statics ``(window, horizon, draft_k)`` —
        the ``buckets x {1, H} x {k}`` half of the compile ladder.
        Greedy-only (enforced at construction), so no sample keys
        ride the signature."""
        model = self.model
        cs = _make_cs(self.mesh)
        attn_impl = self._attn_impl
        block_k = self._decode_block_k
        page_size = self.pool.page_size
        draft_model = self._draft_model
        cs_cache = self._make_cs_cache(cs)

        def run(params, k_pages, v_pages, page_table, positions,
                last_tokens, active, remaining, eos_ids, *, window,
                horizon, draft_k, draft_table=None, draft_params=None,
                dk=None, dv=None):
            keys = jnp.zeros((horizon, 2), jnp.uint32)  # greedy
            tokens, carry = _decode_horizon(
                model, params, k_pages, v_pages, positions,
                last_tokens, active, remaining, eos_ids, keys, cs=cs,
                cs_cache=cs_cache, window=window, attn_impl=attn_impl,
                block_k=block_k, page_table=page_table,
                page_size=page_size, draft_k=draft_k,
                draft_table=draft_table,
                draft_model=(draft_model if draft_params is not None
                             else None),
                draft_params=draft_params, draft_k_caches=dk,
                draft_v_caches=dv)
            return (tokens,) + carry

        if draft_model is not None:
            def spec_step(params, draft_params, k_pages, v_pages,
                          page_table, dk, dv, positions, last_tokens,
                          active, remaining, eos_ids, *, window,
                          horizon, draft_k):
                return run(params, k_pages, v_pages, page_table,
                           positions, last_tokens, active, remaining,
                           eos_ids, window=window, horizon=horizon,
                           draft_k=draft_k, draft_params=draft_params,
                           dk=dk, dv=dv)
        else:
            def spec_step(params, k_pages, v_pages, page_table,
                          positions, last_tokens, active, remaining,
                          eos_ids, draft_table, *, window, horizon,
                          draft_k):
                return run(params, k_pages, v_pages, page_table,
                           positions, last_tokens, active, remaining,
                           eos_ids, window=window, horizon=horizon,
                           draft_k=draft_k, draft_table=draft_table)
        return spec_step

    def _make_draft_prefill(self):
        """Whole-prompt prefill of the DRAFT model (graftspec) — the
        shared ``_prefill`` pass, caches only (the target's prefill
        already sampled tok0). Compiles once per prompt bucket, like
        the target's prefill."""
        draft_model = self._draft_model

        def prefill(dparams, prompt):
            _x, k_pref, v_pref = _prefill(draft_model, dparams, prompt,
                                          prompt.shape[1])
            return k_pref, v_pref

        return prefill

    @staticmethod
    def _draft_insert_fn(dk, dv, k_pref, v_pref, slot):
        """Splice a draft-model prefill into slot ``slot`` of the
        draft caches (graftspec). Stale columns beyond the prompt stay
        masked by the position gate until the draft's own decode
        writes overwrite them — the same invariant as the target
        splice."""
        s_max = dk.shape[2]
        if k_pref.shape[2] > s_max:
            k_pref = jax.lax.slice_in_dim(k_pref, 0, s_max, axis=2)
            v_pref = jax.lax.slice_in_dim(v_pref, 0, s_max, axis=2)
        dk = jax.lax.dynamic_update_slice(dk, k_pref, (0, slot, 0, 0, 0))
        dv = jax.lax.dynamic_update_slice(dv, v_pref, (0, slot, 0, 0, 0))
        return dk, dv

    def _spec_admit(self, request: Request, slot: int,
                    length: int) -> None:
        """Per-admission graftspec hook, called after the target
        splice on EVERY admission path (whole, chunked, prefix hits):
        self-drafting rebuilds the slot's n-gram index from the
        request's history; draft-model mode prefills the draft on the
        (bucket-padded) prompt and splices its caches. Failures raise
        into the caller's quarantine path — the request fails named,
        the engine keeps serving."""
        if self._drafter is not None:
            self._drafter.note_history(
                slot, list(request.prompt) + list(request.tokens))
            return
        if self._draft_model is None:
            return
        pool = self.pool
        bucket = bucket_length(length, self.min_bucket, pool.s_max)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :length] = request.prompt[:length]

        def prefill_once():
            with expected_transfer("draft-model prompt upload at "
                                   "admission (graftspec)"):
                k_pref, v_pref = self._draft_prefill_jit(
                    self._draft_params, padded)
                return k_pref, v_pref

        with graftscope.span("spec.draft_prefill", cat="serving",
                             req=request.uid, bucket=bucket):
            k_pref, v_pref = self._attempted(prefill_once)
        record_jit_key(self._draft_prefill_jit,
                       ("draft_prefill", bucket))

        def splice_once():
            with expected_transfer("draft-cache splice at admission "
                                   "(graftspec, scalar H2D)"):
                return self._donated(lambda: self._draft_insert_jit(
                    self._draft_k_caches, self._draft_v_caches,
                    k_pref, v_pref, np.int32(slot)))

        self._draft_k_caches, self._draft_v_caches = self._attempted(
            splice_once)

    def _make_prefill(self):
        """Whole-prompt prefill-on-join: the SHARED ``_prefill`` pass on
        one right-padded prompt + first-token sampling (``generate``'s
        ``tok0``). Causality makes right-pad columns invisible to the
        real prefix, so no masks are needed; compiles once per bucket
        size (the prompt's padded shape)."""
        model, family = self.model, self._family
        cs = _make_cs(self.mesh)
        temperature, top_k, top_p = self._sampling

        def cs_cache(c):
            return cs(c, None, None, None, "model", None)

        def prefill(params, prompt, length, key):
            x, k_pref, v_pref = family.prefill(
                model, params, prompt, cs=cs, cs_cache=cs_cache)
            x_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1,
                                                  axis=1)
            logits = family.logits(model, params, x_last, cs)[:, 0]
            tok0 = _sample(logits, temperature, top_k, top_p, key)
            return tok0[0].astype(jnp.int32), k_pref, v_pref

        return prefill

    def _make_chunk_prefill(self):
        """One ``[1, chunk]`` slice of an incremental prefill: writes
        the chunk's K/V at ``[start, start+chunk)`` into the standalone
        prefill cache and attends each token to its causal prefix
        (``inference.generate._block_chunk_prefill``). ONE static shape
        per (chunk, cache-width) pair regardless of prompt length or
        chunk index — ``start`` is traced. A family with a chunk
        attention kernel runs it in the decode step's implementation."""
        model, family = self.model, self._family
        cs = _make_cs(self.mesh)
        attn_impl = self._attn_impl

        def cs_cache(c):
            return cs(c, None, None, None, "model", None)

        def chunk(params, k_pref, v_pref, tokens, start):
            return family.chunk(model, params, k_pref, v_pref, tokens,
                                start, cs=cs, cs_cache=cs_cache,
                                attn_impl=attn_impl)

        return chunk

    def _make_tok0(self):
        """First-token sampling off the final chunk's activations —
        ``generate``'s ``tok0`` math on a dynamic within-chunk index."""
        model, family = self.model, self._family
        cs = _make_cs(self.mesh)
        temperature, top_k, top_p = self._sampling

        def tok0_fn(params, x, idx, key):
            x_last = jax.lax.dynamic_slice_in_dim(x, idx, 1, axis=1)
            logits = family.logits(model, params, x_last, cs)[:, 0]
            tok = _sample(logits, temperature, top_k, top_p, key)
            return tok[0].astype(jnp.int32)

        return tok0_fn

    @staticmethod
    def _paged_insert_fn(k_pages, v_pages, positions, last_tokens,
                         active, budgets, eos_ids, k_pref, v_pref,
                         write_ids, slot, length, tok0, budget, eos):
        """Splice a prefilled request into slot ``slot`` (graftpage):
        the standalone prefill cache ``[L, 1, W, *row]`` is cut into
        page blocks (a reshape: a page is ``ps`` whole rows, ``[ps,
        prod(row)]``) and scattered into the donated pools, in place,
        at ``write_ids`` — the column-ordered page targets the HOST
        chose (fresh pages for the columns this request computed; the
        SCRATCH page 0 for columns a shared prefix already holds —
        their stale re-write is discarded — and for pure-pad
        overshoot). The position counter starts at the prompt length,
        the pending token is the prefill's first sample, and the
        on-device finish gates arm — ``budget`` decode tokens
        remaining (``max_new_tokens - 1``; the first token came from
        prefill) and the stop id (``-1`` = none). Pad/stale columns
        beyond ``length`` are masked until the decode position reaches
        (and overwrites) them. Compiles once per prefill width (the
        ``write_ids`` length is width-derived). graftquant: when the
        pool is int8, ``k_pref``/``v_pref`` arrive ALREADY quantized
        (``_quant_pref_jit`` or a quantized transfer); the pair's
        scales ``[L, 1, W, H]`` follow the same rule into ``[L, P,
        ps, H]``."""
        ps = k_pages.shape[2]
        n = write_ids.shape[0]
        pad = n * ps - k_pref.shape[2]

        def to_pages(c):  # [L, 1, W, *row] -> [L, n, ps, prod(row)]
            if pad:  # width not a page multiple: pad-only columns
                c = jnp.pad(c, ((0, 0), (0, 0), (0, pad))
                            + ((0, 0),) * (c.ndim - 3))
            return c.reshape(c.shape[0], n, ps,
                             int(np.prod(c.shape[3:], dtype=int)))

        def splice(pool, pref):
            return pool.at[:, write_ids].set(to_pages(pref))

        k_pages = jax.tree.map(splice, k_pages, k_pref)
        v_pages = jax.tree.map(splice, v_pages, v_pref)
        positions = positions.at[slot].set(length)
        last_tokens = last_tokens.at[slot].set(tok0)
        active = active.at[slot].set(True)
        budgets = budgets.at[slot].set(budget)
        eos_ids = eos_ids.at[slot].set(eos)
        return (k_pages, v_pages, positions, last_tokens, active,
                budgets, eos_ids)

    @staticmethod
    def _ring_insert_fn(full_pages, ring_pages, positions, last_tokens,
                        active, budgets, eos_ids, full_pref, ring_pref,
                        write_ids, slot, length, tok0, budget, eos):
        """:meth:`_paged_insert_fn` for a family with two kinds of
        layer (``kv_pages``): the full layers' standalone cache goes
        into the paged pool at ``write_ids`` as there; of the sliding
        layers' ``[L, 1, W, row]`` only the LAST WINDOW is kept: entry
        ``j`` of slot ``slot``'s ring takes the newest page ``g <=
        (length - 1) // ps`` of the prompt with ``g % ring == j`` (the
        entry the decode steps will look for it at), one contiguous
        block of ``ring`` pages into the donated ring pool. An entry
        with no such page yet (a prompt shorter than the ring) takes
        page 0's rows, which no step reads before it writes them."""
        ps = full_pages.shape[2]
        n = write_ids.shape[0]
        ring = ring_pages.shape[1] // positions.shape[0]
        pad = n * ps - full_pref.shape[2]

        def to_pages(c):  # [L, 1, W, row] -> [L, n, ps, row]
            if pad:
                c = jnp.pad(c, ((0, 0), (0, 0), (0, pad), (0, 0)))
            return c.reshape(c.shape[0], n, ps, c.shape[3])

        full_pages = full_pages.at[:, write_ids].set(to_pages(full_pref))
        last = (length - 1) // ps
        newest = last - jnp.mod(last - jnp.arange(ring), ring)
        ring_pages = jax.lax.dynamic_update_slice(
            ring_pages,
            jnp.take(to_pages(ring_pref), jnp.clip(newest, 0, n - 1),
                     axis=1),
            (0, slot * ring, 0, 0))
        return ((full_pages, ring_pages)
                + ServingEngine._state_insert_fn(
                    positions, last_tokens, active, budgets, eos_ids,
                    slot, length, tok0, budget, eos))

    @staticmethod
    def _state_insert_fn(positions, last_tokens, active, budgets,
                         eos_ids, slot, length, tok0, budget, eos):
        """FULL prefix hit (graftpage): every prefill column already
        lives in cached pages, so the splice touches only the slot's
        scalar decode state — the near-zero-TTFT path."""
        positions = positions.at[slot].set(length)
        last_tokens = last_tokens.at[slot].set(tok0)
        active = active.at[slot].set(True)
        budgets = budgets.at[slot].set(budget)
        eos_ids = eos_ids.at[slot].set(eos)
        return positions, last_tokens, active, budgets, eos_ids

    @staticmethod
    def _copy_page_fn(k_pages, v_pages, src, dst):
        """Copy-on-write fork: duplicate ONE page (the shared
        prefix's partial last page) into a private page the joiner's
        first divergent write (its column ``L``) may land in. One
        compiled program (``src``/``dst`` traced); the only data moved
        is the single page — everything else about a prefix hit is
        copy-free table wiring (cf. arXiv:2112.01075 on keeping
        redistribution gather-free)."""
        def one(leaf):
            # an int8 pair COW-forks BOTH leaves: the forked page keeps
            # its exact quantized values (no requant round-trip)
            blk = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=1)
            return jax.lax.dynamic_update_slice(leaf, blk, (0, dst, 0, 0))

        return jax.tree.map(one, (k_pages, v_pages))

    def _gather_pages_fn(self, k_pages, v_pages, ids, *, width):
        """PARTIAL prefix hit: materialize the ``len(ids)`` shared
        prefix pages into the leading columns of a standalone
        chunk-prefill cache of ``width`` columns (the suffix chunks
        attend over it, then the splice writes ONLY the suffix pages
        back). Pages are NOT donated — the shared prefix lives on.
        graftquant pages DEQUANTIZE here: the standalone chunk cache
        is model-dtype in both modes (the chunk program's signature
        never forks), and the shared prefix pages themselves are not
        re-written at splice time, so no requant error accrues."""
        dtype = self.model.dtype
        (_, row, _), _ = serving_family(self.model).cache_rows(self.model)

        def rows(leaf, last):  # [L, P, ps, .] -> [L, 1, k * ps, *last]
            g = jnp.take(leaf, ids, axis=1)
            return g.reshape((g.shape[0], 1, -1) + tuple(last))

        def one(pages):
            if isinstance(pages, QuantizedKV):
                g = dequantize_kv(QuantizedKV(rows(pages.data, row),
                                              rows(pages.scale, row[:-1])),
                                  dtype)
            else:
                g = rows(pages, row)
            pad = width - g.shape[2]
            return jnp.pad(
                g, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * len(row))

        return one(k_pages), one(v_pages)

    @staticmethod
    def _evict_fn(active, budgets, slot):
        """Scrub one slot's on-device finish gates (quarantine /
        deadline eviction): the row freezes exactly like an EOS'd one
        — masked every step, its stale KV columns invisible until the
        next tenant's insert overwrites them (the same invariant slot
        recycling already rests on — a quarantined request is never
        resurrected with stale cache state)."""
        return active.at[slot].set(False), budgets.at[slot].set(0)

    # ---- fault domains (graftfault) -----------------------------------
    def _donated(self, fn):
        """Execute a jitted program whose inputs DONATE the pool's
        arrays (``_decode``/``_insert_jit``/``_evict_jit`` on TPU).
        Once the launch starts, the donated buffers are consumed — a
        mid-execution failure (XlaRuntimeError, device OOM, even an
        OSError-shaped one) leaves the pool unusable for EVERY
        resident request, not just the one being worked on, so it is
        classified as the engine-fatal named ``PoolPoisonedError``:
        quarantine would keep "serving" from deleted buffers and a
        retry would replay against them. Injected faults fire BEFORE
        this wrapper (nothing is donated yet) and keep their
        transient/retry semantics; the CPU backend never donates, so
        there ordinary per-request classification applies."""
        if not self._donate_cache:
            return fn()
        try:
            return fn()
        except GraftFaultError:
            raise
        except Exception as e:
            # flight-record FIRST: the ring holds the dispatch/drain
            # events leading into the poisoned launch — exactly what
            # the postmortem needs and exactly what a propagating
            # exception is about to make unreachable
            graftscope.emit("engine.fatal", cat="fault",
                            error="PoolPoisonedError",
                            cause=type(e).__name__)
            graftscope.flight_dump(
                f"PoolPoisonedError: {type(e).__name__}: {e}")
            raise PoolPoisonedError(
                "a pool-donating program failed mid-execution "
                f"({type(e).__name__}: {e}); the KV slot pool's "
                "buffers are consumed — discard this engine replica "
                "(and the requests it held), it cannot keep serving"
            ) from e

    def _attempted(self, fn):
        """Run one host-side operation under the engine's bounded
        retry policy (transient OSError-family failures only — incl.
        injected ``FaultInjected``); every absorbed retry is counted
        and opens the post-fault horizon-collapse cooldown."""
        return retry_with_backoff(
            fn, attempts=self._dispatch_retries,
            base_delay_s=self._retry_backoff_s,
            on_retry=self._note_retry)

    def _note_retry(self, attempt: int, exc: BaseException) -> None:
        self.metrics.record_retry()
        self._cooldown = self._cooldown_steps

    def _attempted_engine(self, fn, what: str):
        """Engine-wide operations (decode dispatch, readback): retries
        exhausted means the whole fault domain is down — fail FAST
        with a named error, never a hang or a stale engine."""
        try:
            return self._attempted(fn)
        except GraftFaultError:
            raise
        except OSError as e:
            raise GraftFaultError(
                f"{what} still failing after {self._dispatch_retries} "
                f"attempt(s): {type(e).__name__}: {e}") from e

    def _quarantine(self, request: Request, error: BaseException,
                    reason: str = "error",
                    slot: Optional[int] = None) -> None:
        """Evict one request as FAILED with its error recorded. If it
        holds a slot, the slot's device gates are scrubbed and the
        slot is recycled; tokens it may still emit from already-
        dispatched horizons are dropped at drain (the ``_running``
        identity check). The engine keeps serving everyone else."""
        if slot is None:
            slot = request.slot
        if slot is not None:
            self._scrub_slot(slot)
            if self._running.get(slot) is request:
                del self._running[slot]
            self.pool.release(slot)
        self.scheduler.fail(request, error, reason)
        request.finish_time = time.perf_counter()
        self.metrics.record_failure()
        if self.journal is not None:
            # terminal in the WAL too: a quarantined request is
            # accounted, never redelivered as if the crash ate it
            self.journal.record_failed(request)
        graftscope.emit("request.failed", cat="request",
                        req=request.uid, reason=reason,
                        error=type(error).__name__,
                        tokens=len(request.tokens))

    def _poisoned(self, request: Request, error: BaseException,
                  slot: Optional[int] = None) -> None:
        """Classify a per-request failure: transient classes (retries
        already exhausted) and ordinary exceptions quarantine the
        request; a FATAL injected/declared fault propagates — the
        fail-fast half of the contract."""
        if (isinstance(error, GraftFaultError)
                and not isinstance(error, (FaultInjected,
                                           DeadlineExceeded))):
            raise error
        self._quarantine(request, error, slot=slot)

    def _scrub_slot(self, slot: int) -> None:
        pool = self.pool
        with expected_transfer("slot-scrub control upload on "
                               "quarantine/eviction (scalar H2D, "
                               "fault path only)"):
            pool.active, pool.budgets = self._donated(
                lambda: self._evict_jit(
                    pool.active, pool.budgets, np.int32(slot)))

    def _expire_deadlines(self) -> None:
        """Fail every request past its per-request deadline — queued,
        mid-prefill (chunks pending or first token unread), or running
        (evicted + slot scrubbed).
        Free when no deadline-bearing request was ever submitted (the
        default config): the sticky flag skips the per-step scans."""
        if not self._deadlines_seen:
            return
        now = time.perf_counter()
        for request in self.scheduler.expire(now):
            self._quarantine(
                request,
                DeadlineExceeded(
                    f"request {request.uid} exceeded its "
                    f"{request.deadline_s:.3g}s deadline in the queue"),
                reason="deadline")
        for pend in self._joining():
            if pend.request.overdue(now):
                self._drop_joining(pend)
                self._quarantine(
                    pend.request,
                    DeadlineExceeded(
                        f"request {pend.request.uid} exceeded its "
                        f"{pend.request.deadline_s:.3g}s deadline "
                        f"mid-prefill"),
                    reason="deadline")
        for slot, request in list(self._running.items()):
            if request.overdue(now):
                self._quarantine(
                    request,
                    DeadlineExceeded(
                        f"request {request.uid} exceeded its "
                        f"{request.deadline_s:.3g}s deadline after "
                        f"{len(request.tokens)} token(s)"),
                    reason="deadline", slot=slot)

    # ---- graftmeter: static decode-program analysis -------------------
    def decode_program_analysis(self, window: int, horizon: int) -> dict:
        """XLA's cost + memory analyses of the ``(window, horizon)``
        decode program — the graftmeter record serving efficiency is
        attributed against (``serving_bench`` MFU, the ledger's
        per-bucket temp gauges). AOT lowering on abstract shapes:
        compiles but never executes, never enters the jit trace cache
        (the recompile sentinels cannot see it), and is memoized per
        signature. On TPU the persistent compilation cache makes the
        duplicate compile ~free; on the hot path it is only reached
        the step a signature FIRST compiles anyway."""
        key = (int(window), int(horizon))
        if key not in self._program_costs:
            from ..analysis.meter import costs_record
            from ..utils.compile_cache import lowered_program_analysis

            # under TP the executed program's GSPMD partition is part
            # of its identity: carry each arg's real sharding into the
            # abstract avals, or the metered program (collectives,
            # temp allocation) would be a replicated-input variant of
            # the one the dispatcher actually runs
            keep_sharding = self.mesh is not None

            def sds(x):
                sharding = (getattr(x, "sharding", None)
                            if keep_sharding else None)
                if sharding is not None:
                    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                sharding=sharding)
                return jax.ShapeDtypeStruct(x.shape, x.dtype)

            args = self._decode_avals(
                sds, jax.tree.map(sds, self.params))
            _compiled, cost, memory = lowered_program_analysis(
                self._decode, *args, window=key[0], horizon=key[1])
            self._program_costs[key] = costs_record(cost, memory)
        return self._program_costs[key]

    def _decode_avals(self, sds, params) -> tuple:
        """The ``_decode`` program's operands as abstract values
        (``sds``: array -> ``jax.ShapeDtypeStruct``). The pools go
        through ``tree.map``: a graftquant pool's are QuantizedKV
        pairs (two aval leaves), a model-dtype pool's plain arrays."""
        pool = self.pool
        return (params, jax.tree.map(sds, pool.k_pages),
                jax.tree.map(sds, pool.v_pages),
                sds(pool.device_table()), sds(pool.positions),
                sds(pool.last_tokens), sds(pool.active),
                sds(pool.budgets), sds(pool.eos_ids),
                jax.ShapeDtypeStruct((2,), jnp.uint32))

    def _note_decode_program(self, window: int, horizon: int) -> None:
        """A decode signature just compiled: put its temp HBM on the
        armed ledger (per-bucket decode-program temps — the residency
        the bucket ladder trades against window size). Best-effort BY
        CONTRACT: a failed measurement must never take down a dispatch
        that already succeeded — reported to stderr, never raised."""
        if hbm.active_ledger() is None:
            return
        try:
            costs = self.decode_program_analysis(window, horizon)
            mem = costs.get("memory") or {}
            hbm.register(
                f"serving.decode_temp_w{window}_h{horizon}",
                int(mem.get("temp_bytes", 0)), category="temps",
                window=window, horizon=horizon)
        except Exception as e:  # noqa: BLE001
            import sys

            print(f"graftmeter: decode-program metering failed for "
                  f"(window={window}, horizon={horizon}): "
                  f"{type(e).__name__}: {e}", file=sys.stderr)

    # ---- compile counters ---------------------------------------------
    @property
    def decode_step_compiles(self) -> int:
        """Distinct compiled decode-step programs (<= the bucket
        ladder's length; == the buckets the traffic touched)."""
        return jit_cache_size(self._decode)

    @property
    def decode_windows(self) -> Tuple[int, ...]:
        """The window buckets that actually compiled, in first-use
        order (``compile_cache.jit_cache_keys``; a window may repeat
        when both horizon rungs compiled at it — ``decode_programs``
        has the full pairs)."""
        return tuple(w for tag, w, _ in jit_cache_keys(self._decode)
                     if tag == "decode")

    @property
    def decode_programs(self) -> Tuple[Tuple[int, int], ...]:
        """``(window, horizon)`` pairs that actually compiled, in
        first-use order — the ladder-bounded program set, never more
        than ``len(decode_buckets) * 2`` entries."""
        return tuple((w, h) for tag, w, h in jit_cache_keys(self._decode)
                     if tag == "decode")

    @property
    def spec_programs(self) -> Tuple[Tuple[int, int, int], ...]:
        """``(window, horizon, draft_k)`` SPECULATIVE programs that
        actually compiled (graftspec), in first-use order — the
        ``x {k on}`` half of the ladder; the k=0 half is
        ``decode_programs``, untouched by arming spec."""
        if self._decode_spec is None:
            return ()
        return tuple((w, h, k) for tag, w, h, k in
                     jit_cache_keys(self._decode_spec)
                     if tag == "decode_spec")

    @property
    def draft_k(self) -> int:
        """The configured max draft length (0 = spec disarmed)."""
        return self._draft_k

    @property
    def spec_accept_ema(self) -> Optional[float]:
        """Decayed mean accepted/k per verify pass (None before the
        first speculative drain) — pick_draft_k's collapse signal."""
        return self._accept_ema

    @property
    def decode_horizon(self) -> int:
        """The configured max fused-decode horizon (H_max)."""
        return self._horizon_max

    @property
    def decode_attn(self) -> str:
        """The decode attention implementation in use — ``"pallas"`` or
        ``"xla"``, ``"auto"`` already resolved from the platform."""
        return self._attn_impl

    @property
    def donate_cache(self) -> bool:
        """Whether the jitted programs donate the KV pool (every
        backend but the CPU's, which lacks donation)."""
        return self._donate_cache

    @property
    def decode_buckets(self) -> Tuple[int, ...]:
        """The configured window ladder (ends at ``s_max``)."""
        return self._buckets

    @property
    def prefill_compiles(self) -> int:
        """Distinct compiled whole-prompt prefill programs (== buckets
        seen)."""
        return jit_cache_size(self._prefill_jit)

    @property
    def chunk_prefill_compiles(self) -> int:
        """Distinct compiled chunk-prefill programs (== (chunk, width)
        pairs seen)."""
        return jit_cache_size(self._chunk_jit)

    # ---- request lifecycle --------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               eos_id: Optional[int] = None, uid=None,
               deadline_s: Optional[float] = None) -> Request:
        """Queue a request (FIFO). Raises ValueError when it can never
        fit a slot, ``QueueFull`` at the queue bound. ``deadline_s``
        bounds the request's total wall time from submission; past it
        the engine evicts it as FAILED (``DeadlineExceeded``)."""
        request = Request(prompt, max_new_tokens,
                          self.eos_id if eos_id is None else eos_id,
                          uid, deadline_s=deadline_s)
        return self.enqueue(request)

    def submit_retrying(self, prompt: Sequence[int],
                        max_new_tokens: int, *, attempts: int = 8,
                        backoff_s: float = 0.0,
                        eos_id: Optional[int] = None, uid=None,
                        deadline_s: Optional[float] = None,
                        events_out: Optional[list] = None) -> Request:
        """The tested retry path behind ``QueueFull``'s "shed load or
        retry" advice: bounded retry-with-backoff that STEPS the
        engine between attempts, so the bounded queue can actually
        drain instead of spinning on a full one. The request keeps its
        first attempt's ``submit_time`` (TTFT includes backpressure
        wait); the final ``QueueFull`` propagates — bounded means
        bounded, and every rejected attempt is already counted in
        ``ServingMetrics.requests_shed``.

        The drain steps produce token events like any other
        :meth:`step` — an event-driven caller passes ``events_out``
        (appended in order) or those completions would be invisible to
        its own event loop; callers that track request state instead
        can ignore it."""
        request = Request(prompt, max_new_tokens,
                          self.eos_id if eos_id is None else eos_id,
                          uid, deadline_s=deadline_s)

        def drain_a_step(attempt: int, exc: BaseException) -> None:
            events = self.step()
            if events_out is not None:
                events_out.extend(events)

        return retry_with_backoff(
            lambda: self.enqueue(request), attempts=attempts,
            base_delay_s=backoff_s, retry_on=(QueueFull,),
            on_retry=drain_a_step)

    def enqueue(self, request: Request) -> Request:
        """Queue a pre-built :class:`Request`. ``submit_time`` is
        stamped on the FIRST attempt and survives ``QueueFull`` retries,
        so TTFT honestly includes backpressure wait. Every rejection at
        the queue bound is counted (``requests_shed``) — load-shedding
        is part of the degradation ladder, not a silent drop."""
        if request.submit_time is None:
            request.submit_time = time.perf_counter()
        if not self.health.ready:
            # graftheal: admission is CLOSED outside READY — a
            # draining/dead engine sheds instead of accepting work it
            # cannot promise to finish (QueueFull is the backpressure
            # signal callers already handle; the reason names the
            # drain so a retry loop knows not to spin on this replica)
            self.metrics.record_shed()
            graftscope.emit("request.shed", cat="request",
                            req=request.uid,
                            reason=self.health.state)
            raise QueueFull(
                f"admission closed: engine {self.health.state.upper()}"
                f" ({self.health.reason}); submit to another replica")
        if request.deadline_s is not None:
            self._deadlines_seen = True
        if request.prompt and (
                min(request.prompt) < 0
                or max(request.prompt) >= self.model.vocab_size):
            raise ValueError(
                f"prompt token ids must be in [0, vocab_size="
                f"{self.model.vocab_size})")
        total = len(request.prompt) + request.max_new_tokens
        if request.prompt and total <= self.pool.s_max:
            # never-fits for the PAGE pool is a submission error, like
            # the scheduler's s_max check below, which speaks first
            # (transient pressure is the admission gate's hold, not
            # this)
            need = PagePool.pages_for(total, self.pool.page_size)
            if need > self.pool.num_pages - 1:
                raise ValueError(
                    f"request needs {need} page(s); the pool holds "
                    f"{self.pool.num_pages - 1} allocatable "
                    f"(num_pages={self.pool.num_pages} incl. scratch)")
        try:
            submitted = self.scheduler.submit(request)
        except QueueFull:
            self.metrics.record_shed()
            graftscope.emit("request.shed", cat="request",
                            req=request.uid)
            raise
        if self.journal is not None:
            # WAL the admission BEFORE any work happens on it: a crash
            # from here on redelivers the request (idempotent by uid —
            # a redelivered request re-admitting appends nothing)
            self.journal.record_admit(submitted)
        graftscope.emit("request.submit", cat="request",
                        req=request.uid,
                        prompt_len=len(request.prompt),
                        max_new_tokens=request.max_new_tokens)
        return submitted

    def _next_key(self) -> jax.Array:
        """Per-call PRNG key (sampling only; greedy programs take the
        constant zero key ``generate`` uses, keeping one signature)."""
        if self._sampling[0] <= 0.0:
            return self._rng
        self._key_idx += 1
        return jax.random.fold_in(self._rng, self._key_idx)

    def _finished(self, request: Request, token: int) -> Optional[str]:
        if request.eos_id is not None and token == request.eos_id:
            return "eos"
        if len(request.tokens) >= request.max_new_tokens:
            return "length"
        return None

    def _complete(self, request: Request, reason: str) -> None:
        request.finish_time = time.perf_counter()
        self.scheduler.complete(request, reason)
        self.metrics.record_completion(len(request.tokens))
        graftscope.emit("request.done", cat="request",
                        req=request.uid, reason=reason,
                        tokens=len(request.tokens))

    def _pop_admission(self) -> Optional[Request]:
        """FIFO head into prefill: stamp admission (the queue-wait half
        of TTFT) the moment its prefill work is about to start."""
        request = self.scheduler.next_to_admit()
        if request is not None:
            request.admit_time = time.perf_counter()
            self.metrics.record_admission(
                request.admit_time - request.submit_time)
            graftscope.emit(
                "request.admit", cat="request", req=request.uid,
                queue_wait_s=request.admit_time - request.submit_time)
        return request

    def _first_token(self, request: Request, token: int,
                     events: List) -> Optional[int]:
        """Shared tail of both prefill paths: stamp TTFT, record the
        token, retire an already-finished request or acquire its slot
        (returned; None = retired)."""
        request.first_token_time = time.perf_counter()
        self.metrics.record_first_token(
            request.first_token_time - request.submit_time)
        graftscope.emit(
            "request.first_token", cat="request", req=request.uid,
            ttft_s=request.first_token_time - request.submit_time)
        request.tokens.append(token)
        reason = self._finished(request, token)
        if reason is not None:
            self._complete(request, reason)
            events.append((request, token, True))
            return None
        slot = self.pool.acquire()
        led = life.active_ledger()
        if led is not None:
            led.tag("slot", (id(self.pool), slot), request.uid)
        request.slot = slot
        self._running[slot] = request
        events.append((request, token, False))
        return slot

    def _admit(self) -> List[Tuple[Request, int, bool]]:
        """Move FIFO-head requests toward slots, in two stages a step
        apart so that the host never waits for a program it has just
        dispatched. First the prefills dispatched LAST step give up
        their first tokens and are spliced into slots
        (:meth:`_finish_prefills`); then whole-prompt mode dispatches
        one prefill call for every slot still free, and chunked mode
        advances the single in-flight :class:`PrefillPlan` by EXACTLY
        one chunk (the bounded stall the mode exists for) — dispatch
        only, nothing is read. Requests past their deadline are failed
        first, inside the same ``engine.admit`` span: the span is all
        the scheduling a step does before it can dispatch."""
        with graftscope.span("engine.admit", cat="serving") as admit_span:
            self._expire_deadlines()
            events: List[Tuple[Request, int, bool]] = []
            self._finish_prefills(events)
            queued = self.scheduler.queue_depth
            if self._prefill_chunk is None:
                self._admit_whole(events)
            else:
                self._admit_chunked(events)
            depth = self.scheduler.queue_depth
            admit_span.note(admitted=queued - depth, queue_depth=depth)
        return events

    def _finish_prefills(self, events: List) -> None:
        """Stage two of an admission, one step after stage one: read
        the first token of every prefill dispatched last step (the
        TTFT boundary), retire the request there or acquire its slot,
        and splice. The prefill ran right behind the block that was in
        flight when it was dispatched, so the read waits for little or
        nothing, and the device has the NEXT block to run meanwhile;
        the insert queues behind that block and ahead of this step's
        dispatch, so the tenant joins the block dispatched now. Shared
        tail of both prefill paths."""
        unread, self._unread = self._unread, []
        for pend in unread:
            request = pend.request

            def tok0_once(pend=pend):
                # per-request work on a value no program donates:
                # retry, then quarantine just this request
                maybe_fault(_SITE_TOK0)
                with expected_transfer("first-token readback (the TTFT "
                                       "boundary)"):
                    return int(pend.tok0)

            try:
                with graftscope.span("serving.prefill_tok0",
                                     cat="serving", req=request.uid):
                    tok0_host = self._attempted(tok0_once)
            except Exception as e:
                self._abort_prep(pend.prep)
                self._poisoned(request, e)
                continue
            slot = self._first_token(request, tok0_host, events)
            if slot is None:
                self._abort_prep(pend.prep)
                continue
            try:
                self._insert(request, slot, pend.k_pref, pend.v_pref,
                             pend.length, pend.tok0, prep=pend.prep)
            except Exception as e:
                self._abort_prep(pend.prep)
                self._poisoned(request, e, slot=slot)

    # ---- paged admission (graftpage) ----------------------------------
    def _paged_prep_head(self):
        """Reserve pages for the FIFO head BEFORE popping it. Returns
        a :class:`_PagedPrep` (pages + prefix-cache outcome reserved),
        ``None`` (queue empty), ``"hold"`` (not enough free pages —
        the head STAYS QUEUED; prefix-cache entries were already shed
        LRU-first; running work frees pages at every completion), or
        ``"retry"`` (the head could NEVER be satisfied — quarantined
        named ``PagePoolExhausted`` — and admission may look at the
        next head). Host-only: free-list pops and refcounts, no device
        work."""
        pool = self.pool
        head = self.scheduler.peek()
        if head is None:
            return None
        n_total = PagePool.pages_for(
            len(head.prompt) + head.max_new_tokens, pool.page_size)
        while True:
            entry, k = ((None, 0) if self._prefix_cache is None
                        else self._prefix_cache.lookup(head.prompt))
            full = (entry is not None
                    and entry.tokens == tuple(head.prompt)
                    and entry.tok0 is not None)
            if not full:
                # a partial hit must leave >= 1 suffix token to
                # prefill (it provides tok0); a prompt that IS a
                # page-aligned prefix of a longer cached one caps here
                k = min(k, (len(head.prompt) - 1) // pool.page_size)
            needed = n_total - k
            if pool.free_pages >= needed:
                break
            # shed cache before holding traffic: LRU entries whose
            # pages no live slot shares actually free pages. Re-run
            # the lookup after each eviction — the shed may have taken
            # the very entry the hit planned to reuse (lookups keep it
            # MRU, so it goes last).
            if not (self._prefix_cache is not None
                    and self._prefix_cache.evict_lru()):
                break
        if pool.free_pages < needed:
            if (not self._running and not self._joining()
                    and not self._blocks
                    and not (self._prefix_cache
                             and len(self._prefix_cache))):
                # nothing in flight will ever free a page: fail the
                # head NAMED, keep serving the queue behind it
                request = self._pop_admission()
                self._quarantine(request, PagePoolExhausted(
                    f"request {request.uid} needs {needed} page(s); "
                    f"only {pool.free_pages} exist free with nothing "
                    "in flight to free more (num_pages="
                    f"{pool.num_pages})"), reason="pages")
                return "retry"
            if self._held_uid != head.uid:
                # count (and timeline) the TRANSITION into held, not
                # every step the head stays there — one deferred
                # admission is one hold, however long the wait
                self._held_uid = head.uid
                self.metrics.record_page_hold()
                graftscope.emit("request.held", cat="request",
                                req=head.uid, pages_needed=needed,
                                pages_free=pool.free_pages)
            return "hold"
        self._held_uid = None  # the head is getting pages
        shared = list(entry.shared_ids[:k]) if entry is not None else []
        pool.incref(shared)
        fork_src = None
        if full and len(head.prompt) % pool.page_size:
            fork_src = entry.partial_id
            pool.incref([fork_src])
        fresh = pool.alloc_pages(needed)
        mode = "full" if full else ("partial" if k else "miss")
        return _PagedPrep(mode, entry, k, shared, fresh, fork_src,
                          n_total)

    def _abort_prep(self, prep) -> None:
        """Return a reservation's pages (quarantined admission,
        finished-at-first-token, failed prefill)."""
        pool = self.pool
        pool.decref(prep.shared_ids)
        pool.decref(prep.fresh_ids)
        if prep.fork_src is not None:
            pool.decref([prep.fork_src])
        prep.shared_ids, prep.fresh_ids, prep.fork_src = [], [], None

    def _joining(self) -> List[_PendingPrefill]:
        """Admissions in flight, neither queued nor running: the
        chunked prefill in progress and every request whose prefill is
        dispatched and whose first token is unread."""
        return (([] if self._pending is None else [self._pending])
                + self._unread)

    def _drop_joining(self, pend: _PendingPrefill) -> None:
        """Detach one admission in flight, returning its pages (every
        quarantine/drain path that clears one goes through here; its
        device values are simply dropped)."""
        if self._pending is pend:
            self._pending = None
        else:
            self._unread.remove(pend)
        self._abort_prep(pend.prep)

    def _copy_page(self, src: int, dst: int) -> None:
        """One COW page fork on the device (donated pages — engine-
        fatal if it dies mid-flight, like every pool-donating
        program)."""
        pool = self.pool

        def copy_once():
            with expected_transfer("page-fork control upload "
                                   "(scalar H2D, prefix-hit path)"):
                return self._donated(lambda: self._copy_page_jit(
                    pool.k_pages, pool.v_pages, np.int32(src),
                    np.int32(dst)))

        pool.k_pages, pool.v_pages = self._attempted(copy_once)

    def _admit_full_hit(self, request: Request, prep: _PagedPrep,
                        events: List) -> None:
        """FULL prefix hit: zero prefill compute. The cached first
        token is replayed (greedy — enforced at construction), the
        prompt's pages are referenced read-only, the partial last page
        (if any) is COW-forked, and only the scalar slot state is
        spliced. TTFT ~ one tiny state program + at most one page
        copy."""
        pool = self.pool
        entry = prep.entry
        with graftscope.span("serving.prefix_hit", cat="serving",
                             req=request.uid, pages_shared=prep.k,
                             mode="full"):
            slot = self._first_token(request, int(entry.tok0), events)
            if slot is None:  # finished at its first token
                self._abort_prep(prep)
                return
            length = len(request.prompt)
            eos = -1 if request.eos_id is None else int(request.eos_id)

            def splice_once():
                maybe_fault(_SITE_INSERT)
                if prep.fork_src is not None:
                    # COW fork FIRST: the forked page must hold the
                    # partial prefix columns before any decode write
                    self._copy_page(prep.fork_src, prep.fresh_ids[0])
                    pool.decref([prep.fork_src])
                    prep.fork_src = None
                with expected_transfer("slot-state control upload at "
                                       "prefix-hit admission (scalar "
                                       "H2D)"):
                    return self._donated(
                        lambda: self._state_insert_jit(
                            pool.positions, pool.last_tokens,
                            pool.active, pool.budgets, pool.eos_ids,
                            np.int32(slot), np.int32(length),
                            np.int32(int(entry.tok0)),
                            np.int32(request.max_new_tokens - 1),
                            np.int32(eos)))

            try:
                (pool.positions, pool.last_tokens, pool.active,
                 pool.budgets, pool.eos_ids) = self._attempted(
                    splice_once)
            except Exception as e:
                self._abort_prep(prep)
                self._poisoned(request, e, slot=slot)
                return
            pool.bind_slot(slot, prep.page_ids)
            prep.shared_ids, prep.fresh_ids = [], []
            pool.note_insert(slot, length)
            if self._draft_k:
                try:
                    self._spec_admit(request, slot, length)
                except Exception as e:
                    self._poisoned(request, e, slot=slot)

    def _seed_partial_pending(self, request: Request, prep: _PagedPrep,
                              chunk: int) -> _PendingPrefill:
        """PARTIAL prefix hit: build the chunked-prefill state with
        the shared prefix pages gathered into the standalone cache and
        a plan that starts at the first uncached column — the suffix
        is the only prefill compute left."""
        pool = self.pool
        start_at = prep.k * pool.page_size
        plan = PrefillPlan(request, chunk, self.min_bucket, pool.s_max,
                           start_at=start_at)

        def gather_once():
            with expected_transfer("prefix-page gather control upload "
                                   "(partial-hit admission)"):
                return self._gather_jit(
                    pool.k_pages, pool.v_pages,
                    np.asarray(prep.shared_ids, np.int32),
                    width=plan.width)

        with graftscope.span("serving.prefix_hit", cat="serving",
                             req=request.uid, pages_shared=prep.k,
                             mode="partial"):
            k_pref, v_pref = self._attempted(gather_once)
        return _PendingPrefill(request, plan, k_pref, v_pref, prep)

    def _drive_pending(self, pend: _PendingPrefill) -> bool:
        """Advance a pending chunked prefill by ONE chunk; on the last
        chunk, dispatch the first token's sample and hand the prefill
        to ``_unread`` (read and spliced by the next step's
        ``_finish_prefills``). Returns True while more chunks remain.
        Shared by chunked admission (one call per step) and the
        whole-prompt engine's partial-hit path (driven to completion in
        a loop)."""
        start, valid, is_last = pend.plan.next_chunk()
        chunk = pend.plan.chunk
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :valid] = pend.request.prompt[start:start + valid]

        def chunk_once():
            # site before the jitted call (donated prefill caches):
            # injected retries are always safe, see _insert's note
            maybe_fault(_SITE_CHUNK)
            with expected_transfer("chunk upload (fixed [1, chunk] "
                                   "shape)"):
                return self._chunk_jit(
                    self.params, pend.k_pref, pend.v_pref,
                    padded, np.int32(start))

        try:
            with graftscope.span("serving.prefill_chunk", cat="serving",
                                 req=pend.request.uid, start=start,
                                 chunk=chunk):
                x, pend.k_pref, pend.v_pref = self._attempted(
                    chunk_once)
                self.metrics.record_prompt_dispatch(chunk=True)
        except Exception as e:
            if self._pending is pend:
                self._pending = None
            self._abort_prep(pend.prep)
            self._poisoned(pend.request, e)
            return False
        record_jit_key(self._chunk_jit,
                       ("prefill_chunk", chunk, pend.plan.width))
        if not is_last:
            return True
        if self._pending is pend:
            self._pending = None
        key = self._next_key()

        def tok0_once():
            # _tok0_jit donates nothing, so retries are safe
            with expected_transfer("first-token sample's index upload "
                                   "(scalar H2D)"):
                return self._tok0_jit(
                    self.params, x,
                    np.int32(pend.plan.length - 1 - start), key)

        try:
            pend.tok0 = self._attempted(tok0_once)
        except Exception as e:
            self._abort_prep(pend.prep)
            self._poisoned(pend.request, e)
            return False
        self._unread.append(pend)  # read and spliced next step
        return False

    def _admit_whole(self, events: List) -> None:
        pool = self.pool
        # a free slot is held back for every prefill already on its way
        while pool.free_slots > len(self._unread):
            prep = self._paged_prep_head()
            if prep is None or prep == "hold":
                break
            if prep == "retry":
                continue
            request = self._pop_admission()
            if request is None:
                break
            request.prefix_hit = (None if prep.mode == "miss"
                                  else prep.mode)
            if self._prefix_cache is not None:
                # a miss only counts against an ARMED cache
                self.metrics.record_prefix_outcome(request.prefix_hit)
            if prep.mode == "full":
                self._admit_full_hit(request, prep, events)
                continue
            if prep.mode == "partial":
                # suffix-only prefill through the chunk machinery,
                # driven to completion within this admission (the
                # whole-prompt engine has no pending interleave)
                try:
                    pend = self._seed_partial_pending(
                        request, prep,
                        self._prefill_chunk or pool.page_size)
                except Exception as e:
                    self._abort_prep(prep)
                    self._poisoned(request, e)
                    continue
                while self._drive_pending(pend):
                    pass
                continue
            length = len(request.prompt)
            bucket = bucket_length(length, self.min_bucket, pool.s_max)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :length] = request.prompt
            key = self._next_key()

            def prefill_once():
                maybe_fault(_SITE_PREFILL)
                with expected_transfer("prompt upload"):
                    out = self._prefill_jit(
                        self.params, padded,
                        np.int32(length), key)
                    record_jit_key(self._prefill_jit,
                                   ("prefill", bucket))
                    return out

            try:
                with graftscope.span("serving.prefill", cat="serving",
                                     req=request.uid, bucket=bucket,
                                     prompt_len=length):
                    tok0, k_pref, v_pref = self._attempted(prefill_once)
                    self.metrics.record_prompt_dispatch(chunk=False)
            except Exception as e:
                self._abort_prep(prep)
                self._poisoned(request, e)
                continue
            # dispatched, not waited for: read and spliced next step
            self._unread.append(_PendingPrefill(
                request, None, k_pref, v_pref, prep, tok0=tok0))

    def _insert(self, request: Request, slot: int, k_pref, v_pref,
                length: int, tok0, prep: _PagedPrep) -> None:
        """Splice a prefilled request into ``slot`` and arm its
        on-device finish gates (budget = decode tokens still owed; the
        prefill token is already appended, so ``max_new_tokens - 1``).
        The standalone cache's page blocks scatter at the
        reservation's fresh pages (shared-prefix columns and pure-pad
        overshoot land in scratch) and the slot's table row is bound —
        page ownership transfers from ``prep`` to the row."""
        pool = self.pool
        eos = -1 if request.eos_id is None else int(request.eos_id)

        if self._kv_quant and not isinstance(k_pref, QuantizedKV):
            # graftquant: quantize the model-dtype prefill block ONCE,
            # right before the splice (transferred blocks arrive
            # pre-quantized by the sender's host twin and skip this)
            def quant_once():
                with expected_transfer("prefill-block quantize before "
                                       "splice (graftquant)"):
                    return self._quant_pref_jit(k_pref, v_pref)

            k_pref, v_pref = self._attempted(quant_once)

        n_w = -(-k_pref.shape[2] // pool.page_size)
        write_ids = np.zeros((n_w,), np.int32)
        for j, page in enumerate(prep.fresh_ids):
            col = prep.k + j  # column-order page index
            if col < n_w:
                write_ids[col] = page

        def insert_once():
            # the injected site fires BEFORE the jitted call, so a
            # retried injection never re-runs against donated buffers;
            # a real mid-call failure consumed the donated pool —
            # _donated classifies it engine-fatal (PoolPoisonedError).
            # Scalars and ids go in as NUMPY values (here and at every
            # jitted call of the engine): the call's own argument
            # transfer carries them; ``jnp.int32(x)`` is a device
            # program and ~0.4 ms of host time EACH on a v5e host
            # (PERF.md section 6, PR 32), with the device idle behind
            # the first token's read-back
            maybe_fault(_SITE_INSERT)
            with expected_transfer("slot/length/budget control upload "
                                   "at admission (scalar H2D)"):
                return self._donated(lambda: self._insert_jit(
                    pool.k_pages, pool.v_pages, pool.positions,
                    pool.last_tokens, pool.active, pool.budgets,
                    pool.eos_ids, k_pref, v_pref,
                    write_ids, np.int32(slot),
                    np.int32(length), tok0,
                    np.int32(request.max_new_tokens - 1),
                    np.int32(eos)))

        with graftscope.span("serving.slot_insert", cat="serving",
                             req=request.uid, slot=slot):
            (pool.k_pages, pool.v_pages, pool.positions,
             pool.last_tokens, pool.active, pool.budgets,
             pool.eos_ids) = self._attempted(insert_once)
            page_ids = prep.page_ids
            pool.bind_slot(slot, page_ids)
            # ownership now lives in the table row: neutralize the
            # reservation so a later abort cannot double-release
            prep.shared_ids, prep.fresh_ids = [], []
            self._register_prefix(request, page_ids)
        pool.note_insert(slot, length)
        if self._draft_k:
            # raises into the caller's quarantine path on failure
            self._spec_admit(request, slot, length)

    def _register_prefix(self, request: Request, page_ids) -> None:
        """Offer a freshly spliced prompt's prefix to the cache (miss
        and partial-hit admissions — a partial hit registers the now-
        longer covered prefix). Greedy first token from the request's
        own stream. BEST-EFFORT by contract: the splice already
        succeeded, so a failed registration (e.g. the partial-page
        copy dies) must never take the request down — reported to
        stderr, never raised. The cache itself skips covered prefixes
        and degrades to the aligned prefix when no free page exists
        for the partial copy."""
        if self._prefix_cache is None or self._sampling[0] > 0.0:
            return
        tok0 = request.tokens[0] if request.tokens else None
        if tok0 is None:
            return
        try:
            self._prefix_cache.register(
                request.prompt, page_ids, int(tok0), self._copy_page)
        except GraftFaultError:
            raise  # a poisoned pool is engine-fatal, never swallowed
        except Exception as e:  # noqa: BLE001
            import sys

            # on the telemetry bus too: a cache that silently never
            # populates (repeated copy failures) must be visible to
            # the tooling built to catch exactly this
            graftscope.emit("prefix_cache.register_failed",
                            cat="serving", req=request.uid,
                            error=type(e).__name__)
            print(f"graftpage: prefix registration failed for request "
                  f"{request.uid}: {type(e).__name__}: {e}",
                  file=sys.stderr)

    def _pref_sharded(self, c):
        """Place a standalone prefill cache (``[L, 1, W, H, Dh]``;
        graftquant pairs place both leaves) head-sharded on the
        mesh."""
        if self.mesh is None:
            return c
        if isinstance(c, QuantizedKV):
            return QuantizedKV(
                jax.device_put(c.data, NamedSharding(
                    self.mesh, P(None, None, None, "model", None))),
                jax.device_put(c.scale, NamedSharding(
                    self.mesh, P(None, None, None, "model"))))
        return jax.device_put(
            c, NamedSharding(self.mesh,
                             P(None, None, None, "model", None)))

    def _admit_chunked(self, events: List) -> None:
        pool = self.pool
        if (self._pending is None
                and pool.free_slots > len(self._unread)):
            prep = self._paged_prep_head()
            admit = prep is not None and prep not in ("hold", "retry")
            request = self._pop_admission() if admit else None
            if request is not None:
                request.prefix_hit = (None if prep.mode == "miss"
                                      else prep.mode)
                if self._prefix_cache is not None:
                    self.metrics.record_prefix_outcome(
                        request.prefix_hit)
                if prep.mode == "full":
                    self._admit_full_hit(request, prep, events)
                    return
                if prep.mode == "partial":
                    try:
                        self._pending = self._seed_partial_pending(
                            request, prep, self._prefill_chunk)
                    except Exception as e:
                        self._abort_prep(prep)
                        self._poisoned(request, e)
                        return
                else:
                    plan = PrefillPlan(request, self._prefill_chunk,
                                       self.min_bucket, pool.s_max)
                    k_shape, v_shape = pref_cache_shapes(
                        self.model, plan.width)
                    self._pending = _PendingPrefill(
                        request, plan,
                        self._pref_sharded(
                            jnp.zeros(k_shape, self.model.dtype)),
                        self._pref_sharded(
                            jnp.zeros(v_shape, self.model.dtype)),
                        prep)
        if self._pending is not None:
            self._drive_pending(self._pending)

    # ---- horizon scheduling / dispatch / drain ------------------------
    def _inflight_steps(self) -> int:
        """Max tokens any slot may have advanced in dispatched-but-
        undrained blocks — the host mirror's conservative position
        overshoot (every in-flight row MAY have advanced every slot;
        rows frozen or rejected mid-horizon advanced less, which only
        widens the window pick, never under-sizes it). A speculative
        block counts ``h * (k + 1)`` rows."""
        return sum(block.rows for block in self._blocks)

    def _remaining_eff(self) -> List[int]:
        """Remaining decode budget of every running request,
        discounted by the in-flight rows already dispatched against
        its slot (host knows only DRAINED tokens); never negative. A
        block counts only for the tenant it was dispatched with."""
        rem = []
        for slot, request in self._running.items():
            assumed = sum(block.rows for block in self._blocks
                          if block.slots.get(slot) is request)
            rem.append(max(0, request.max_new_tokens
                           - len(request.tokens) - assumed))
        return rem

    def _pick_k(self) -> int:
        """Realized draft length for the next dispatch, on the
        ``{0, draft_k}`` ladder: collapsed during the post-fault
        cooldown and under sustained low acceptance, with a periodic
        probe dispatch so a stream that turned repetitive again can
        re-arm (acceptance data only exists when drafts actually
        run). The decision counter advances on EVERY pick — collapsed
        dispatches included — or the probe could never come due while
        collapsed and speculation would disarm permanently."""
        if not self._draft_k:
            return 0
        probe = (self._spec_dispatches % 16 == 0)
        self._spec_dispatches += 1
        return pick_draft_k(self._draft_k, self._accept_ema,
                            self._cooldown > 0, probe=probe)

    def _pick_schedule(self) -> Tuple[int, int, int]:
        """``(window, horizon, draft_k)`` for the next dispatch, off
        the conservative host mirror: the smallest bucket covering the
        highest possible next write (a speculative pass writes AND
        reads up to ``k + 1`` columns past each position, so the
        window must cover ``h * (k + 1)`` columns of advance), and the
        scheduler's adaptive horizon snapped to the ``{1, H_max}``
        ladder."""
        k = self._pick_k()
        max_eff = self.pool.max_active_pos + self._inflight_steps()
        need = max_eff + 1 + k
        window = self._buckets[-1]
        for b in self._buckets:
            if b >= need:
                window = b
                break
        admission_pending = (self.scheduler.queue_depth > 0
                             or bool(self._joining()))
        h = pick_horizon(self._horizon_max, window, max_eff,
                         min(self._remaining_eff(), default=0),
                         admission_pending, per_step=k + 1)
        if self._cooldown > 0:
            # post-fault degradation: smaller blast radius per dispatch
            # (one token's work lost on a repeat, not a horizon's) and
            # faster drain while the fault domain is suspect
            self._cooldown -= 1
            if h > 1:
                h = 1
                self.metrics.record_horizon_collapse()
                graftscope.emit("fault.horizon_collapse", cat="fault",
                                cooldown_left=self._cooldown)
        return window, h, k

    def _dispatch(self, overlapped: bool = False) -> None:
        """Launch one fused decode horizon (no host sync — the token
        block stays on device in ``self._blocks`` until drained).
        Transient dispatch failures are retried (the injected site
        fires before the XLA launch, so nothing is donated on a
        retried injection); exhaustion fails fast with a named
        ``GraftFaultError`` — the dispatch domain covers every
        resident slot, so there is no single request to quarantine."""
        with graftscope.span("decode.dispatch", cat="serving",
                             overlapped=overlapped) as dispatch_span:
            pool = self.pool
            window, h, k = self._pick_schedule()
            key = self._next_key()

            # lazy page-table upload: device_table() re-uploads (under
            # its own expected_transfer) only when the host mirror
            # changed at an admission/release boundary — steady state
            # re-uses the device copy, so the armed-sentinel
            # 0-transfer pin holds
            caches = (pool.k_pages, pool.v_pages, pool.device_table())
            # the share of the window's pages the decode kernel has
            # to read (host mirror: no device read); two kinds of
            # layer: what one layer of each kind reads, in pages and
            # in bytes
            by_kind = pool.live_pages_by_kind() if pool.ring_pages else {}
            dispatch_span.note(
                kv_pages_live=pool.live_pages,
                kv_pages_window=pool.max_slots
                * -(-window // pool.page_size),
                **by_kind,
                **(pool.live_bytes_by_kind(by_kind) if by_kind else {}))

            if k:
                if self._drafter is not None:
                    # lazy draft-table upload (the PagePool dirty-upload
                    # discipline): a converged repetitive stream stops
                    # changing its index, so steady state re-uses the
                    # device copy — the host-side refresh is the visible
                    # spec.draft span on the timeline
                    with graftscope.span("spec.draft", cat="serving",
                                         draft_k=k):
                        table = self._drafter.device_table()

                    def launch():
                        maybe_fault(_SITE_DISPATCH)
                        return self._donated(lambda: self._decode_spec(
                            self.params, *caches, pool.positions,
                            pool.last_tokens, pool.active, pool.budgets,
                            pool.eos_ids, table, window=window, horizon=h,
                            draft_k=k))
                else:
                    def launch():
                        maybe_fault(_SITE_DISPATCH)
                        return self._donated(lambda: self._decode_spec(
                            self.params, self._draft_params, *caches,
                            self._draft_k_caches, self._draft_v_caches,
                            pool.positions, pool.last_tokens, pool.active,
                            pool.budgets, pool.eos_ids, window=window,
                            horizon=h, draft_k=k))

                out = self._attempted_engine(launch, "decode dispatch")
                if self._draft_model is not None:
                    (tokens, pool.k_pages, pool.v_pages, pool.positions,
                     pool.last_tokens, pool.active, pool.budgets,
                     self._draft_k_caches, self._draft_v_caches) = out
                else:
                    (tokens, pool.k_pages, pool.v_pages, pool.positions,
                     pool.last_tokens, pool.active, pool.budgets) = out
                record_jit_key(self._decode_spec,
                               ("decode_spec", window, h, k))
            else:
                def launch():
                    maybe_fault(_SITE_DISPATCH)
                    return self._donated(lambda: self._decode(
                        self.params, *caches, pool.positions,
                        pool.last_tokens, pool.active, pool.budgets,
                        pool.eos_ids, key, window=window, horizon=h))

                (tokens, pool.k_pages, pool.v_pages, pool.positions,
                 pool.last_tokens, pool.active,
                 pool.budgets) = self._attempted_engine(
                    launch, "decode dispatch")
                if record_jit_key(self._decode, ("decode", window, h)):
                    # this dispatch just paid a compile anyway — the one
                    # moment measuring the program's temp HBM is off the
                    # steady-state path (no-op unless a ledger is armed)
                    self._note_decode_program(window, h)
            self._blocks.append(
                _TokenBlock(tokens, h, window, dict(self._running), k=k))
            self.metrics.record_dispatch(h, overlapped)
            dispatch_span.note(window=window, horizon=h, draft_k=k,
                               occupancy=pool.occupancy,
                               **self._expert_note)

    def _overlap_ok(self) -> bool:
        """Dispatch the next block before the block in flight is read
        back? Whenever it is safe and useful, at every horizon and
        with admission work queued or pending (the step is a software
        pipeline one block deep): at most ONE older block is undrained
        (the host never runs further ahead of the device than that),
        something is running, and some running request has budget
        beyond what is already dispatched — the device's own budget
        gate would freeze every row of a block past that, pure waste.
        Every dispatch path takes this one rule: a speculative block
        counts its worst case of ``h * (k + 1)`` rows against the
        budgets (so a request's tail falls back to dispatch-then-
        drain), and its drafter's table is simply one block staler
        (fewer accepted drafts, the verified stream unchanged);
        ``admit_prefilled`` splices behind the block in flight like
        any admission."""
        return (len(self._blocks) == 1
                and any(self._remaining_eff()))

    def _drain_one(self, events: List[Tuple[Request, int, bool]]
                   ) -> Tuple[int, int]:
        """Sync the OLDEST pending block (the horizon's ONE host sync)
        and attribute its tokens: append per request, replay the finish
        rules the device applied (the host mirror — ``-1`` marks rows
        the device froze), release finished slots, advance the pool's
        position mirror by the REALIZED per-slot step counts. Returns
        ``(window, tokens_emitted)``."""
        pool = self.pool
        block = self._blocks.popleft()

        def readback():
            maybe_fault(_SITE_READBACK)
            with expected_transfer("per-horizon token-block readback "
                                   "(the horizon's ONE host sync)"):
                return np.asarray(block.tokens)

        def attempt():
            if self._readback_timeout_s is None:
                return readback()
            # watchdog: a WEDGED readback (device/runtime hang) raises
            # a named FaultTimeout instead of blocking the engine
            # forever — the failure mode retries cannot see because
            # nothing ever returns. Bounds ONE attempt, inside the
            # retry ladder, so backoff sleeps between transient
            # failures are never charged against the hang budget (a
            # FaultTimeout is not OSError-shaped, so it propagates
            # un-retried — a hang fails fast, a flake retries).
            try:
                return run_with_timeout(
                    readback, self._readback_timeout_s,
                    "horizon token-block readback",
                    hint="the device never delivered the block "
                         "(wedged runtime or an injected hang); the "
                         "engine fails fast rather than serving stale "
                         "state.")
            except FaultTimeout:
                self.metrics.record_watchdog_trip()
                graftscope.emit("fault.watchdog_trip", cat="fault",
                                what="horizon_readback")
                raise

        with graftscope.span("decode.drain", cat="serving", h=block.h,
                             window=block.window) as drain_span:
            # the wait for the device, apart from the per-token loop
            # below; opened on THIS thread (the watchdog may run the
            # read itself on a helper)
            with graftscope.span("decode.readback", cat="serving"):
                tokens = self._attempted_engine(
                    attempt, "horizon token-block readback")
            if tokens.ndim == 1:
                # a family with per-horizon integers (expert counts)
                # packs them behind the token block: ONE readback
                n_tok = block.rows * pool.max_slots
                self.metrics.record_moe(tokens[n_tok:].reshape(
                    self._family.aux_shape(self.model)))
                tokens = tokens[:n_tok].reshape(block.rows,
                                                pool.max_slots)
            realized: Dict[int, int] = {}
            for h in range(block.rows):
                for slot, request in block.slots.items():
                    if self._running.get(slot) is not request:
                        continue  # finished in an earlier step/block
                        # (or a later tenant now holds the slot — its
                        # tokens are in a later block)
                    token = int(tokens[h, slot])
                    if token < 0:
                        continue  # device froze the row pre-block (or
                        # rejected the draft position, under spec)
                    request.tokens.append(token)
                    realized[slot] = realized.get(slot, 0) + 1
                    reason = self._finished(request, token)
                    if reason is not None:
                        # the device already cleared the row's active
                        # flag mid-horizon — no release program, just
                        # host books
                        self._complete(request, reason)
                        pool.release(slot)
                        del self._running[slot]
                    events.append((request, token, reason is not None))
            pool.note_advance_slots(realized)
            if pool.ring_pages:
                self.metrics.record_kv_pages(pool)
            emitted = sum(realized.values())
            if block.k:
                self._note_spec_drain(block, tokens, realized)
            drain_span.note(tokens=emitted)
        return block.window, emitted

    def _note_spec_drain(self, block: _TokenBlock, tokens,
                         realized: Dict[int, int]) -> None:
        """Acceptance accounting for one drained speculative block
        (graftspec): per (pass, slot), the emitted-row count ``e``
        means ``e - 1`` accepted drafts (an active pass always emits
        its verified pending token first). Feeds the ``accept_len``
        percentiles + drafted/accepted counters, the pick_draft_k
        collapse EMA, and the drafters' n-gram refresh for every slot
        that advanced."""
        k1 = block.k + 1
        mat = (np.asarray(tokens) >= 0).reshape(block.h, k1, -1)
        e = mat.sum(axis=1)                      # [passes, slots]
        act = e >= 1                             # active verify passes
        passes = int(act.sum())
        accept_lens = (e[act] - 1).tolist()
        accepted = int(sum(accept_lens))
        drafted = block.k * passes
        if passes:
            self.metrics.record_spec(drafted, accept_lens)
            rate = accepted / drafted if drafted else 0.0
            ema = self._accept_ema
            self._accept_ema = (rate if ema is None
                                else 0.75 * ema + 0.25 * rate)
        self._last_spec = (drafted, accepted, passes, block.k)
        if self._drafter is not None:
            for slot in realized:
                request = block.slots.get(slot)
                if request is not None:
                    self._drafter.note_history(
                        slot,
                        list(request.prompt) + list(request.tokens))

    def step(self) -> List[Tuple[Request, int, bool]]:
        """One engine iteration of the one-block-deep pipeline: admit
        (splice last step's prefills; dispatch a whole prompt per
        free slot, or one chunk) under the block still in flight,
        dispatch the NEXT decode horizon over the
        pool at the active-length bucket window, then drain exactly
        one token block, the OLDER one (a cold start, with nothing in
        flight, dispatches twice). Returns the iteration's token events
        as ``(request, token, finished)`` tuples (admission first
        tokens included; a quarantined request emits no event — read
        its ``state``/``error``).

        Metered from inside at entry and exit (``metrics.record_step``:
        wall and thread-CPU seconds, the collector's pauses, the
        caller's time since the previous step's exit)."""
        t_in, gc_in = time.perf_counter(), graftscope.host_pauses()
        cpu_in = time.thread_time()
        try:
            with graftscope.span("engine.step", cat="serving"):
                return self._step_inner()
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:
            # engine-fatal: whatever escapes step() (watchdog
            # fail-fast, exhausted dispatch retries, PoolPoisonedError,
            # a plain bug) takes the engine down — leave the flight
            # ring on disk first. Quarantined per-request failures
            # never reach here (absorbed inside the admit/drain paths).
            if not isinstance(e, PoolPoisonedError):  # already dumped
                graftscope.emit("engine.fatal", cat="fault",
                                error=type(e).__name__)
                graftscope.flight_dump(
                    f"engine step: {type(e).__name__}: {e}")
            # /healthz flips with the crash: a replica router must see
            # this replica dead the moment its step loop is
            self.health.to_dead(type(e).__name__)
            raise
        finally:
            cpu_s = time.thread_time() - cpu_in
            self.metrics.record_step(t_in, gc_in, cpu_s,
                                     time.perf_counter(),
                                     graftscope.host_pauses())

    def _step_inner(self) -> List[Tuple[Request, int, bool]]:
        # Admit, dispatch block k, drain block k - 1: in steady state a
        # decode program is queued on the device through all of it.
        # Why admitting and evicting UNDER a block in flight is exact
        # (the device runs its programs in dispatch order):
        # - a slot is only free on the host after its tenant's finish
        #   was DRAINED; the block in flight may still name that tenant,
        #   but its row there is frozen (the device cleared ``active``
        #   when the gate fired) and drain skips rows whose tenant
        #   changed (``_running`` identity), so no token is misfiled;
        # - that frozen row re-writes its pinned column through the
        #   table the block was dispatched with, i.e. into a page since
        #   released and perhaps handed on. The new owner's insert and
        #   decode come LATER in device order and write every column
        #   before reading it (``kv_pages`` invariants), so the stale
        #   write is never read. The same holds for an ACTIVE row of a
        #   tenant that ``_quarantine`` / ``_expire_deadlines`` /
        #   ``withdraw`` / ``hard_reclaim`` evict under the block: the
        #   scrub program and the successor's insert queue behind it;
        # - pages for prompt + budget are reserved at admission
        #   (``_paged_prep_head``) and the budget gate is on the device,
        #   so no block can outrun its reservation however far ahead
        #   it was dispatched; ``_blocks`` counts as work in flight in
        #   the "nothing will ever free a page" test and in
        #   ``in_flight``;
        # - admission never waits for what it has just dispatched: a
        #   prefill (or last chunk and first-token sample) dispatched in
        #   step n runs behind block k - 1; step n + 1 reads its first
        #   token while block k runs, and its insert queues behind
        #   block k and ahead of block k + 1, the tenant's first block.
        #   Until then a free slot is held back for it (``_unread``).
        events = self._admit()
        pool = self.pool
        if self._running or self._blocks:
            # t0 .. end of drain spans this dispatch and the wait for
            # the OLDER block: ``decode_step`` is no longer one
            # program's time (the step's length is the caller's clock
            # round ``step()``)
            t0 = time.perf_counter()
            if self._running and not self._blocks:
                self._dispatch()  # cold start: nothing in flight
            if self._overlap_ok():
                self._dispatch(overlapped=True)
            occupancy = pool.occupancy  # before releases, like PR 2
            self._last_spec = None
            window, emitted = self._drain_one(events)
            dt = time.perf_counter() - t0
            self.metrics.record_decode_step(
                dt, emitted, occupancy, self.scheduler.queue_depth,
                window)
            if self._last_spec is not None:
                # spec.verify rides the bus at the drain boundary the
                # host already synced; waste_s apportions the step's
                # wall to the REJECTED verify rows — the GoodputLedger
                # books it as goodput_spec_waste_s, not productive
                drafted, accepted, passes, k = self._last_spec
                rows = passes * (k + 1)
                waste = (dt * (drafted - accepted) / rows
                         if rows else 0.0)
                graftscope.emit_span(
                    "spec.verify", dt, cat="serving", drafted=drafted,
                    accepted=accepted, passes=passes, waste_s=waste)
        self._step_idx += 1
        if self.journal is not None and events:
            # one fsync'd WAL batch per step, at the drain boundary
            # the host already synced; replay-prefix tokens dedup
            # (and verify) inside — a journal failure is engine-fatal
            # through step()'s flight-dump path, never silent
            self.journal.note_events(events)
        return events

    @property
    def in_flight(self) -> int:
        """Work somewhere in the engine: requests queued, mid-prefill
        (chunks pending or first token unread) or decoding — or, with
        none of those left, a dispatched-but-unsynced token block
        (drive loops should drain until 0). The block in flight under
        running requests is their pipeline, not one more unit of load:
        placement and admission windows (``serving/replica.py``) count
        requests."""
        return (self.scheduler.queue_depth + len(self._running)
                + len(self._joining())
                or (1 if self._blocks else 0))

    def run(self) -> Iterable[Tuple[Request, int, bool]]:
        """Drive ``step`` until queue, pending prefill and pool drain,
        streaming token events."""
        while self.in_flight:
            yield from self.step()

    # ---- graftheal: drain + redelivery --------------------------------
    def begin_drain(self, reason: str = "drain") -> None:
        """Flip the health machine to DRAINING (idempotent; signal-
        handler-safe — it only writes host state): admission closes
        (``enqueue`` raises ``QueueFull`` naming the drain), /healthz
        starts serving 503, and the drive loop finishes in-flight work
        through :meth:`drain`. SIGTERM is wired here by
        ``runtime.heal.install_drain_handler``."""
        if self.health.state in (heal.DRAINING, heal.DEAD):
            return
        self.health.to_draining(reason)
        graftscope.emit("engine.draining", cat="serving", reason=reason,
                        in_flight=self.in_flight)

    def drain(self, deadline_s: Optional[float] = None
              ) -> List[Tuple[Request, int, bool]]:
        """Finish every in-flight request (admission stays closed),
        bounded by ``deadline_s``: past it, every unfinished request —
        queued, mid-prefill, or running — is failed NAMED
        (``DeadlineExceeded``, reason ``"drain"``), never silently
        dropped. The engine lands DEAD, its journal (if any) is
        compacted + closed (a clean full drain leaves it empty), and
        the step's token events are returned for delivery."""
        self.begin_drain("drain")
        t0 = time.perf_counter()
        events: List[Tuple[Request, int, bool]] = []
        with graftscope.span("engine.drain", cat="serving",
                             deadline_s=deadline_s) as drain_span:
            overdue = 0
            while self.in_flight:
                if (deadline_s is not None
                        and time.perf_counter() - t0 > deadline_s):
                    overdue = self._fail_unfinished(deadline_s)
                    break
                events.extend(self.step())
            drain_span.note(drained=len(events), overdue=overdue)
        self.health.to_dead("drained")
        if self.journal is not None:
            self.journal.close()
        return events

    def _fail_unfinished(self, deadline_s: float) -> int:
        """Drain-deadline eviction: fail everything still in flight,
        named. In-flight token blocks are dropped undrained (their
        requests are being failed and the pool dies with the engine);
        running slots are scrubbed like any quarantine."""
        self._blocks.clear()
        failed = 0

        def overdue_error(request, where):
            return DeadlineExceeded(
                f"request {request.uid} still {where} at the drain "
                f"deadline ({deadline_s:.3g}s): failed named, not "
                "silently dropped — resubmit to another replica (the "
                "journal records it terminal, so a restart will not "
                "double-serve it)")

        while True:
            request = self.scheduler.next_to_admit()
            if request is None:
                break
            self._quarantine(request, overdue_error(request, "queued"),
                             reason="drain")
            failed += 1
        for pend in self._joining():
            self._drop_joining(pend)
            self._quarantine(
                pend.request,
                overdue_error(pend.request, "mid-prefill"),
                reason="drain")
            failed += 1
        for slot, request in list(self._running.items()):
            self._quarantine(request, overdue_error(request, "running"),
                             reason="drain", slot=slot)
            failed += 1
        return failed

    def redeliver(self, entries,
                  events_out: Optional[list] = None) -> List[Request]:
        """Re-submit journaled unfinished requests (supervised-restart
        recovery): each :class:`~..runtime.heal.JournalEntry` re-enters
        admission under its ORIGINAL uid — the journal recognizes it
        (no duplicate WAL record) and prefix-dedups its already-emitted
        tokens as the deterministic decode regenerates them, so the
        recovered run is token-exact and nothing is double-journaled.

        A crash can leave MORE unfinished entries than the bounded
        queue admits (running + queued at crash time vs a fresh empty
        engine), so ``QueueFull`` here is absorbed by stepping the
        engine between attempts — the same backpressure discipline as
        ``submit_retrying`` — never a crashed recovery (the drain
        steps' token events land in ``events_out`` when given).
        Returns the redelivered ``Request`` records in journal order."""
        out: List[Request] = []
        for entry in entries:
            request = Request(entry.prompt, entry.max_new_tokens,
                              entry.eos_id, uid=entry.uid)
            while True:
                try:
                    self.enqueue(request)
                    break
                except QueueFull:
                    if not self.health.ready:
                        raise  # draining/dead: admission closed for good
                    # bounded queue at capacity: serve a step so it
                    # drains (guaranteed progress — a full queue means
                    # work is resident), then re-enqueue
                    events = self.step()
                    if events_out is not None:
                        events_out.extend(events)
            self.metrics.record_redelivery()
            graftscope.emit("request.redelivered", cat="request",
                            req=entry.uid,
                            replayed_tokens=len(entry.tokens))
            out.append(request)
        return out

    # ---- graftroute: fleet seams --------------------------------------
    def prefill_detached(self, request: Request,
                         chunk: Optional[int] = None
                         ) -> Tuple[int, jax.Array, jax.Array]:
        """Run ONE request's prefill WITHOUT touching this engine's
        pool — the prefill half of graftroute's prefill/decode split.

        Returns ``(tok0, k_pref, v_pref)``: the sampled first token
        (host int) and the standalone ``[L, 1, W, H, Dh]`` prefill
        cache block, computed by the SAME jitted programs ordinary
        admission runs (whole-prompt ``_prefill_jit``, or the fixed
        ``[1, chunk]`` incremental program when ``chunk`` is given — a
        dedicated prefill replica has no resident decode to interleave
        with, so its chunks run back-to-back inside the call). Because
        program, bucket padding and params are identical to a
        monolithic admission, a handed-off continuation is token-exact
        by construction; the receiving engine splices the block at ITS
        OWN chosen write_ids (:meth:`admit_prefilled`) — the
        receiver-chosen scatter of the portable-redistribution
        discipline (arXiv:2112.01075). The block stays on THIS
        engine's devices; the :class:`~.replica.PageTransfer` seam
        owns the host round-trip.

        Faults ride the normal admission domains (``serving.prefill``
        / ``prefill_chunk`` / ``prefill_tok0`` sites, bounded retry);
        exhaustion raises to the caller, who fails the request named —
        there is no pool state to scrub."""
        pool = self.pool
        length = len(request.prompt)
        if length < 1:
            raise ValueError("empty prompt")
        if length + request.max_new_tokens > pool.s_max:
            raise ValueError(
                f"prompt {length} + max_new_tokens "
                f"{request.max_new_tokens} exceeds the slot capacity "
                f"s_max={pool.s_max}")
        if chunk is None:
            bucket = bucket_length(length, self.min_bucket, pool.s_max)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :length] = request.prompt
            key = self._next_key()

            def prefill_once():
                maybe_fault(_SITE_PREFILL)
                with expected_transfer("prompt upload + first-token "
                                       "readback (detached prefill)"):
                    tok0, k_pref, v_pref = self._prefill_jit(
                        self.params, padded,
                        np.int32(length), key)
                    record_jit_key(self._prefill_jit,
                                   ("prefill", bucket))
                    return int(tok0), k_pref, v_pref

            with graftscope.span("serving.prefill", cat="serving",
                                 req=request.uid, bucket=bucket,
                                 prompt_len=length, detached=True):
                out = self._attempted(prefill_once)
                self.metrics.record_prompt_dispatch(chunk=False)
                return out
        plan = PrefillPlan(request, int(chunk), self.min_bucket,
                           pool.s_max)
        model = self.model
        k_shape, v_shape = pref_cache_shapes(model, plan.width)
        k_pref = self._pref_sharded(jnp.zeros(k_shape, model.dtype))
        v_pref = self._pref_sharded(jnp.zeros(v_shape, model.dtype))
        x = None
        start = 0
        while not plan.done:
            start, valid, _is_last = plan.next_chunk()
            padded = np.zeros((1, plan.chunk), np.int32)
            padded[0, :valid] = request.prompt[start:start + valid]

            def chunk_once(k=k_pref, v=v_pref, p=padded, s=start):
                # the injected site fires BEFORE the jitted call, like
                # _drive_pending: a retried injection never replays
                # against donated buffers
                maybe_fault(_SITE_CHUNK)
                with expected_transfer("chunk upload (detached "
                                       "prefill)"):
                    return self._chunk_jit(self.params, k, v,
                                           p,
                                           np.int32(s))

            with graftscope.span("serving.prefill_chunk",
                                 cat="serving", req=request.uid,
                                 start=start, chunk=plan.chunk,
                                 detached=True):
                x, k_pref, v_pref = self._attempted(chunk_once)
                self.metrics.record_prompt_dispatch(chunk=True)
            record_jit_key(self._chunk_jit,
                           ("prefill_chunk", plan.chunk, plan.width))
        key = self._next_key()

        def tok0_once():
            maybe_fault(_SITE_TOK0)
            with expected_transfer("first-token readback (detached "
                                   "prefill)"):
                return int(self._tok0_jit(
                    self.params, x, np.int32(length - 1 - start),
                    key))

        with graftscope.span("serving.prefill_tok0", cat="serving",
                             req=request.uid, detached=True):
            tok0 = self._attempted(tok0_once)
        return tok0, k_pref, v_pref

    def prefill_detached_wire(self, request: Request,
                              chunk: Optional[int] = None):
        """:meth:`prefill_detached` shaped for the host transfer seam:
        ``(tok0, k_block, v_block, k_scale, v_scale)`` with the blocks
        as host numpy. On a graftquant engine the blocks leave ALREADY
        int8 (scales the f32 sidecars; the numpy formula is the
        device one's bit-equal twin, test-pinned) — half the bytes on
        the wire AND a receiver splice bit-identical to a local
        admission. Model-dtype engines return ``None`` scales (the
        historical payload, unchanged)."""
        tok0, k_pref, v_pref = self.prefill_detached(request,
                                                     chunk=chunk)
        k_block = np.asarray(k_pref)
        v_block = np.asarray(v_pref)
        if not self._kv_quant:
            return tok0, k_block, v_block, None, None
        k_block, k_scale = quantize_kv_np(k_block)
        v_block, v_scale = quantize_kv_np(v_block)
        return tok0, k_block, v_block, k_scale, v_scale

    def prefill_detached_resident(self, request: Request,
                                  chunk: Optional[int] = None):
        """graftlink's device-resident transfer export: the
        :meth:`prefill_detached_wire` tuple shape with the blocks
        left as DEVICE arrays — no host bounce. A same-process decode
        engine splices them via a device-to-device put
        (:meth:`admit_prefilled`'s ``_pref_sharded`` resharding IS the
        transfer collective — audited under graftcheck's
        ``serving_transfer_insert_*`` programs); a remote target's
        proxy lacks this method, so :meth:`~.replica.ServingReplica
        .prefill_step` automatically falls back to the host/wire path
        (the cross-mesh/CPU fallback, byte-identical by pin).

        graftquant engines quantize ON DEVICE (``_quant_pref_jit`` —
        the same program a local splice of a model-dtype block runs),
        so the exported int8 data + f32 scale sidecars match the host
        ``quantize_kv_np`` twin bit-for-bit."""
        tok0, k_pref, v_pref = self.prefill_detached(request,
                                                     chunk=chunk)
        if not self._kv_quant:
            return tok0, k_pref, v_pref, None, None

        def quant_once():
            with expected_transfer("device-resident transfer "
                                   "quantization (detached prefill)"):
                return self._quant_pref_jit(k_pref, v_pref)

        qk, qv = self._attempted(quant_once)
        return tok0, qk.data, qv.data, qk.scale, qv.scale

    def admit_prefilled(self, request: Request, tok0: int, k_pref,
                        v_pref, k_scale=None, v_scale=None
                        ) -> List[Tuple[Request, int, bool]]:
        """Splice a transferred prefill block into THIS engine — the
        decode half of graftroute's split. ``k_pref``/``v_pref`` may
        be device arrays or host numpy (the host-round-trip transfer
        seam); this engine chooses the destination itself — a free
        slot, and in paged mode freshly allocated pages whose ids
        become the splice's write_ids — and runs the SAME jitted
        insert program ordinary admission runs, so the continuation
        is token-exact with a monolithic admission (test-pinned).

        graftquant transfer matrix: ``k_scale``/``v_scale`` present
        means the sender already quantized (half the bytes crossed the
        wire) — a quantized engine splices the int8 block + scale
        sidecar DIRECTLY, no requantization, so the spliced columns
        are bit-identical to the sender's. Scales absent on a
        quantized engine: the model-dtype block is quantized here at
        the splice (``_insert``'s seam). Scales present on a
        model-dtype engine is a ``ValueError`` — dequantizing into a
        full-precision pool would silently launder quantization error
        into an engine whose pins promise exact model-dtype math.

        Raises ``QueueFull`` when admission is closed (not READY), no
        slot is free, or the page pool cannot cover the request (after
        shedding prefix-cache entries LRU-first, exactly like local
        admission) — the router's signal to HOLD the transfer and
        retry after this engine steps. Token events (the first token;
        possibly finished-at-first-token) are returned AND journaled
        like any admission."""
        if (k_scale is None) != (v_scale is None):
            raise ValueError("k_scale/v_scale must be given together")
        if k_scale is not None and not self._kv_quant:
            raise ValueError(
                "quantized transfer block offered to a model-dtype "
                "engine (kv_dtype='model'): dequantizing into a "
                "full-precision pool is forbidden — re-route to an "
                "int8 replica or resend unquantized")
        if not self.health.ready:
            self.metrics.record_shed()
            graftscope.emit("request.shed", cat="request",
                            req=request.uid,
                            reason=self.health.state)
            raise QueueFull(
                f"admission closed: engine {self.health.state.upper()}"
                f" ({self.health.reason}); transfer to another replica")
        pool = self.pool
        length = len(request.prompt)
        if length < 1:
            raise ValueError("empty prompt")
        if length + request.max_new_tokens > pool.s_max:
            raise ValueError(
                f"prompt {length} + max_new_tokens "
                f"{request.max_new_tokens} exceeds the slot capacity "
                f"s_max={pool.s_max}")
        if pool.free_slots <= len(self._unread):
            raise QueueFull(
                "no free slot for the transferred prefill; step this "
                "engine and retry (graftroute holds the transfer)")
        n_total = PagePool.pages_for(
            length + request.max_new_tokens, pool.page_size)
        if n_total > pool.num_pages - 1:
            raise ValueError(
                f"transfer needs {n_total} page(s); the pool holds "
                f"{pool.num_pages - 1} allocatable")
        while (pool.free_pages < n_total
               and self._prefix_cache is not None
               and self._prefix_cache.evict_lru()):
            pass  # shed cache before holding a transfer
        if pool.free_pages < n_total:
            self.metrics.record_page_hold()
            graftscope.emit("request.held", cat="request",
                            req=request.uid, pages_needed=n_total,
                            pages_free=pool.free_pages)
            raise QueueFull(
                f"page pressure: transfer needs {n_total} page(s),"
                f" {pool.free_pages} free — retry after running "
                "work completes")
        prep = _PagedPrep("miss", None, 0, [],
                          pool.alloc_pages(n_total), None, n_total)
        if request.submit_time is None:
            request.submit_time = time.perf_counter()
        if self.journal is not None:
            self.journal.record_admit(request)
        request.state = RUNNING
        request.admit_time = time.perf_counter()
        self.metrics.record_admission(
            request.admit_time - request.submit_time)
        graftscope.emit("request.admit", cat="request",
                        req=request.uid, transfer=True,
                        queue_wait_s=(request.admit_time
                                      - request.submit_time))
        events: List[Tuple[Request, int, bool]] = []
        try:
            slot = self._first_token(request, int(tok0), events)
        except BaseException:
            # the fresh pages in prep have no owner until _insert
            # binds them — an engine fault inside the first token
            # (slot grant, decode, injected fault) must not leak them
            self._abort_prep(prep)
            raise
        if slot is None:  # finished at its (transferred) first token
            self._abort_prep(prep)
        else:
            try:
                if k_scale is not None:
                    k_dev = self._pref_sharded(QuantizedKV(
                        jnp.asarray(k_pref), jnp.asarray(k_scale)))
                    v_dev = self._pref_sharded(QuantizedKV(
                        jnp.asarray(v_pref), jnp.asarray(v_scale)))
                else:
                    k_dev = self._pref_sharded(jnp.asarray(k_pref))
                    v_dev = self._pref_sharded(jnp.asarray(v_pref))
                self._insert(request, slot, k_dev, v_dev, length,
                             np.int32(int(tok0)), prep=prep)
            except Exception as e:
                self._abort_prep(prep)
                self._poisoned(request, e, slot=slot)
        if self.journal is not None and events:
            self.journal.note_events(events)
        return events

    def withdraw(self, uid) -> bool:
        """Abandon one request NOW, wherever it is — QUEUED,
        mid-prefill, or RUNNING (ROADMAP item 4: an
        abandoned request otherwise decodes to its full token budget,
        burning slot-steps nobody will read). Eviction rides the
        existing quarantine machinery: a running request's slot has
        its device gates scrubbed and its pages decref'd back to the
        pool (ledger-verified reclaim), the WAL records the request
        terminal (a restart never redelivers it), and every OTHER
        slot's token stream is untouched — pinned token-exact in
        tests/test_graftlife.py. The request leaves FAILED with
        reason ``"withdraw"`` and :class:`~.scheduler.
        RequestWithdrawn` on ``.error``: accounted, never silently
        dropped. Returns True when ``uid`` was found. The fleet-level
        cancellation verb is a thin wire wrapper over this."""
        err = RequestWithdrawn(
            f"request {uid} withdrawn by its client")
        for slot, request in list(self._running.items()):
            if request.uid == uid:
                self._quarantine(request, err, reason="withdraw",
                                 slot=slot)
                return True
        for pend in self._joining():
            if pend.request.uid == uid:
                self._drop_joining(pend)
                self._quarantine(pend.request, err, reason="withdraw")
                return True
        request = self.scheduler.withdraw_uid(uid)
        if request is not None:
            self._quarantine(request, err, reason="withdraw")
            return True
        return False

    def withdraw_queued(self, max_n: int = 1) -> List[Request]:
        """graftroute work stealing: hand up to ``max_n`` QUEUED
        requests (taken from the queue TAIL — the FIFO head keeps its
        admission order on this replica; the request that would wait
        LONGEST moves) back to the router for re-placement on a
        drained peer. The ROUTER journals the handoff
        (``RequestJournal.record_handoff``) only once the peer
        ACCEPTS — a refused theft requeues here with its WAL entry
        still live, so the redelivery guarantee never has a gap."""
        out: List[Request] = []
        for _ in range(max_n):
            request = self.scheduler.withdraw_tail()
            if request is None:
                break
            graftscope.emit("request.stolen", cat="request",
                            req=request.uid)
            out.append(request)
        return out

    def hard_reclaim(self) -> None:
        """Release every device resource this engine holds WITHOUT
        touching request state: the in-process analogue of the OS
        reclaiming a SIGKILLed serving process. The router calls it
        at the reap — the dead engine's requests are redelivered
        from its journal under their original uids, so only the
        residency (slots, pages, chunked-prefill prep buffers) must
        go; marking the ``Request`` records here would corrupt the
        redelivery path that now owns them. Idempotent."""
        for pend in self._joining():
            self._drop_joining(pend)
        for slot in list(self._running):
            self._scrub_slot(slot)
            del self._running[slot]
            self.pool.release(slot)

    def serve(self, requests: Iterable[Tuple[Sequence[int], int]]
              ) -> List[Request]:
        """Convenience batch API: submit ``(prompt, max_new_tokens)``
        pairs, run to drain, return the ``Request`` records in
        submission order. Every record comes back terminal: ``DONE``,
        or ``FAILED`` with the cause on ``request.error`` (quarantined
        / deadline-evicted requests are reported, not hidden — check
        ``state`` when a fault plan or deadlines are in play)."""
        submitted = [self.submit(p, n) for p, n in requests]
        for _ in self.run():
            pass
        assert all(r.state in (DONE, FAILED) for r in submitted)
        return submitted


# --------------------------------------------------------------- graftcheck

def audit_programs():
    """graftcheck registration hook: the serving decode ladder.

    The engine's whole compile-budget story is that decode programs
    form a SMALL CLOSED SET — ``buckets x {1, H}`` — regardless of
    traffic (``decode_programs`` pins the runtime side). This hook
    enumerates that exact ladder abstractly (the same jitted
    ``_decode`` the dispatcher calls, traced per static ``(window,
    horizon)`` with the pool's own shapes), so every program traffic
    can ever run has a committed fingerprint: a semantic change to the
    hot decode scan — an extra cache copy, a dropped freeze gate, a
    new f32 upcast — fails tier-1 with the program named, before any
    TPU time is burned on it.

    Audited on a reduced bucket set ({8, 32} x {1, 4} — the structural
    family; every window shares one gather/scatter shape recipe) with
    ``num_pages`` sized BELOW dense worst case, as production would:
    any drift in the table-driven gather/scatter structure fails the
    gate.

    The SPEC ladder (graftspec) fingerprints the draft+verify
    programs at (32, 4, 4): self-draft and the draft-model twin. The
    committed costs.json budgets are the bandwidth argument made
    enforceable: the verify pass must show ~(k+1)x the non-spec
    program's FLOPs at ~1x its bytes accessed (more MXU rows over the
    same weight/KV stream) — drift in either direction fails tier-1
    (``tests/test_graftspec.py`` pins the ratio from the committed
    records). Spec OFF leaves the original programs' fingerprints
    untouched (separate jitted function)."""
    def specs():
        # ONE audit geometry across the LM-family hooks
        from ..analysis.programs import audit_tiny_gpt

        model = audit_tiny_gpt()
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 1), jnp.int32),
                               train=False))["params"]
        # 4 slots x 4 pages/slot worst case would be 17 pages; 13
        # (incl. scratch) is the capacity-lever shape, committed
        geometry = dict(max_slots=4, s_max=32, min_bucket=8,
                        decode_horizon=4, page_size=8, num_pages=13)
        paged = ServingEngine(model, params, decode_buckets=(8, 32),
                              **geometry)

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        out = []
        args = paged._decode_avals(sds, params)
        for window in paged.decode_buckets:
            for horizon in sorted({1, paged.decode_horizon}):
                def build(a=args, w=window, h=horizon):
                    return {
                        "fn": paged._decode, "args": a,
                        "kwargs": {"window": w, "horizon": h},
                        # single-shard decode moves zero collective
                        # bytes — that IS the serving cost model
                        "expect_collectives": {},
                    }
                out.append({
                    "name": f"serving_decode_paged_w{window}_h{horizon}",
                    "min_devices": 1, "build": build,
                })

        # ---- graftquant: the int8-KV ladder ----
        # Audited at head_dim=64 (the smallest production-shaped head:
        # int8+scale is (64+4)/(2*64) = 0.53x of bf16 per KV group, so
        # the committed costs.json argument-bytes show the ~halving
        # the residency claim rests on — at the default Dh=16 audit
        # geometry the 4-byte scale would eat the win and the audit
        # would pin a number nobody ships). One (window=32, horizon=4)
        # rung per engine: the quant ladder shares the structural
        # recipes already fingerprinted above, so one rung
        # pins the dtype story (convert counts + argument bytes) and a
        # bf16 twin at the SAME geometry makes the halving a committed
        # in-file comparison, not an across-geometry inference.
        qmodel = audit_tiny_gpt(hidden_size=128, num_heads=2)
        qparams = jax.eval_shape(
            lambda: qmodel.init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 1), jnp.int32),
                                train=False))["params"]
        for kv_dtype, qtag in (("int8", "quant"), ("model", "quantref")):
            eng = ServingEngine(qmodel, qparams, decode_buckets=(32,),
                                kv_dtype=kv_dtype, **geometry)
            args = eng._decode_avals(sds, qparams)

            def build(e=eng, a=args):
                return {
                    "fn": e._decode, "args": a,
                    "kwargs": {"window": 32, "horizon": 4},
                    "expect_collectives": {},
                }
            out.append({
                "name": f"serving_decode_{qtag}_paged_w32_h4",
                "min_devices": 1, "build": build,
            })

        # ---- graftspec: the draft+verify ladder ----
        spec_paged = ServingEngine(model, params, decode_buckets=(32,),
                                   draft_k=4, **geometry)
        draft_model = audit_tiny_gpt(num_layers=1)
        draft_params = jax.eval_shape(
            lambda: draft_model.init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 1), jnp.int32),
                                     train=False))["params"]
        spec_dm = ServingEngine(model, params, decode_buckets=(32,),
                                draft_k=4, draft_model=draft_model,
                                draft_params=draft_params, **geometry)
        # greedy spec takes no key; the verify pass moves zero
        # collective bytes too — speculation spends BANDWIDTH slack,
        # it never buys communication
        spec_kwargs = {"window": 32, "horizon": 4, "draft_k": 4}

        def build_spec_paged():
            return {
                "fn": spec_paged._decode_spec,
                "args": spec_paged._decode_avals(sds, params)[:-1] + (
                    jax.ShapeDtypeStruct(
                        spec_paged._drafter._table.shape, jnp.int32),),
                "kwargs": spec_kwargs, "expect_collectives": {},
            }

        out.append({"name": "serving_decode_spec_paged_w32_h4_k4",
                    "min_devices": 1, "build": build_spec_paged})

        def build_spec_dm():
            # params, draft params, pools + table, draft caches, state
            base = spec_dm._decode_avals(sds, params)[:-1]
            return {
                "fn": spec_dm._decode_spec,
                "args": (params, draft_params) + base[1:4] + (
                    sds(spec_dm._draft_k_caches),
                    sds(spec_dm._draft_v_caches)) + base[4:],
                "kwargs": spec_kwargs, "expect_collectives": {},
            }

        out.append({"name": "serving_decode_spec_draft_w32_h4_k4",
                    "min_devices": 1, "build": build_spec_dm})

        # ---- graftlink: the transfer-splice ladder ----
        # The device-resident PageTransfer path ends in exactly this
        # program: a detached prefill block (receiver-placed via
        # jax.device_put) splices into the decode pool through
        # ``_insert_jit`` — a receiver-chosen scatter at write_ids.
        # Committing its fingerprint + costs makes the DMA path's
        # budget auditable like every decode rung: the splice must
        # move ZERO collective bytes (single-shard page scatter — the
        # device put IS the transfer; any collective appearing here
        # means the splice started paying communication for what
        # placement already did).
        def build_xfer():
            pool = paged.pool
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            # the standalone prefill cache [L, 1, W, H, Dh]
            pref = jax.ShapeDtypeStruct(
                pref_cache_shapes(model, 32)[0], pool.k_pages.dtype)
            return {
                "fn": paged._insert_jit,
                "args": (sds(pool.k_pages), sds(pool.v_pages),
                         sds(pool.positions), sds(pool.last_tokens),
                         sds(pool.active), sds(pool.budgets),
                         sds(pool.eos_ids), pref, pref,
                         jax.ShapeDtypeStruct(
                             (-(-32 // pool.page_size),), jnp.int32))
                # slot, length, tok0, budget, eos
                + (scalar,) * 5,
                "expect_collectives": {},
            }

        out.append({"name": "serving_transfer_insert_paged_w32",
                    "min_devices": 1, "build": build_xfer})
        return out

    return specs()
