"""Checkpoint save/load.

Artifact parity target: the reference saves the (DDP-wrapped) state dict
on rank 0 at the final epoch only, named ``model_{epoch}.pth``
(``main.py:75-77``), and has NO load/resume path. Here:

- :func:`save_checkpoint` writes the full :class:`..train.TrainState`
  (params, BN running stats, optimizer buffers, epoch) as msgpack bytes
  under the same ``model_{epoch}.pth`` name, single-writer (primary host);
- :func:`load_checkpoint` restores it — the resume path the reference
  lacks (SURVEY.md §5 "Checkpoint / resume").

msgpack via ``flax.serialization`` rather than pickle: deterministic,
framework-neutral bytes, no arbitrary-code-execution on load.

Durability + integrity (graftfault hardening):

- the write path is fsync'd on BOTH sides of the atomic rename (file
  before ``os.replace``, parent directory after) — ``os.replace``
  alone orders nothing on power loss, so "atomic" used to overpromise;
- every checkpoint carries a sha256 sidecar (``model_N.pth.sha256``)
  written from the exact bytes handed to the OS; :func:`load_checkpoint`
  verifies it and a truncated/bit-flipped file raises
  :class:`CheckpointCorruptError` NAMING the file and both digests
  instead of failing deep inside msgpack (or worse, resuming from
  garbage weights);
- :func:`load_with_fallback` is the resume path that survives it:
  newest checkpoint corrupt -> warn with the digest mismatch, fall
  back to the previous valid epoch, resume there.
"""

from __future__ import annotations

import hashlib
import os
from typing import Optional, Tuple

import jax
from flax import serialization
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel import dist
from ..runtime import scope as graftscope
from ..runtime.faults import GraftFaultError, maybe_fault, register_site
from ..utils import profiler  # noqa: F401 (sets graftscope's annotator)
from .state import TrainState

# the torn/corrupt-artifact hazard the fault matrix sweeps: fires on
# the serialized payload right before it reaches the OS, so an
# injected corruption is caught by the digest verification exactly
# like real bit rot would be
_SITE_WRITE = register_site(
    "train.checkpoint_write",
    "msgpack checkpoint payload write + fsync + atomic rename")


class CheckpointCorruptError(GraftFaultError):
    """A checkpoint's bytes do not match its recorded sha256 digest
    (torn write, bit rot, truncation). Names the file and both
    digests; resume paths fall back to the previous valid epoch."""


def _gather_for_host(tree):
    """Make every leaf fully host-addressable before serialization.

    Under ``--zero1`` (and multi-host TP) state leaves are sharded
    across hosts, so a bare ``jax.device_get`` would raise
    "spans non-addressable devices". A jitted identity with replicated
    ``out_shardings`` all-gathers such a leaf onto every device of its
    mesh. This is a COLLECTIVE: every host must call it, so it runs
    BEFORE any primary-host gating. Single-host states pass through
    untouched (everything is already addressable).
    """

    def fix(leaf):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            mesh = leaf.sharding.mesh
            return jax.jit(
                lambda x: x, out_shardings=NamedSharding(mesh, P())
            )(leaf)
        return leaf

    return jax.tree.map(fix, tree)


def checkpoint_path(save_path: str, epoch: int) -> str:
    """``{save_path}/model_{epoch}.pth`` (reference ``main.py:77``)."""
    return os.path.join(save_path, "model_{0}.pth".format(epoch))


def digest_path(path: str) -> str:
    """Sidecar holding the checkpoint's sha256 (hex)."""
    return path + ".sha256"


def _fsync_dir(dirname: str) -> None:
    """fsync a DIRECTORY so a just-renamed entry survives power loss
    (the rename itself lives in the directory's metadata). Platforms
    whose dirfds reject fsync (some network filesystems) degrade to
    the rename-only guarantee."""
    try:
        fd = os.open(dirname, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:  # EINVAL on fsync-less dirfds: keep rename-only
        pass
    finally:
        os.close(fd)


def write_atomic_durable(path: str, payload: bytes) -> None:
    """tmp-write -> fsync(file) -> atomic rename -> fsync(parent dir).

    ``os.replace`` alone is atomic against CONCURRENT readers but
    orders nothing against power loss: the data blocks and the rename
    can reach disk in either order, so the old comment's "no torn
    checkpoints" only held for clean exits. Both fsyncs make the
    rename a real durability barrier."""
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_dir(os.path.dirname(os.path.abspath(path)))


def save_checkpoint(save_path: str, state: TrainState, epoch: int) -> Optional[str]:
    """Write the state on the primary host; returns the path (None on
    non-primary hosts, which mirror the reference's rank-gating at
    ``main.py:75``).

    The sha256 of the serialized payload is written alongside
    (``model_N.pth.sha256``), AFTER the checkpoint itself is durable —
    a crash between the two leaves a valid checkpoint with no digest
    (verified loads treat a missing sidecar as legacy, not corrupt),
    never a digest pointing at torn bytes.

    graftzero: a state carrying a sharded
    :class:`~..parallel.zero.ZeroOptState` saves GATHER-ON-SAVE — the
    moments are unflattened back to the replicated format, so the
    artifact is mode-portable: ``--resume auto`` round-trips between
    ``--zero`` and plain runs (the CLIs load into the replicated
    template and re-shard with ``zero.zeroify_state`` when ``--zero``
    is set). The digest sidecar and ``load_with_fallback`` are
    untouched."""
    # Collective leaf replication first — ALL hosts participate even
    # though only the primary writes (see _gather_for_host). It also
    # makes the zero moment buckets host-addressable for the gather
    # below.
    state = _gather_for_host(state)
    if not dist.is_primary():
        return None
    from ..parallel.zero import ZeroOptState, gather_opt_state

    if isinstance(state.opt_state, ZeroOptState):
        # graftzero gather-on-save: host-local unflatten (no
        # collective — safe after the primary gate), so the artifact
        # is always the replicated, mode-portable format
        state = state.replace(
            opt_state=gather_opt_state(state.opt_state, state.params))
    path = checkpoint_path(save_path, epoch)
    with graftscope.span("checkpoint.write", cat="train", epoch=epoch,
                         path=os.path.basename(path)) as ckpt_span:
        # Pull fully-addressable host copies off the devices.
        host_state = jax.device_get(state)
        payload = serialization.to_bytes(host_state)
        digest = hashlib.sha256(payload).hexdigest()
        # injected fault point: "corrupt" flips a payload byte AFTER
        # the digest was computed — exactly what bit rot / a torn
        # write does
        written = maybe_fault(_SITE_WRITE, payload)
        # re-save of the same epoch (preemption re-save, torn-epoch
        # redo): drop the stale sidecar BEFORE replacing the
        # checkpoint, so a crash between the two replaces degrades to
        # "valid checkpoint, no digest" — never the old digest paired
        # with the new payload
        dpath = digest_path(path)
        if os.path.exists(dpath):
            os.remove(dpath)
        write_atomic_durable(path, written)
        write_atomic_durable(dpath, digest.encode("ascii"))
        ckpt_span.note(bytes=len(payload))
    return path


def verify_checkpoint(path: str, payload: Optional[bytes] = None) -> bool:
    """Check ``path`` against its sha256 sidecar. True when they
    match OR no sidecar exists (legacy checkpoint — nothing to verify
    against); raises :class:`CheckpointCorruptError` on a mismatch.

    ``payload``: the file's already-read bytes, so a verified load
    hashes the SAME buffer it deserializes instead of reading a
    multi-GB checkpoint twice (``load_with_fallback`` walks N
    candidates per host)."""
    dpath = digest_path(path)
    if not os.path.exists(dpath):
        return True
    with open(dpath, "rb") as f:
        expected = f.read().decode("ascii").strip()
    if payload is None:
        with open(path, "rb") as f:
            payload = f.read()
    actual = hashlib.sha256(payload).hexdigest()
    if actual != expected:
        raise CheckpointCorruptError(
            f"checkpoint {path!r} is corrupt: sha256 {actual} does not "
            f"match the recorded digest {expected} ({dpath}) — torn "
            "write, truncation, or bit rot; falling back to the "
            "previous checkpoint is the intended recovery")
    return True


def load_checkpoint(path: str, template: TrainState,
                    verify: bool = True) -> TrainState:
    """Restore a checkpoint into the structure of ``template``
    (a freshly-initialized state with the same model/optimizer).

    ``verify`` (default) checks the sha256 sidecar first: corrupt
    bytes raise :class:`CheckpointCorruptError` naming the file and
    digests instead of a cryptic msgpack unpack error (or a silent
    garbage restore). Checkpoints without a sidecar load unverified.

    Forward-compatible with checkpoints written before a TrainState
    field existed (e.g. ``ema_params``): missing top-level fields keep
    the template's value instead of failing the restore.

    EMA resume semantics: when the template tracks EMA (``--ema``) but
    the checkpoint has none (missing key OR the empty ``{}`` every
    non-EMA checkpoint serializes), the EMA is seeded from the
    checkpoint's TRAINED params — never from the template's fresh
    random init, which would poison every eval for ~1/(1-decay) steps.

    Torch interop: a reference-trained ``model_{epoch}.pth`` is a torch
    zip archive, not msgpack. Detected by magic and routed through
    :mod:`..utils.torch_interop` — params + BN stats load, the
    optimizer starts fresh (torch SGD momentum buffers don't map onto
    this optimizer's tree), and the epoch keeps the template's value.
    """
    # Sniff the torch-zip magic from the FIRST 4 BYTES before
    # committing to a full read: load_torch_checkpoint re-reads from
    # disk itself, so buffering a multi-GB archive here would double
    # the I/O and transiently hold an extra copy. A msgpack state dict
    # starts with a map-header byte, never ``PK\x03\x04``, so the
    # prefix discriminates unambiguously. msgpack checkpoints are read
    # ONCE: the digest check and the deserializer share the buffer.
    with open(path, "rb") as f:
        head = f.read(4)
        is_torch_zip = head == b"PK\x03\x04"
        payload = None if is_torch_zip else head + f.read()
    if verify:
        # torch zips never get a sidecar written (reference artifacts);
        # verify_checkpoint re-reads the file only when one exists.
        verify_checkpoint(path, payload=payload)
    if is_torch_zip:
        from ..utils.torch_interop import load_torch_checkpoint

        params, stats = load_torch_checkpoint(
            path, template.params, template.batch_stats
        )
        state = template.replace(params=params, batch_stats=stats)
        if getattr(template, "ema_params", None):
            state = state.replace(ema_params=params)
        return state
    state_dict = serialization.msgpack_restore(payload)
    template_dict = serialization.to_state_dict(template)
    if template_dict.get("ema_params") and not state_dict.get("ema_params"):
        state_dict["ema_params"] = state_dict["params"]
    for key, value in template_dict.items():
        state_dict.setdefault(key, value)
    return serialization.from_state_dict(template, state_dict)


def _checkpoint_epochs(save_path: str):
    """``[(epoch, filename), ...]`` for every parseable ``model_*.pth``
    under ``save_path`` — the ONE place the naming scheme is decoded
    (prune/latest/auto-resume all consume this)."""
    found = []
    if not os.path.isdir(save_path):
        return found
    for name in os.listdir(save_path):
        if name.startswith("model_") and name.endswith(".pth"):
            try:
                found.append((int(name[len("model_"):-len(".pth")]), name))
            except ValueError:
                continue
    return found


def prune_checkpoints(save_path: str, keep: int) -> None:
    """Delete all but the ``keep`` highest-epoch ``model_*.pth`` files.

    Primary-host-only callers (the trainer gates this like the writes);
    ``keep <= 0`` disables pruning. Removes the LISTED filename (never a
    reconstructed one — ``model_007.pth`` parses to epoch 7 but is not
    named ``model_7.pth``).
    """
    if keep <= 0:
        return
    for _, name in sorted(_checkpoint_epochs(save_path))[:-keep]:
        path = os.path.join(save_path, name)
        os.remove(path)
        # the digest sidecar lives and dies with its checkpoint
        if os.path.exists(digest_path(path)):
            os.remove(digest_path(path))


def latest_checkpoint(save_path: str) -> Optional[str]:
    """Highest-epoch ``model_*.pth`` under ``save_path``, if any."""
    found = _checkpoint_epochs(save_path)
    return os.path.join(save_path, max(found)[1]) if found else None


def checkpoint_epoch(path: str) -> Optional[int]:
    """Epoch parsed from a ``model_<epoch>.pth`` path, else ``None``.

    The inverse of the naming scheme :func:`_checkpoint_epochs`
    decodes; ``--resume auto`` callers use it to turn the
    primary-resolved path back into the ``anchor`` epoch for
    :func:`load_with_fallback`."""
    name = os.path.basename(path)
    if name.startswith("model_") and name.endswith(".pth"):
        try:
            return int(name[len("model_"):-len(".pth")])
        except ValueError:
            pass
    return None


def load_with_fallback(save_path: str, template: TrainState, *,
                       anchor: Optional[int] = None,
                       ) -> Tuple[TrainState, str]:
    """Resume from the newest VALID checkpoint under ``save_path``.

    ``anchor``: cap the walk at this epoch (checkpoints newer than it
    are ignored, not treated as candidates). ``--resume auto`` passes
    the primary-resolved epoch here, so a STALE extra checkpoint on
    one host (newer than what the primary resolved) cannot shift that
    host's walk and get misdiagnosed as cross-host divergence.

    The corrupt-checkpoint recovery path: walk checkpoints newest to
    oldest, verify each digest, restore the first that passes —
    reporting (stderr, primary host) every corrupt artifact skipped,
    with its digest mismatch. Training then resumes at the fallback's
    epoch (the restored ``state.epoch``; the torn epoch is redone,
    exactly like a preemption resume). Raises the LAST
    :class:`CheckpointCorruptError` when every checkpoint is corrupt,
    ``FileNotFoundError`` when there are none.

    Multi-host: digests verify against HOST-LOCAL bytes, so a corrupt
    copy on one host must not silently shift just that host to an
    older epoch — the split-brain :func:`resolve_auto_resume` exists
    to prevent. After the walk, every host — including one whose walk
    found nothing valid — reaches ONE agreement collective with its
    verified epoch (``-1`` = exhausted), and on any divergence EVERY
    host raises: an asymmetric check (peer dies, primary proceeds)
    would leave the survivors wedged forever at their next training
    collective instead of failing loudly.

    Returns ``(state, path_loaded)``."""
    found = _checkpoint_epochs(save_path)
    if anchor is not None:
        found = [(e, n) for e, n in found if e <= anchor]
    last_err: Optional[CheckpointCorruptError] = None
    chosen = None  # (epoch, path, state)
    for epoch, name in sorted(found, reverse=True):
        path = os.path.join(save_path, name)
        try:
            state = load_checkpoint(path, template)
        except CheckpointCorruptError as e:
            last_err = e
            if dist.is_primary():
                import sys

                print(f"[pmdt] {e}\n[pmdt] falling back to the "
                      "previous checkpoint", file=sys.stderr)
            continue
        chosen = (epoch, path, state)
        break
    _require_fallback_agreement(
        -1 if chosen is None else chosen[0],
        save_path if chosen is None else chosen[1])
    if chosen is None:
        if last_err is not None:
            raise last_err
        raise FileNotFoundError(
            f"no model_*.pth checkpoints under {save_path!r}")
    return chosen[2], chosen[1]


def _require_fallback_agreement(epoch: int, path: str) -> None:
    """Every host must fall back to the SAME epoch, or ALL die loudly.

    Symmetric by construction: each host contributes its verified
    epoch (``-1`` = walk exhausted) to one all-gather that every host
    reaches exactly once, then applies the same unanimity check — so
    divergence kills the whole job with a named error on every rank,
    never a survivor hanging at its next collective."""
    if jax.process_count() == 1:
        return
    import numpy as _np
    from jax.experimental import multihost_utils

    epochs = _np.asarray(
        multihost_utils.process_allgather(_np.int32(epoch)))
    if int(epochs.min()) == int(epochs.max()):
        return
    raise CheckpointCorruptError(
        f"--resume auto fallback diverged across hosts: per-host "
        f"verified epochs {epochs.tolist()} (this host, rank "
        f"{dist.get_rank()}: epoch {epoch}, {path}; -1 = every local "
        "copy corrupt). A newer checkpoint copy is corrupt on some "
        "host — restore/re-sync save_path across hosts instead of "
        "resuming split-brain (epoch-skewed save collectives "
        "deadlock). Raised on EVERY rank so no host survives to hang")


def resolve_auto_resume(save_path: str) -> Optional[str]:
    """Multi-host-safe ``--resume auto``: the PRIMARY host's latest
    checkpoint decides for everyone.

    Resolving independently per host can silently disagree (workers with
    a host-local save_path see no files, start at epoch 1, and the
    per-epoch save collectives then deadlock against the primary's
    shifted epoch range). The primary's epoch is broadcast; every other
    host must find the same file locally or fail loudly — ``--resume
    auto`` across hosts requires a shared filesystem.
    """
    found = _checkpoint_epochs(save_path)
    # -1 = no checkpoint: epoch 0 is LEGAL (the preemption handler saves
    # model_0.pth when interrupted during epoch 1)
    my_epoch = max(found)[0] if found else -1
    if jax.process_count() == 1:
        return latest_checkpoint(save_path) if found else None
    from jax.experimental import multihost_utils

    epoch = int(multihost_utils.broadcast_one_to_all(my_epoch))
    if epoch < 0:
        return None
    match = [name for e, name in found if e == epoch]
    # symmetric presence check: EVERY host reaches this one all-gather
    # and every host applies the same test, so a missing file kills
    # the whole job loudly — a host raising alone (while the others
    # proceed into load_with_fallback's agreement collective) would
    # leave them wedged forever instead
    import numpy as _np

    has = _np.asarray(
        multihost_utils.process_allgather(_np.int32(bool(match))))
    if int(has.min()) == 0:
        raise FileNotFoundError(
            f"--resume auto: primary host resolved epoch {epoch} but "
            f"{int((has == 0).sum())} host(s) have no matching "
            f"model_*.pth under {save_path} (this host, rank "
            f"{dist.get_rank()}: "
            f"{'found' if match else 'missing'}) — auto-resume across "
            "hosts requires save_path on a SHARED filesystem (or pass "
            "an explicit --resume path). Raised on EVERY rank so no "
            "host survives to hang at the next collective"
        )
    return os.path.join(save_path, match[0])
