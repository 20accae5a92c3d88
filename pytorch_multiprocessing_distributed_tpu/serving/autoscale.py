"""graftscale: traffic-driven fleet autoscaling + zero-downtime
weight rollout.

The reference trainer fixes its world size at spawn time
(``mp.spawn(..., nprocs=ngpus)``); our fleet did the serving-side
equivalent — ``--replicas N`` was a CLI constant, while the router
already measured everything an autoscaler needs: AIMD admission
windows, :class:`~.router.FleetSaturated` sheds, pending-queue depth,
per-replica ``goodput_frac``, and the TTL'd replica directory. This
module closes the loop: TRAFFIC decides the fleet size, not a flag.

Two host-side policy machines, both tick-driven (one ``tick()``
beside every ``router.step()`` — no threads, no timers, fully
deterministic under test):

1. :class:`FleetAutoscaler` — membership from the router's own
   signals, under graftheal Supervisor discipline (bounded spawn
   budgets, named failures, never a spin):

   - **Scale-up** triggers on SUSTAINED saturation — fresh
     ``FleetSaturated`` sheds, or pending-queue depth above the
     fleet's combined admission windows — ``up_after`` consecutive
     ticks, not one blip.
   - **Scale-down** drains the least-loaded replica (lowest
     ``goodput_frac`` among the idle — the existing ``begin_drain``
     → step-to-empty → ``drain`` verbs) only after ``down_after``
     consecutive idle ticks, and never below ``min_replicas``.
   - **Hysteresis + cooldown**: up_after << down_after, plus a
     ``cooldown`` tick freeze after EVERY membership change — the
     fleet never flaps (test-pinned: a square-wave load produces a
     bounded event sequence, not oscillation).
   - **Roles scale independently**: the transfer backlog vs decode
     windows predicate (the one ``_place_transfers`` already holds
     against) means the DECODE side is the bottleneck; prefill
     intake saturating every prefill window while transfers flow
     means the PREFILL side is. Each signal drives its own role's
     spawn.
   - **Prewarm before admission**: a freshly spawned decode replica
     replays the fleet prefix directory's hottest prompts through
     its own engine (:meth:`~.replica.ServingReplica.prewarm`)
     BEFORE ``router.add_replica`` makes it routable — its first
     client request pays a warm TTFT, and the warm-up tokens are
     subtracted from the fleet merge.
   - **Reap hygiene**: replicas the router reaped (died mid-run,
     work already redelivered) are retired from the roster, their
     child processes released (wait → kill, loudly), and the
     min-replica floor respawns capacity — the autoscaler is the
     fleet's supervisor, with the same bounded-budget discipline.

2. :class:`RollingRollout` — a weight upgrade served under
   continuous load with ZERO failed requests: for each old-version
   replica, a new-weights replica (per-version ``model_tag``
   published through ``fleet.publish_replica``) spawns, prewarms and
   JOINS before the old one begins draining, so admission capacity
   never touches zero. Old replicas finish their in-flight requests
   on OLD weights (drain semantics); new requests route to the new
   version — every request runs start-to-finish on exactly one
   version, and the router pins that: transfers only splice
   same-tag, redelivery prefers same-tag peers. Per-version
   token-exactness is the acceptance pin (each stream byte-identical
   to a fixed fleet of its serving version).

The spawn seam is a two-method protocol (``spawn``/``release``) with
two implementations: :class:`EngineReplicaSpawner` (in-process
engines — tests, benches, the ``serve_lm.py --autoscale`` CLI) and
:class:`ProcessReplicaSpawner` (``--listen`` replica-server
subprocesses dialed through :class:`~.remote.RemoteReplica` — the
deployment shape; children are ALWAYS reaped: wait with a deadline,
then kill loudly, per graftlint GL118).

All host-side: no jitted program changes — graftcheck fingerprints
and cost budgets do not move.
"""

from __future__ import annotations

import os
import subprocess
import time
from typing import Callable, Dict, List, Optional, Sequence

from ..runtime import heal
from ..runtime import scope as graftscope
from ..runtime.faults import GraftFaultError
from .replica import ServingReplica

__all__ = ["AutoscaleError", "SpawnFailed", "ScaleEvent",
           "EngineReplicaSpawner", "ProcessReplicaSpawner",
           "FleetAutoscaler", "RollingRollout"]


class AutoscaleError(GraftFaultError):
    """Named-fatal family for the autoscaler: a supervisor's restart
    budget consumes these like any engine fatal."""


class SpawnFailed(AutoscaleError):
    """One replica spawn attempt failed (engine build error, child
    exited before publishing an address, dial refused). Restartable:
    the per-spawn :class:`~..runtime.heal.Supervisor` retries it
    within the bounded budget; exhaustion surfaces as
    :class:`~..runtime.heal.RestartBudgetExhausted` with this
    chained."""


class ScaleEvent:
    """One membership decision, for the bench/operator timeline."""

    __slots__ = ("tick", "action", "rid", "role", "reason", "t")

    def __init__(self, tick: int, action: str, rid: str, role: str,
                 reason: str):
        self.tick = int(tick)
        self.action = str(action)  # spawn | drain | retire | ...
        self.rid = str(rid)
        self.role = str(role)
        self.reason = str(reason)
        self.t = time.perf_counter()

    def to_dict(self) -> Dict:
        return {"tick": self.tick, "action": self.action,
                "rid": self.rid, "role": self.role,
                "reason": self.reason}

    def __repr__(self) -> str:
        return (f"ScaleEvent({self.action} {self.rid} role="
                f"{self.role} @tick {self.tick}: {self.reason})")


# ------------------------------------------------------- spawn seams

class EngineReplicaSpawner:
    """In-process spawn seam: builds a fresh
    :class:`~.engine.ServingEngine` per replica.

    Args:
      build_engine: ``build_engine(model_tag, journal) -> engine`` —
        the version-aware engine factory (``model_tag`` selects the
        weight set; None = the base version).
      journal_for: optional ``journal_for(rid) -> RequestJournal`` —
        arms a per-replica redelivery WAL.

    ``release`` is a no-op (nothing to reap in-process); build
    errors surface as :class:`SpawnFailed` so the same supervised
    spawn path covers both seams.
    """

    def __init__(self, build_engine: Callable[..., object], *,
                 journal_for: Optional[Callable[[str], object]] = None):
        self._build = build_engine
        self._journal_for = journal_for

    def spawn(self, rid: str, role: str = "both",
              model_tag: Optional[str] = None) -> ServingReplica:
        journal = (self._journal_for(rid) if self._journal_for
                   else None)
        try:
            engine = self._build(model_tag, journal)
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:
            raise SpawnFailed(
                f"engine build for replica {rid!r} (tag "
                f"{model_tag!r}) failed: {type(e).__name__}: {e}"
            ) from e
        return ServingReplica(rid, engine, role=role, journal=journal,
                              model_tag=model_tag)

    def release(self, rid: str, deadline_s: float = 10.0) -> None:
        pass  # in-process engines have no child to reap

    def shutdown(self) -> None:
        pass


def refuse_children_beside_a_tpu(what: str) -> None:
    """A chip belongs to one process: a parent that has initialized
    jax on a TPU holds it, and a replica child that needs it would
    fail or hang. Until replicas share a host's chips from ONE process
    (ROADMAP B2) the process fleet is a CPU-mesh rehearsal — so this
    refuses, loudly, instead of hanging. A parent that never imported
    jax holds no chip and may spawn."""
    import sys

    jax = sys.modules.get("jax")
    if jax is not None and jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what}: this process holds the TPU, so a replica child "
            "could never reach it (one process per chip). The process "
            "fleet is a CPU-mesh rehearsal: run it with "
            "JAX_PLATFORMS=cpu (PMDT_FORCE_CPU_DEVICES=N for a mesh)")


class ProcessReplicaSpawner:
    """Subprocess spawn seam: each replica is a ``--listen``
    replica-server child, dialed through
    :class:`~.remote.RemoteReplica` once it publishes its address.
    A CPU-mesh rehearsal for now: :meth:`spawn` refuses when this
    process holds a TPU (:func:`refuse_children_beside_a_tpu`).

    Args:
      argv_for: ``argv_for(rid, role, model_tag, addr_file) ->
        [cmd...]`` — the child command; the child must write its
        bound ``host:port`` to ``addr_file`` ATOMICALLY (write a tmp
        name, ``os.replace``) once listening. ``benchmarks/
        scale_smoke.py --serve_replica`` and ``serve_lm.py --listen``
        are the two shipped bodies.
      workdir: directory for address files (caller-owned tempdir).
      spawn_timeout_s: how long a child may take to publish before
        the spawn attempt fails named (the child is killed first —
        a half-started orphan is worse than a retry).
      client_kw: extra :class:`~.remote.RemoteReplica` kwargs.

    Reaping discipline (graftlint GL118): every child this class
    starts is released through :meth:`release` / :meth:`shutdown` —
    ``wait`` with a deadline, ``terminate``, then ``kill`` LOUDLY.
    An autoscaler that leaks children is an incident generator.
    """

    def __init__(self, argv_for: Callable[..., List[str]],
                 workdir: str, *, spawn_timeout_s: float = 120.0,
                 poll_s: float = 0.1,
                 sleep: Callable[[float], None] = time.sleep,
                 **client_kw):
        self._argv_for = argv_for
        self.workdir = str(workdir)
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.poll_s = float(poll_s)
        self._sleep = sleep
        self._client_kw = client_kw
        self._children: Dict[str, subprocess.Popen] = {}

    def spawn(self, rid: str, role: str = "both",
              model_tag: Optional[str] = None) -> ServingReplica:
        from .remote import RemoteReplica

        refuse_children_beside_a_tpu(f"spawning replica {rid!r}")
        addr_file = os.path.join(self.workdir, f"addr_{rid}")
        try:
            os.remove(addr_file)  # a retry must not read last
        except OSError:          # attempt's address
            pass
        argv = self._argv_for(rid, role, model_tag, addr_file)
        try:
            proc = subprocess.Popen(argv)
        except OSError as e:
            raise SpawnFailed(
                f"replica child {rid!r} failed to start: {e}") from e
        t0 = time.perf_counter()
        address = None
        while time.perf_counter() - t0 < self.spawn_timeout_s:
            if os.path.exists(addr_file):
                with open(addr_file) as f:
                    address = f.read().strip()
                break
            if proc.poll() is not None:
                raise SpawnFailed(
                    f"replica child {rid!r} exited "
                    f"{proc.returncode} before publishing an "
                    f"address (argv: {' '.join(argv)})")
            self._sleep(self.poll_s)
        if not address:
            proc.kill()
            proc.wait()
            raise SpawnFailed(
                f"replica child {rid!r} published no address within "
                f"{self.spawn_timeout_s}s; killed")
        self._children[rid] = proc
        try:
            replica = RemoteReplica(address, rid=rid,
                                    **self._client_kw)
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:
            self.release(rid)
            raise SpawnFailed(
                f"replica child {rid!r} at {address!r} refused the "
                f"dial: {type(e).__name__}: {e}") from e
        replica.model_tag = (None if model_tag is None
                             else str(model_tag))
        return replica

    def release(self, rid: str, deadline_s: float = 30.0) -> None:
        """Reap one child: wait for the clean exit a drain produces,
        escalate to terminate, then kill -9 — loudly. Never leaks."""
        proc = self._children.pop(rid, None)
        if proc is None:
            return
        try:
            proc.wait(timeout=deadline_s)
            return
        except subprocess.TimeoutExpired:
            pass
        print(f"graftscale: replica child {rid!r} (pid {proc.pid}) "
              f"did not exit within {deadline_s}s of its drain; "
              "terminating", flush=True)
        proc.terminate()
        try:
            proc.wait(timeout=5.0)
        except subprocess.TimeoutExpired:
            print(f"graftscale: replica child {rid!r} (pid "
                  f"{proc.pid}) ignored SIGTERM; killing -9",
                  flush=True)
            proc.kill()
            proc.wait()

    def shutdown(self, deadline_s: float = 10.0) -> None:
        for rid in list(self._children):
            self.release(rid, deadline_s=deadline_s)

    @property
    def children(self) -> Dict[str, int]:
        """Live child pids by rid (observability + tests)."""
        return {rid: p.pid for rid, p in self._children.items()}


# ----------------------------------------------------- the policy loop

class FleetAutoscaler:
    """Traffic-driven fleet membership: call :meth:`tick` once beside
    every ``router.step()``.

    Args:
      router: the live :class:`~.router.Router`.
      spawner: :class:`EngineReplicaSpawner` or
        :class:`ProcessReplicaSpawner`.
      min_replicas / max_replicas: decode-capable bounds (the floor
        is enforced — a reaped replica below it respawns, and a
        respawn failure past the spawn budget propagates named).
      min_prefill / max_prefill: prefill-role bounds (0/0 = a fleet
        with no prefill role never grows one).
      up_after: consecutive saturated ticks before a scale-up.
      down_after: consecutive idle ticks before a scale-down
        (hysteresis: keep ``down_after >> up_after``).
      cooldown: ticks with NO membership changes after any change.
      spawn_retries / spawn_backoff_s: the per-spawn Supervisor
        budget (named exhaustion, never a spin).
      prewarm_prompts: hottest prefix-directory prompts replayed
        through a joining decode replica before it admits.
      model_tag: version label for spawned replicas (a
        :class:`RollingRollout` retargets this to the new version).
      sleep: injectable (tests never wait).
    """

    def __init__(self, router, spawner, *, min_replicas: int = 1,
                 max_replicas: int = 4, min_prefill: int = 0,
                 max_prefill: int = 0, up_after: int = 2,
                 down_after: int = 8, cooldown: int = 5,
                 spawn_retries: int = 1, spawn_backoff_s: float = 0.0,
                 prewarm_prompts: int = 4,
                 model_tag: Optional[str] = None,
                 rid_prefix: str = "as",
                 sleep: Callable[[float], None] = time.sleep):
        if min_replicas < 1:
            raise ValueError(
                f"min_replicas must be >= 1, got {min_replicas}")
        if max_replicas < min_replicas:
            raise ValueError(
                f"max_replicas {max_replicas} < min_replicas "
                f"{min_replicas}")
        if max_prefill < min_prefill:
            raise ValueError(
                f"max_prefill {max_prefill} < min_prefill "
                f"{min_prefill}")
        self.router = router
        self.spawner = spawner
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.min_prefill = int(min_prefill)
        self.max_prefill = int(max_prefill)
        self.up_after = int(up_after)
        self.down_after = int(down_after)
        self.cooldown = int(cooldown)
        self.spawn_retries = int(spawn_retries)
        self.spawn_backoff_s = float(spawn_backoff_s)
        self.prewarm_prompts = int(prewarm_prompts)
        self.rid_prefix = str(rid_prefix)
        self._sleep = sleep
        if model_tag is None:
            for r in router.replicas:
                if r.decode_capable:
                    model_tag = r.model_tag
                    break
        self.model_tag = model_tag
        self._tick = 0
        self._seq = 0
        self._cooldown_left = 0
        self._sat_ticks = {"decode": 0, "prefill": 0}
        self._idle_ticks = {"decode": 0, "prefill": 0}
        self._shed_base = router.requests_shed_fleet
        self._draining: Dict[str, ServingReplica] = {}
        self.events: List[ScaleEvent] = []
        self.scale_ups = 0
        self.scale_downs = 0
        self.spawn_failures = 0

    # ---- roster views --------------------------------------------------
    def _alive(self, role: str) -> List[ServingReplica]:
        """Replicas still carrying capacity for ``role``: live, not
        reaped, and not already draining toward removal."""
        out = []
        for r in self.router.replicas:
            if r.dead or r.reaped or r.rid in self._draining:
                continue
            if role == "decode" and r.decode_capable:
                out.append(r)
            elif role == "prefill" and r.role == "prefill":
                out.append(r)
        return out

    def _next_rid(self) -> str:
        while True:
            rid = f"{self.rid_prefix}{self._seq}"
            self._seq += 1
            if rid not in self.router._by_rid:
                return rid

    def _event(self, action: str, rid: str, role: str,
               reason: str) -> None:
        event = ScaleEvent(self._tick, action, rid, role, reason)
        self.events.append(event)
        graftscope.emit("scale.event", cat="serving",
                        action=action, rid=rid, role=role,
                        reason=reason, tick=self._tick)

    # ---- signals -------------------------------------------------------
    def signals(self) -> Dict:
        """The policy inputs, one dict — the same numbers
        ``merged_metrics`` exposes on /snapshot.json (``fleet_pending``
        / ``fleet_admit_window_total`` / sheds), read live."""
        router = self.router
        decode = self._alive("decode")
        prefill = self._alive("prefill")
        return {
            "pending": router.pending_depth,
            "transfers": router.transfer_depth,
            "transfer_backlog_full": (router.transfer_backlog_full
                                      if prefill else False),
            "shed_total": router.requests_shed_fleet,
            "decode_window_total": sum(r.window for r in decode),
            "decode_in_flight": sum(r.in_flight for r in decode),
            "prefill_window_total": sum(r.window for r in prefill),
            "prefill_in_flight": sum(r.in_flight for r in prefill),
            "n_decode": len(decode),
            "n_prefill": len(prefill),
            "n_draining": len(self._draining),
        }

    # ---- membership actions --------------------------------------------
    def spawn_replica(self, role: str = "both",
                      model_tag: Optional[str] = None,
                      required: bool = False,
                      reason: str = "scale_up"
                      ) -> Optional[ServingReplica]:
        """Supervised spawn + prewarm + join. ``required`` spawns
        (min-floor enforcement, rollout replacements) propagate
        budget exhaustion named; opportunistic ones absorb it into
        ``spawn_failures`` + a cooldown and return None."""
        rid = self._next_rid()
        tag = self.model_tag if model_tag is None else model_tag
        supervisor = heal.Supervisor(
            lambda attempt: self.spawner.spawn(rid, role, tag),
            max_restarts=self.spawn_retries,
            backoff_s=self.spawn_backoff_s,
            sleep=self._sleep,
            name=f"graftscale spawn {rid} ({role})")
        try:
            replica = supervisor.run()
        except heal.RestartBudgetExhausted:
            self.spawn_failures += 1
            self._cooldown_left = self.cooldown
            self._event("spawn_failed", rid, role, reason)
            if required:
                raise
            return None
        if replica.decode_capable and self.prewarm_prompts > 0:
            prompts = self._hot_prompts()
            if prompts:
                replica.prewarm(prompts)
        self.router.add_replica(replica)
        self.scale_ups += 1
        self._cooldown_left = self.cooldown
        self._sat_ticks[
            "decode" if replica.decode_capable else "prefill"] = 0
        self._event("spawn", rid, role, reason)
        return replica

    def _hot_prompts(self) -> List[Sequence[int]]:
        directory = getattr(self.router, "_directory", None)
        if directory is None:
            return []
        return directory.hot_prompts(self.prewarm_prompts)

    def begin_drain_replica(self, replica: ServingReplica,
                            reason: str = "scale_down") -> None:
        """Close one replica's admission and track it to removal:
        DRAINING replicas keep stepping through the router until
        their in-flight work finishes; :meth:`tick` retires them once
        empty."""
        if replica.rid in self._draining:
            return
        if replica.role == "prefill":
            # un-prefilled intake re-routes now (no tokens exist, a
            # plain re-place is exact — same as the router's reap)
            self.router._pending.extend(replica.withdraw_prefill())
            replica.engine.health.to_draining(reason)
        else:
            replica.engine.begin_drain(reason)
        self._draining[replica.rid] = replica
        self.scale_downs += 1
        self._cooldown_left = self.cooldown
        self._idle_ticks[
            "decode" if replica.decode_capable else "prefill"] = 0
        self._event("drain", replica.rid, replica.role, reason)
        self.router._publish(replica)

    def _advance_draining(self) -> None:
        """Retire draining replicas whose in-flight work finished
        (``drain`` flips them DEAD + compacts the journal), release
        their children, and fold their counters into the router's
        retired totals."""
        for rid, replica in list(self._draining.items()):
            if not (replica.dead or replica.reaped):
                if replica.in_flight:
                    continue  # still finishing on its own weights
                try:
                    replica.engine.drain(None)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except BaseException as e:
                    # died at the very last step: retired either way,
                    # but the death is named in the timeline
                    graftscope.emit("scale.drain_failed",
                                    cat="serving", rid=rid,
                                    error=type(e).__name__)
            del self._draining[rid]
            if rid in self.router._by_rid:
                self.router.remove_replica(rid)
            self.spawner.release(rid)
            self._event("retire", rid, replica.role, "drained")

    def _retire_reaped(self) -> None:
        """Replicas the ROUTER reaped (died mid-run, unfinished work
        already redelivered to peers) leave the roster here, and
        their children are released — the autoscaler owns fleet
        hygiene, the router owns request recovery."""
        for replica in list(self.router.replicas):
            if not replica.reaped:
                continue
            self._draining.pop(replica.rid, None)
            self.router.remove_replica(replica.rid)
            self.spawner.release(replica.rid)
            self._event("retire", replica.rid, replica.role,
                        "reaped")

    # ---- the policy tick ----------------------------------------------
    def tick(self) -> Dict:
        """One policy iteration (call beside every router step):
        advance drains, retire the reaped, enforce the min floor,
        then make AT MOST ONE traffic-driven membership change.
        Returns the signals dict it decided on."""
        self._tick += 1
        self._advance_draining()
        self._retire_reaped()
        sig = self.signals()

        # the floor is not traffic policy: capacity lost to a death
        # respawns immediately (required — exhaustion is named)
        while sig["n_decode"] < self.min_replicas:
            role = "decode" if sig["n_prefill"] else "both"
            self.spawn_replica(role, required=True,
                               reason="min_floor")
            sig = self.signals()
        while sig["n_prefill"] < self.min_prefill:
            self.spawn_replica("prefill", required=True,
                               reason="min_floor")
            sig = self.signals()

        # saturation / idleness sustain counters (hysteresis)
        shed_delta = sig["shed_total"] - self._shed_base
        self._shed_base = sig["shed_total"]
        decode_sat = (shed_delta > 0
                      or sig["pending"] > sig["decode_window_total"]
                      or sig["transfer_backlog_full"])
        # prefill-side bottleneck: intake waits (pending > 0) while
        # the decode side has room (no transfer backlog) and the
        # prefill windows are effectively full — each prefill replica
        # consumes one prompt per step, so "full" is free admission
        # slots <= the number of prefill replicas, not == 0
        prefill_sat = (sig["n_prefill"] > 0
                       and not sig["transfer_backlog_full"]
                       and sig["pending"] > 0
                       and (sig["prefill_window_total"]
                            - sig["prefill_in_flight"])
                       <= sig["n_prefill"])
        self._sat_ticks["decode"] = (
            self._sat_ticks["decode"] + 1 if decode_sat else 0)
        self._sat_ticks["prefill"] = (
            self._sat_ticks["prefill"] + 1 if prefill_sat else 0)
        fleet_idle = (sig["pending"] == 0 and sig["transfers"] == 0)
        self._idle_ticks["decode"] = (
            self._idle_ticks["decode"] + 1
            if fleet_idle and sig["decode_in_flight"] == 0 else 0)
        self._idle_ticks["prefill"] = (
            self._idle_ticks["prefill"] + 1
            if fleet_idle and sig["prefill_in_flight"] == 0 else 0)

        if self._cooldown_left > 0:
            self._cooldown_left -= 1
            return sig

        # at most one traffic-driven change per tick
        if (self._sat_ticks["decode"] >= self.up_after
                and sig["n_decode"] < self.max_replicas):
            role = "decode" if sig["n_prefill"] else "both"
            self.spawn_replica(role, reason="saturated")
        elif (self._sat_ticks["prefill"] >= self.up_after
                and sig["n_prefill"] < self.max_prefill):
            self.spawn_replica("prefill", reason="saturated")
        elif (self._idle_ticks["decode"] >= self.down_after
                and sig["n_decode"] > self.min_replicas):
            self._scale_down("decode")
        elif (self._idle_ticks["prefill"] >= self.down_after
                and sig["n_prefill"] > self.min_prefill):
            self._scale_down("prefill")
        return sig

    def _scale_down(self, role: str) -> None:
        cands = [r for r in self._alive(role) if r.in_flight == 0]
        if not cands:
            return
        # least-loaded victim: lowest goodput fraction among the
        # idle — the replica whose absence costs the least
        victim = min(cands,
                     key=lambda r: r.snapshot().get("goodput_frac",
                                                    0.0))
        self.begin_drain_replica(victim, reason="idle")

    # ---- teardown ------------------------------------------------------
    def shutdown(self) -> None:
        """Release every child the spawner still holds (the end of a
        serve: the router has drained the fleet; children must not
        outlive the policy loop)."""
        self._draining.clear()
        self.spawner.shutdown()

    def metrics(self) -> Dict:
        """The scaler's own counters, merged-snapshot-shaped."""
        sig = self.signals()
        return {
            "scale_ticks": self._tick,
            "scale_ups": self.scale_ups,
            "scale_downs": self.scale_downs,
            "scale_spawn_failures": self.spawn_failures,
            "scale_events": [e.to_dict() for e in self.events],
            "scale_replicas_decode": sig["n_decode"],
            "scale_replicas_prefill": sig["n_prefill"],
        }


# --------------------------------------------------- rolling rollout

class RollingRollout:
    """Zero-downtime weight upgrade: replace every replica whose
    ``model_tag`` differs from ``new_tag``, one at a time, each
    replacement JOINING (spawned + prewarmed + routable) before its
    predecessor begins draining — admission capacity never touches
    zero, so a continuously loaded fleet completes the upgrade with
    zero failed requests (the acceptance pin).

    Drive it beside the serving loop: ``rollout.tick()`` after every
    ``router.step()`` until it returns True. The scaler's draining
    machinery (step-to-empty → ``drain`` → retire → release) does
    the teardown; this class only sequences the waves.

    Version pinning rides the ``model_tag`` plumbing: old replicas
    finish their in-flight requests on old weights (drain
    semantics), new admissions route to the new version once the old
    side stops admitting, transfers splice same-tag only, and
    redelivery prefers same-tag peers — every request is served
    start-to-finish by exactly ONE weight version, and each stream
    is byte-identical to a fixed fleet of that version.
    """

    def __init__(self, scaler: FleetAutoscaler, new_tag: str, *,
                 reason: str = "rollout"):
        self.scaler = scaler
        self.router = scaler.router
        self.new_tag = str(new_tag)
        self.reason = str(reason)
        self.done = False
        self.duration_s: Optional[float] = None
        self.replaced: List[Dict] = []
        self._t0: Optional[float] = None
        self._current: Optional[str] = None
        # the upgrade set is fixed at arm time: every live replica
        # serving a different version (replicas that die mid-rollout
        # leave the set at their wave — the reap already recovered
        # their work, and the min floor respawns at the NEW tag)
        self._old = [r.rid for r in self.router.replicas
                     if not r.dead and not r.reaped
                     and r.model_tag != self.new_tag]

    def tick(self) -> bool:
        """Advance one wave step; True once every old-version replica
        is gone."""
        if self.done:
            return True
        if self._t0 is None:
            self._t0 = time.perf_counter()
            # scale-ups during (and after) the rollout spawn the new
            # version — the floor never resurrects old weights
            self.scaler.model_tag = self.new_tag
            graftscope.emit("scale.rollout_begin", cat="serving",
                            tag=self.new_tag, waves=len(self._old))
        self.scaler._advance_draining()
        if (self._current is not None
                and self._current not in self.router._by_rid):
            self._current = None  # wave complete: old fully retired
        while self._current is None and self._old:
            old_rid = self._old.pop(0)
            old = self.router._by_rid.get(old_rid)
            if old is None or old.reaped:
                continue  # died on its own; work already redelivered
            # replacement joins FIRST (spawn failures propagate named
            # — a rollout that cannot hold capacity must not drain)
            new = self.scaler.spawn_replica(
                old.role, model_tag=self.new_tag, required=True,
                reason=self.reason)
            self.replaced.append({"old": old_rid, "new": new.rid,
                                  "role": old.role})
            self.scaler.begin_drain_replica(old, reason=self.reason)
            self._current = old_rid
        if self._current is None and not self._old:
            self.done = True
            self.duration_s = time.perf_counter() - self._t0
            graftscope.emit("scale.rollout_done", cat="serving",
                            tag=self.new_tag,
                            replaced=len(self.replaced),
                            duration_s=self.duration_s)
        return self.done
