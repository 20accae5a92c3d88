"""graftscale smoke: the full elastic-fleet lifecycle against REAL
``--listen`` replica subprocesses — spawn-from-zero, a traffic burst
that scales the fleet UP, an idle plateau that drains it back DOWN,
then a rolling v1->v2 weight rollout under load — children reaped
loudly, zero failed requests, every stream pinned to exactly one
weight version.

The ``make scale`` target (and the slow tier-1 mirror,
``test_scale_smoke_script_end_to_end``) runs this module. The parent
holds the router + :class:`FleetAutoscaler` over a
:class:`ProcessReplicaSpawner`; every replica is a subprocess
(``python benchmarks/scale_smoke.py --serve_replica --tag vN ...``)
building a tiny engine from a per-version seed (v1 = seed 1, v2 =
seed 2 — so per-version byte-exactness is checkable against
in-parent reference engines) and publishing its bound address
atomically through ``--addr_file``.

Asserted end to end:

1. **spawn-from-zero** — the spawner boots the first replica; the
   autoscaler's min floor owns fleet existence, not a CLI constant;
2. **burst -> scale-up** — sustained ``FleetSaturated`` sheds grow
   the fleet (bounded by max), and every burst request completes;
3. **idle -> scale-down** — a quiet plateau drains the extra
   replicas (hysteresis: one change at a time, cooldown between),
   their CHILD PROCESSES exit (wait-then-kill, loudly);
4. **rolling rollout** — v2 replicas join + prewarm BEFORE v1
   replicas drain; zero failed requests, every stream byte-identical
   to a fixed single-version engine (v1 or v2, never a mix);
5. **no leaks** — at exit every spawned pid has been reaped; a
   leaked child is a test FAILURE, not a shrug.

Exit code 0 and one ``graftscale smoke OK`` line = the elastic fleet
is deployable. Run: ``python benchmarks/scale_smoke.py``
(A CPU-mesh rehearsal — ``ProcessReplicaSpawner`` refuses to start
children from a parent that holds a TPU; tiny model, a few minutes —
each subprocess pays the jax import).
"""

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MAX_NEW = 4
SEEDS = {"v1": 1, "v2": 2}


def _tiny_model():
    from pytorch_multiprocessing_distributed_tpu import models

    return models.GPT(vocab_size=61, max_seq_len=64, hidden_size=32,
                      num_layers=2, num_heads=2, mlp_dim=64,
                      attn_impl="xla")


def _engine(tag="v1"):
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ServingEngine, init_params)

    model = _tiny_model()
    # per-version seeds: parent reference engines and every child of
    # that tag build bit-identical params, so per-version exactness
    # is a ROLLOUT claim, not a luck claim
    params = init_params(model, SEEDS[tag])
    return ServingEngine(model, params, max_slots=2, s_max=32,
                         min_bucket=8, retry_backoff_s=0.0)


def _prompts(n=6):
    import numpy as np

    rng = np.random.default_rng(0)
    return [rng.integers(0, 61, (int(rng.integers(4, 16)),)).tolist()
            for _ in range(n)]


# --------------------------------------------------------------- child

def serve_replica(args) -> int:
    """The subprocess body: one tagged engine behind a ReplicaServer,
    address handed to the parent through ``--addr_file``, alive until
    the autoscaler drains it."""
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ReplicaServer)

    engine = _engine(args.tag)
    server = ReplicaServer(engine, rid=args.rid, role=args.role)
    server.start()
    tmp = args.addr_file + ".tmp"
    with open(tmp, "w") as f:
        f.write(server.address)
    os.replace(tmp, args.addr_file)  # atomic: parent never reads half
    print(f"graftscale smoke replica {args.rid} ({args.tag}): "
          f"listening on {server.address} (pid {os.getpid()})",
          flush=True)
    server.serve_forever()
    return 0


# -------------------------------------------------------------- parent

def run_smoke(verbose: bool = True) -> dict:
    from pytorch_multiprocessing_distributed_tpu.serving import (
        FleetAutoscaler, FleetSaturated, ProcessReplicaSpawner,
        RollingRollout, Router)

    def note(msg):
        if verbose:
            print(msg, flush=True)

    prompts = _prompts()
    # per-version byte-identity references, computed in-parent
    ref = {}
    for tag in ("v1", "v2"):
        engine = _engine(tag)
        out = engine.serve([(list(p), MAX_NEW) for p in prompts])
        ref[tag] = {tuple(prompts[i]): list(r.tokens)
                    for i, r in enumerate(out)}
    note(f"references: {len(prompts)} streams per version, "
         f"{sum(len(t) for t in ref['v1'].values())} tokens each")

    tmpdir = tempfile.mkdtemp(prefix="pmdt_scale_smoke_")

    def argv_for(rid, role, tag, addr_file):
        return [sys.executable, os.path.abspath(__file__),
                "--serve_replica", "--rid", rid, "--role", role,
                "--tag", tag or "v1", "--addr_file", addr_file]

    spawner = ProcessReplicaSpawner(argv_for, tmpdir,
                                    spawn_timeout_s=180.0)
    report = {"scale_ups": 0, "scale_downs": 0,
              "requests_failed": -1, "leaked_children": None}
    try:
        # ---- 1. spawn-from-zero: the spawner boots the first
        # replica; the scaler's min floor owns it from here
        t0 = time.perf_counter()
        boot = spawner.spawn("s0", "both", "v1")
        note(f"spawn-from-zero: s0 up in "
             f"{time.perf_counter() - t0:.1f}s (pid "
             f"{spawner.children['s0']})")
        router = Router([boot], max_pending=4)
        scaler = FleetAutoscaler(
            router, spawner, min_replicas=1, max_replicas=3,
            up_after=2, down_after=8, cooldown=4, model_tag="v1",
            rid_prefix="s", spawn_retries=1)
        scaler._seq = 1  # s0 is the boot replica
        timeline = []

        def pump():
            events = router.step()
            scaler.tick()
            timeline.append((scaler._tick, len(router.replicas)))
            return events

        # ---- 2. burst -> scale-up: sustained sheds past max_pending
        uid = [0]

        def offer(n):
            for _ in range(n):
                p = prompts[uid[0] % len(prompts)]
                try:
                    router.submit(list(p), MAX_NEW,
                                  uid=f"u{uid[0]}")
                    uid[0] += 1
                except FleetSaturated:
                    pass
        for _ in range(20):
            offer(2)
            pump()
        steps = 0
        while (router.in_flight or router.pending_depth) \
                and steps < 5000:
            pump()
            steps += 1
        assert scaler.scale_ups >= 1, (
            f"burst never scaled up: {scaler.signals()}")
        peak = max(n for _, n in timeline)
        note(f"burst: scaled up to {peak} replicas "
             f"({scaler.scale_ups} spawn(s)), {uid[0]} requests "
             "admitted and drained")

        # ---- 3. idle plateau -> scale-down to min, children exit
        for _ in range(40):
            pump()
        assert len(router.replicas) == 1, (
            f"idle fleet should drain to min: "
            f"{[r.rid for r in router.replicas]}")
        assert scaler.scale_downs >= 1
        assert len(spawner.children) == 1, (
            f"drained children must be reaped: {spawner.children}")
        note(f"idle: drained back to 1 replica "
             f"({scaler.scale_downs} retire(s)); drained children "
             "exited on their own")

        # ---- 4. rolling rollout v1 -> v2 under continuous load
        rollout = RollingRollout(scaler, "v2")
        target = uid[0] + 2 * len(prompts)
        for _ in range(5000):
            if uid[0] < target:
                offer(1)
            pump()
            rollout.tick()
            if (rollout.done and uid[0] >= target
                    and not router.in_flight
                    and not router.pending_depth):
                break
        assert rollout.done, "rollout did not converge"
        assert all(r.model_tag == "v2" for r in router.replicas)
        recs = router.records()
        failed = [u for u, r in recs.items() if r.state != "done"]
        assert not failed, f"rollout failed requests: {failed}"
        mixed = []
        for u, rec in recs.items():
            key = tuple(rec.prompt)
            want = (ref["v1"].get(key), ref["v2"].get(key))
            if list(rec.tokens) not in want:
                mixed.append(u)
        assert not mixed, (
            f"streams matching NEITHER version (mixed weights): "
            f"{mixed}")
        note(f"rollout: {len(rollout.replaced)} replica(s) replaced "
             f"v1->v2 in {rollout.duration_s:.1f}s under load; "
             f"{len(recs)} streams total, 0 failed, every stream "
             "byte-exact to one version")

        # ---- 5. teardown: drain the fleet, reap every child
        router.drain(None)
        scaler.shutdown()
        leaked = sorted(spawner.children)
        report.update({
            "scale_ups": scaler.scale_ups,
            "scale_downs": scaler.scale_downs,
            "spawn_failures": scaler.spawn_failures,
            "requests_total": len(recs),
            "requests_failed": len(failed),
            "peak_replicas": peak,
            "replicas_timeline": timeline[-200:],
            "events": [e.to_dict() for e in scaler.events],
            "rollout": {"duration_s": rollout.duration_s,
                        "replaced": rollout.replaced},
            "leaked_children": leaked,
        })
        assert not leaked, f"leaked replica children: {leaked}"
        note("teardown: every child reaped; no leaks")
    finally:
        spawner.shutdown(deadline_s=5.0)
        import shutil

        shutil.rmtree(tmpdir, ignore_errors=True)
    return report


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--serve_replica", action="store_true",
                        help="internal: run as one replica-server "
                             "subprocess")
    parser.add_argument("--rid", default="s0")
    parser.add_argument("--role", default="both")
    parser.add_argument("--tag", default="v1", choices=sorted(SEEDS))
    parser.add_argument("--addr_file", default="")
    parser.add_argument("--out", default="",
                        help="write the smoke report JSON here")
    args = parser.parse_args(argv)
    from pytorch_multiprocessing_distributed_tpu.utils.hostenv import (
        force_cpu_devices_from_env)

    force_cpu_devices_from_env()
    if args.serve_replica:
        if not args.addr_file:
            raise SystemExit("--serve_replica needs --addr_file")
        return serve_replica(args)
    report = run_smoke(verbose=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    print("graftscale smoke OK " + json.dumps(
        {k: report[k] for k in ("scale_ups", "scale_downs",
                                "requests_failed",
                                "leaked_children")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
