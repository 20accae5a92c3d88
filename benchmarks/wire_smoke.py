"""graftwire smoke: a router driving 2 REAL replica-server
subprocesses over localhost sockets must stream byte-identically to
the in-process fleet, meter the PageTransfer bytes it ships, and
survive a ``SIGKILL``\\ ed replica process — end to end.

The ``make wire`` target (and the slow tier-1 test that drives this
module in-process, ``test_wire_smoke_end_to_end``) spawns replica
servers as SUBPROCESSES (``python benchmarks/wire_smoke.py
--serve_replica ...`` — each builds the same tiny paged engine from
the same seed and prints its bound address), then asserts from a
router in THIS process:

1. **disaggregation over the wire** — a prefill + decode subprocess
   pair serves token-exact vs the in-process fleet baseline, every
   prompt's KV block crossing the wire as raw framed numpy
   (``router.transfer_bytes`` metered, and the process-wide
   ``wire_bytes_sent`` meter carried at least that payload), then
   drains cleanly: both children exit 0 on their own;
2. **SIGKILL → redelivery** — a both/both pair with WALs serves the
   same request set; mid-run the busiest replica's PROCESS is killed
   -9 (no drain, no goodbye frame) WITH a pipelined frame in flight
   (a ``step`` submitted, not yet completed — graftlink's hard
   case). The orphaned completion handle fails NAMED (``WireDead``),
   never hangs and never leaks; the router reaps the victim, reads
   its WAL from the router-known path (``hello`` published it; same
   host = shared filesystem), redelivers the unfinished requests to
   the peer under ORIGINAL uids — every stream still byte-exact, and
   the fleet ``tokens_generated`` merge dedups the replayed prefix
   to the unique token count.

Exit code 0 and one ``graftwire smoke OK`` line = the wire transport
stack is deployable. Run: ``python benchmarks/wire_smoke.py``
(A CPU-mesh rehearsal — it refuses to start children from a parent
that holds a TPU; tiny model, ~2 min — subprocesses pay the jax import).
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MAX_NEW = 6


def _tiny_model():
    from pytorch_multiprocessing_distributed_tpu import models

    return models.GPT(vocab_size=61, max_seq_len=64, hidden_size=32,
                      num_layers=2, num_heads=2, mlp_dim=64,
                      attn_impl="xla")


def _engine(journal=None):
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ServingEngine, init_params)

    model = _tiny_model()
    # seed 1 everywhere: parent baseline and every child build
    # bit-identical params, so byte-identity is a transport claim
    params = init_params(model, 1)
    return model, ServingEngine(
        model, params, max_slots=2, s_max=32, min_bucket=8,
        kv_layout="paged", page_size=8, retry_backoff_s=0.0,
        journal=journal)


def _prompts():
    import numpy as np

    rng = np.random.default_rng(0)
    return [rng.integers(0, 61, (int(rng.integers(4, 20)),)).tolist()
            for _ in range(6)]


# --------------------------------------------------------------- child

def serve_replica(args) -> int:
    """The subprocess body: one paged engine behind a ReplicaServer,
    address handed to the parent through ``--addr_file``, alive until
    the remote router drains it (or the parent kills -9)."""
    from pytorch_multiprocessing_distributed_tpu.runtime import heal
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ReplicaServer)

    journal = (heal.RequestJournal(args.journal) if args.journal
               else None)
    _, engine = _engine(journal)
    server = ReplicaServer(engine, rid=args.rid, role=args.role)
    server.start()
    tmp = args.addr_file + ".tmp"
    with open(tmp, "w") as f:
        f.write(server.address)
    os.replace(tmp, args.addr_file)  # atomic: parent never reads half
    print(f"graftwire smoke replica {args.rid}: listening on "
          f"{server.address} (pid {os.getpid()})", flush=True)
    server.serve_forever()
    return 0


# -------------------------------------------------------------- parent

def _spawn(tmpdir, rid, role, journal=None):
    from pytorch_multiprocessing_distributed_tpu.serving.autoscale import (
        refuse_children_beside_a_tpu)

    refuse_children_beside_a_tpu(f"graftwire smoke replica {rid!r}")
    addr_file = os.path.join(tmpdir, f"addr_{rid}")
    cmd = [sys.executable, os.path.abspath(__file__),
           "--serve_replica", "--rid", rid, "--role", role,
           "--addr_file", addr_file]
    if journal:
        cmd += ["--journal", journal]
    proc = subprocess.Popen(cmd, cwd=REPO)
    return proc, addr_file


def _wait_addr(proc, addr_file, deadline_s=120.0):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < deadline_s:
        if os.path.exists(addr_file):
            with open(addr_file) as f:
                return f.read().strip()
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica subprocess exited {proc.returncode} before "
                "publishing its address")
        time.sleep(0.1)
    raise RuntimeError(
        f"replica subprocess published no address within "
        f"{deadline_s}s ({addr_file})")


def _reap(procs, timeout_s=30.0):
    """Children must exit on their own after a drain; anything still
    alive past the deadline is a bug — killed loudly, never leaked."""
    leaked = []
    for proc in procs:
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            leaked.append(proc.pid)
            proc.kill()
            proc.wait()
    return leaked


def run_smoke(verbose: bool = True) -> dict:
    from pytorch_multiprocessing_distributed_tpu.runtime import wire
    from pytorch_multiprocessing_distributed_tpu.serving import (
        RemoteReplica, Router, ServingReplica)

    def note(msg):
        if verbose:
            print(msg, flush=True)

    prompts = _prompts()

    # ---- the byte-identity reference: the IN-PROCESS fleet
    base_router = Router([ServingReplica("a", _engine()[1]),
                          ServingReplica("b", _engine()[1])])
    ref = {f"u{i}": list(r.tokens) for i, r in enumerate(
        base_router.serve((p, MAX_NEW) for p in prompts))}
    total_unique = sum(len(t) for t in ref.values())
    note(f"baseline: {len(ref)} in-process fleet streams, "
         f"{total_unique} tokens")

    tmpdir = tempfile.mkdtemp(prefix="pmdt_wire_smoke_")
    out = {"killed": False, "redelivered": 0, "streams_ok": False}
    procs = []
    try:
        # ---- 1. prefill/decode split across REAL processes:
        # PageTransfer rides the wire, metered, then a clean drain
        pf, pf_addr = _spawn(tmpdir, "pf", "prefill")
        dc, dc_addr = _spawn(tmpdir, "dc", "decode")
        procs += [pf, dc]
        replicas = [RemoteReplica(_wait_addr(pf, pf_addr)),
                    RemoteReplica(_wait_addr(dc, dc_addr))]
        meter0 = wire.wire_meter()["wire_bytes_sent"]
        router = Router(replicas)
        served = router.serve([(p, MAX_NEW) for p in prompts])
        for i, rec in enumerate(served):
            assert rec.state == "done", (rec.state, rec.finish_reason)
            assert list(rec.tokens) == ref[f"u{i}"], (
                f"disaggregated stream {i} diverged from the "
                "in-process fleet over the wire")
        assert router.transfers_routed == len(prompts), (
            "every prompt should prefill remotely and transfer: "
            f"{router.transfers_routed}/{len(prompts)}")
        assert router.transfer_bytes > 0
        wire_sent = wire.wire_meter()["wire_bytes_sent"] - meter0
        assert wire_sent >= router.transfer_bytes, (
            "the wire meter missed the KV payload: "
            f"{wire_sent} < {router.transfer_bytes}")
        router.drain(None)
        leaked = _reap([pf, dc])
        assert not leaked, (
            f"drained replica processes failed to exit: {leaked}")
        out["transfers"] = router.transfers_routed
        out["transfer_bytes"] = router.transfer_bytes
        out["wire_bytes_sent"] = wire_sent
        note(f"disagg: {router.transfers_routed} PageTransfers, "
             f"{router.transfer_bytes} KV bytes over the wire "
             f"({wire_sent} framed bytes total); both processes "
             "drained and exited 0")

        # ---- 2. SIGKILL a replica PROCESS mid-run -> WAL redelivery
        wals = [os.path.join(tmpdir, f"wal{i}.jsonl") for i in range(2)]
        r0, a0 = _spawn(tmpdir, "r0", "both", journal=wals[0])
        r1, a1 = _spawn(tmpdir, "r1", "both", journal=wals[1])
        procs += [r0, r1]
        replicas = [RemoteReplica(_wait_addr(r0, a0)),
                    RemoteReplica(_wait_addr(r1, a1))]
        by_pid = {replicas[0].engine.pid: r0,
                  replicas[1].engine.pid: r1}
        router = Router(replicas)
        for i, p in enumerate(prompts):
            router.submit(p, MAX_NEW, uid=f"u{i}")
        for _ in range(3):
            router.step()  # tokens into both WALs before the kill
        victim = max(replicas, key=lambda r: r.in_flight)
        assert victim.in_flight > 0
        victim_proc = by_pid[victim.engine.pid]
        # graftlink: kill with a PIPELINED frame in flight — a step
        # submitted but not completed. The completion handle must
        # fail NAMED (WireDead), never hang and never leak, and the
        # WAL must still redeliver token-exact afterwards.
        from pytorch_multiprocessing_distributed_tpu.runtime.wire \
            import WireDead
        handle = victim.step_submit()
        assert handle is not None, (
            "pipelined submit surface missing: RemoteReplica should "
            "default to a pipelined client")
        os.kill(victim_proc.pid, signal.SIGKILL)
        victim_proc.wait()
        out["killed"] = True
        try:
            victim.step_complete(handle)
            raise AssertionError(
                "completing a frame submitted to a SIGKILLed replica "
                "did not fail")
        except WireDead as e:
            out["handle_failed_named"] = f"WireDead: {e}"[:120]
        lane = victim._client._lanes.get("eng")
        assert lane is None or not lane._pending, (
            "pipelined completion handle leaked past the kill")
        note(f"kill: SIGKILLed replica {victim.rid} "
             f"(pid {victim_proc.pid}, {victim.in_flight} in flight, "
             "1 pipelined frame submitted-uncompleted -> failed "
             "named, not leaked)")
        deadline = time.perf_counter() + 120.0
        while router.in_flight:
            assert time.perf_counter() < deadline, (
                "post-kill serve did not converge")
            router.step()
        assert victim.reaped
        assert "WireDead" in victim.engine.health.reason
        assert router.requests_redelivered >= 1, (
            "the victim's WAL redelivered nothing")
        recs = router.records()
        for uid, want in ref.items():
            got = list(recs[uid].tokens)
            assert got == want, (
                f"stream {uid} diverged across the process kill: "
                f"{got} vs {want}")
        merged = router.merged_metrics()
        assert merged["tokens_generated"] == total_unique, (
            "redelivery dedup broke the fleet token count: "
            f"{merged['tokens_generated']} vs {total_unique} unique")
        out["redelivered"] = router.requests_redelivered
        out["replayed_tokens"] = router.redelivery_replayed_tokens
        out["merged_tokens"] = merged["tokens_generated"]
        out["streams_ok"] = True
        router.drain(None)
        leaked = _reap([r1])
        assert not leaked, (
            f"surviving replica failed to exit after drain: {leaked}")
        note(f"redelivery: {out['redelivered']} requests replayed "
             f"from the victim's WAL ({out['replayed_tokens']} "
             f"tokens deduped), all {len(ref)} streams byte-exact, "
             f"merged tokens {merged['tokens_generated']} == unique "
             f"{total_unique}; survivor drained and exited 0")
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        import shutil

        shutil.rmtree(tmpdir, ignore_errors=True)
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--serve_replica", action="store_true",
                        help="internal: run as one replica-server "
                             "subprocess")
    parser.add_argument("--rid", default="r0")
    parser.add_argument("--role", default="both")
    parser.add_argument("--journal", default="")
    parser.add_argument("--addr_file", default="")
    args = parser.parse_args(argv)
    from pytorch_multiprocessing_distributed_tpu.utils.hostenv import (
        force_cpu_devices_from_env)

    force_cpu_devices_from_env()
    if args.serve_replica:
        if not args.addr_file:
            raise SystemExit("--serve_replica needs --addr_file")
        return serve_replica(args)
    out = run_smoke(verbose=True)
    print("graftwire smoke OK " + json.dumps(
        {k: out[k] for k in ("killed", "redelivered",
                             "transfer_bytes")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
