"""graftfault: the fault matrix and the recovery machinery it proves.

The headline invariant (``make chaos`` runs this file): for EVERY
registered injection site, an injected fault is either RECOVERED
(bounded retries absorb it, or the poisoned request is quarantined
while the engine keeps serving) or fails FAST with a named
``GraftFaultError`` — no hang, no silent swallow — and every
*unaffected* request's tokens are byte-identical to the fault-free
run (dense + TP, decode horizon H>1 and chunked prefill active).

``SCENARIOS`` maps each registered site to the matrix entry that
exercises it; registering a new hazard point without adding a
scenario fails ``test_matrix_covers_every_registered_site``.
"""

import os
import signal
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_multiprocessing_distributed_tpu import models
from pytorch_multiprocessing_distributed_tpu.runtime.faults import (
    DeadlineExceeded, FaultInjected, FaultPlan, FaultRule, FaultTimeout,
    GraftFaultError, PoolPoisonedError, active_plan, armed, maybe_fault,
    plan_from_spec, registered_sites, retry_with_backoff,
    run_with_timeout)
from pytorch_multiprocessing_distributed_tpu.serving import (
    DONE, FAILED, QueueFull, ServingEngine, init_params)

# importing these registers the non-serving sites the matrix sweeps
from pytorch_multiprocessing_distributed_tpu.parallel import dist  # noqa: F401
from pytorch_multiprocessing_distributed_tpu.runtime import heal
from pytorch_multiprocessing_distributed_tpu.runtime import store  # noqa: F401
from pytorch_multiprocessing_distributed_tpu.runtime import wire
from pytorch_multiprocessing_distributed_tpu.runtime.store import MemStore
from pytorch_multiprocessing_distributed_tpu.train import (  # noqa: F401
    checkpoint as ckpt_mod, orbax_ckpt)


def _tiny(**kw):
    return models.GPT(vocab_size=61, max_seq_len=64, hidden_size=32,
                      num_layers=2, num_heads=2, mlp_dim=64,
                      attn_impl="xla", **kw)


# ---------------------------------------------------------- chaos core

class TestFaultPlan:
    def test_rule_schedule(self):
        r = FaultRule("s", "error", times=2, after=1)
        fires = [r.should_fire(h) for h in range(5)]
        # triggered is bumped by the PLAN; emulate it
        got = []
        for h in range(5):
            f = r.should_fire(h)
            if f:
                r.triggered += 1
            got.append(f)
        assert got == [False, True, True, False, False]
        with pytest.raises(ValueError, match="kind"):
            FaultRule("s", "explode")
        with pytest.raises(ValueError, match=">= 0"):
            FaultRule("s", "error", times=-1)

    def test_every_k_is_a_rate(self):
        plan = FaultPlan([FaultRule("s", "error", times=0, every=3)])
        hits = []
        for i in range(9):
            try:
                plan.apply("s", None)
                hits.append(False)
            except FaultInjected:
                hits.append(True)
        assert hits == [True, False, False] * 3

    def test_corrupt_is_deterministic_and_flips_one_byte(self):
        payload = bytes(range(64))
        a = FaultPlan([FaultRule("s", "corrupt")], seed=5).apply(
            "s", payload)
        b = FaultPlan([FaultRule("s", "corrupt")], seed=5).apply(
            "s", payload)
        assert a == b and a != payload
        assert sum(x != y for x, y in zip(a, payload)) == 1

    def test_disarmed_is_identity(self):
        assert active_plan() is None
        obj = object()
        assert maybe_fault("serving.decode_dispatch", obj) is obj
        assert maybe_fault("no.such.site") is None

    def test_spec_grammar(self):
        plan = plan_from_spec(
            "seed=7; store.get=error:2 ; serving.horizon_readback="
            "hang:1:0.5; train.checkpoint_write=corrupt:1:3")
        assert plan.seed == 7
        by = {r.site: r for r in plan.rules}
        assert by["store.get"].kind == "error"
        assert by["store.get"].times == 2
        assert by["serving.horizon_readback"].hang_s == 0.5
        assert by["train.checkpoint_write"].after == 3

    def test_corrupt_rule_at_payloadless_site_fails_loud(self):
        """corrupt at a site that passes no payload raises named
        instead of silently no-opping while consuming budget —
        triggered() must never report faults that never happened."""
        plan = FaultPlan([FaultRule("s", "corrupt")])
        with pytest.raises(GraftFaultError, match="passes no payload"):
            plan.apply("s", None)

    def test_spec_modifiers_are_position_independent(self):
        """``seed=``/``every=`` are plan-wide wherever they appear:
        ``"site=...;every=10"`` and ``"every=10;site=..."`` build the
        SAME plan — the documented grammar has no order-sensitive
        elements (a trailing ``every=`` silently building a
        fire-every-attempt rule would turn a 1/10 background rate
        into guaranteed retry exhaustion)."""
        trailing = plan_from_spec(
            "serving.decode_dispatch=error:1;every=10;seed=3")
        leading = plan_from_spec(
            "seed=3;every=10;serving.decode_dispatch=error:1")
        assert trailing.seed == leading.seed == 3
        assert [r.every for r in trailing.rules] == [10]
        assert [r.every for r in leading.rules] == [10]

    def test_env_hook_arms_at_import(self):
        code = (
            "from pytorch_multiprocessing_distributed_tpu.runtime "
            "import faults\n"
            "p = faults.active_plan()\n"
            "assert p is not None and p.seed == 9, p\n"
            "assert [r.site for r in p.rules] == ['store.get']\n"
            "print('armed-ok')\n")
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env=dict(os.environ, PMDT_FAULT_PLAN="seed=9;store.get=error"),
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert proc.returncode == 0, proc.stderr
        assert "armed-ok" in proc.stdout


class TestRecoveryPrimitives:
    def test_retry_bounded_and_selective(self):
        calls = {"n": 0}
        naps = []

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise ConnectionError("flake")
            return "ok"

        assert retry_with_backoff(flaky, attempts=3, base_delay_s=0.5,
                                  sleep=naps.append) == "ok"
        assert naps == [0.5, 1.0]  # exponential, injectable sleep

        def logic_bug():
            raise KeyError("not transient")

        with pytest.raises(KeyError):  # non-OSError propagates at once
            retry_with_backoff(logic_bug, attempts=5, sleep=lambda s: None)

        def always():
            raise ConnectionError("dead")

        with pytest.raises(ConnectionError):
            retry_with_backoff(always, attempts=2, sleep=lambda s: None)
        with pytest.raises(ValueError, match="attempts"):
            retry_with_backoff(lambda: None, attempts=0)

    def test_run_with_timeout(self):
        assert run_with_timeout(lambda: 41 + 1, 5.0, "sum") == 42
        with pytest.raises(KeyError):  # worker's own error re-raised
            run_with_timeout(lambda: {}[0], 5.0, "boom")
        ev = threading.Event()
        with pytest.raises(FaultTimeout, match="hint here"):
            run_with_timeout(ev.wait, 0.05, "stuck wait",
                             hint="hint here")
        ev.set()  # release the abandoned daemon worker


# ------------------------------------------------- serving fault matrix

@pytest.fixture(scope="module")
def chaos():
    """ONE engine (dense, H=4, chunked prefill) reused across matrix
    entries — transient and quarantine faults must leave it healthy,
    which is itself part of what the matrix proves. Returns
    (engine, prompts, baseline tokens per request index)."""
    model = _tiny()
    params = init_params(model, 1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, (n,)).tolist()
               for n in (3, 7, 12, 5)]
    engine = ServingEngine(model, params, max_slots=2, s_max=32,
                           min_bucket=8, decode_horizon=4,
                           prefill_chunk=4, retry_backoff_s=0.0)
    baseline = _serve(engine, prompts)
    assert all(t is not None for t in baseline)
    return engine, prompts, baseline


def _serve(engine, prompts, new_tokens=4, deadline_s=None):
    """Submit + drain; returns per-request token lists (None for a
    FAILED request). Never uses serve() — FAILED requests are legal
    here."""
    reqs = [engine.submit(p, new_tokens, deadline_s=deadline_s)
            for p in prompts]
    for _ in engine.run():
        pass
    assert engine.pool.occupancy == 0  # every slot recycled
    assert engine.in_flight == 0
    return [r.tokens if r.state == DONE else None for r in reqs]


def _transient_recovered(chaos, site, after=0):
    """kind='error' x1 at ``site``: absorbed by bounded retry — every
    request completes with byte-identical tokens, the retry is
    counted, nothing silently swallowed."""
    engine, prompts, baseline = chaos
    before = engine.metrics.dispatch_retries
    plan = FaultPlan([FaultRule(site, "error", times=1, after=after)])
    with armed(plan):
        got = _serve(engine, prompts)
    assert plan.triggered() == 1, f"{site}: fault never hit"
    assert got == baseline
    assert engine.metrics.dispatch_retries == before + 1


def _scenario_dispatch(chaos):
    _transient_recovered(chaos, "serving.decode_dispatch", after=1)


def _scenario_readback(chaos):
    _transient_recovered(chaos, "serving.horizon_readback", after=1)


def _scenario_chunk(chaos):
    _transient_recovered(chaos, "serving.prefill_chunk", after=1)


def _scenario_tok0(chaos):
    _transient_recovered(chaos, "serving.prefill_tok0")


def _scenario_insert(chaos):
    _transient_recovered(chaos, "serving.slot_insert")


def _scenario_prefill(chaos):
    """The chaos engine admits chunked, so exercise the whole-prompt
    site on a persistent fault: retries exhaust -> the FIRST request
    is quarantined FAILED with its error, the rest are token-exact,
    and the engine (fresh one, whole-prompt mode) keeps serving."""
    engine, prompts, baseline = chaos
    whole = ServingEngine(engine.model, engine.params, max_slots=2,
                          s_max=32, min_bucket=8, retry_backoff_s=0.0,
                          dispatch_retries=2)
    base = _serve(whole, prompts)
    assert base == baseline  # chunked == whole-prompt, fault-free
    plan = FaultPlan([FaultRule("serving.prefill", "error", times=2)])
    with armed(plan):
        reqs = [whole.submit(p, 4) for p in prompts]
        for _ in whole.run():
            pass
    assert plan.triggered() == 2
    assert reqs[0].state == FAILED
    assert reqs[0].finish_reason == "error"
    assert isinstance(reqs[0].error, FaultInjected)
    assert [r.state for r in reqs[1:]] == [DONE] * 3
    assert [r.tokens for r in reqs[1:]] == baseline[1:]
    assert whole.metrics.requests_failed == 1
    # quarantined slot was recycled: a re-serve is pristine
    assert _serve(whole, prompts) == baseline


def _scenario_store(chaos, site="store.get"):
    """Covered in depth by tests/test_runtime_store.py (recovered
    after injected flakes, bounded-fail after); here the matrix pins
    the site exists end-to-end when the toolchain is present."""
    import shutil

    if shutil.which("g++") is None and shutil.which("make") is None:
        pytest.skip("no C++ toolchain for the TCP store")
    from pytorch_multiprocessing_distributed_tpu.runtime import (
        TCPStore, TCPStoreServer)

    with TCPStoreServer(port=0) as srv:
        with TCPStore(port=srv.port, retries=3, backoff_s=0.0) as c:
            plan = FaultPlan([
                FaultRule("store.set", "error", times=1),
                FaultRule("store.get", "error", times=1),
            ])
            with armed(plan):
                c.set("k", b"v")
                assert c.get("k") == b"v"
            assert plan.triggered() == 2


def _scenario_store_set(chaos):
    _scenario_store(chaos, "store.set")


def _scenario_checkpoint_write(chaos, tmpdir=None):
    """kind='corrupt' at the write site: the payload byte-flips AFTER
    its digest is computed — load fails fast with the file named, and
    load_with_fallback recovers to the previous valid epoch."""
    import tempfile

    from pytorch_multiprocessing_distributed_tpu.train import (
        create_train_state)
    from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
        CheckpointCorruptError, load_checkpoint, load_with_fallback,
        save_checkpoint)
    from pytorch_multiprocessing_distributed_tpu.train.optim import sgd

    model = models.get_model("vit_tiny", num_classes=10)
    opt = sgd(learning_rate=0.1)
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((2, 32, 32, 3)), opt)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, state.replace(
            epoch=jnp.asarray(1, jnp.int32)), 1)
        plan = FaultPlan([FaultRule("train.checkpoint_write",
                                    "corrupt")])
        with armed(plan):
            path2 = save_checkpoint(d, state.replace(
                epoch=jnp.asarray(2, jnp.int32)), 2)
        assert plan.triggered() == 1
        with pytest.raises(CheckpointCorruptError) as err:
            load_checkpoint(path2, state)
        assert "model_2.pth" in str(err.value)  # names the file
        restored, used = load_with_fallback(d, state)
        assert used.endswith("model_1.pth")
        assert int(jax.device_get(restored.epoch)) == 1


def _scenario_orbax(chaos):
    """Fail fast, named: an injected fault at the orbax save site
    surfaces as ITS error at the save call — a failed commit never
    becomes a resume candidate."""
    import tempfile

    pytest.importorskip("orbax.checkpoint")
    from pytorch_multiprocessing_distributed_tpu.train.orbax_ckpt import (
        OrbaxCheckpointer)
    from pytorch_multiprocessing_distributed_tpu.train.state import (
        TrainState)

    state = TrainState(params={"w": jnp.ones((2,))}, batch_stats={},
                       opt_state={}, epoch=jnp.ones((), jnp.int32))
    with tempfile.TemporaryDirectory() as d:
        with OrbaxCheckpointer(d) as ck:
            with armed(FaultPlan([FaultRule("train.orbax_save",
                                            "error")])):
                with pytest.raises(FaultInjected):
                    ck.save(state, 1)
            assert ck.latest_epoch() is None  # nothing half-committed
            ck.save(state, 1)  # disarmed: clean save
            ck.wait()
            assert ck.latest_epoch() == 1


def _scenario_rendezvous(chaos):
    """A faulted control-plane barrier raises named — a half-synced
    fleet must never proceed silently."""
    with armed(FaultPlan([FaultRule("runtime.rendezvous", "error")])):
        with pytest.raises(FaultInjected):
            dist.barrier("chaos")
    dist.barrier("chaos")  # disarmed: no-op on one host


def _scenario_heartbeat_write(chaos):
    """error x1 at the beat publish: absorbed by bounded retry (the
    beat still lands, monotone); a persistent failure fails fast
    named — a host that cannot reach the store must look dead to its
    peers, never silently healthy."""
    mem = MemStore()
    hb = heal.Heartbeat(mem, "h0", backoff_s=0.0)
    plan = FaultPlan([FaultRule("heartbeat.write", "error", times=1)])
    with armed(plan):
        assert hb.beat() == 1
    assert plan.triggered() == 1
    assert mem.get("heal/beat/h0") == b"1"  # recovered write landed
    with armed(FaultPlan([FaultRule("heartbeat.write", "error",
                                    times=0)])):
        with pytest.raises(FaultInjected):
            hb.beat()


def _scenario_heartbeat_read(chaos):
    """error x1 at the liveness poll: recovered — the retried read
    still observes the peer's beat (no false SUSPECT/DEAD from a
    transient store flake)."""
    mem = MemStore()
    monitor = heal.HeartbeatMonitor(
        mem, "0", ["0", "1"], soft_timeout_s=5.0, hard_timeout_s=10.0,
        backoff_s=0.0)
    heal.Heartbeat(mem, "1", backoff_s=0.0).beat()
    plan = FaultPlan([FaultRule("heartbeat.read", "error", times=1)])
    with armed(plan):
        states = monitor.poll()
    assert plan.triggered() == 1
    assert states == {"1": "alive"}


def _scenario_journal_write(chaos):
    """error x1 at the WAL append: recovered (the record is durable —
    a reopened journal replays it); exhausted retries fail loudly
    NAMED — a WAL that silently stops recording voids the redelivery
    guarantee."""
    import tempfile
    from types import SimpleNamespace

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "wal.jsonl")
        journal = heal.RequestJournal(path, backoff_s=0.0)
        req = SimpleNamespace(uid=1, prompt=[1, 2, 3],
                              max_new_tokens=4, eos_id=None)
        plan = FaultPlan([FaultRule("heal.journal_write", "error",
                                    times=1)])
        with armed(plan):
            journal.record_admit(req)
        assert plan.triggered() == 1
        replayed = heal.RequestJournal(path, backoff_s=0.0)
        assert [e.uid for e in replayed.unfinished()] == [1]
        req2 = SimpleNamespace(uid=2, prompt=[4], max_new_tokens=2,
                               eos_id=None)
        with armed(FaultPlan([FaultRule("heal.journal_write", "error",
                                        times=0)])):
            with pytest.raises(GraftFaultError, match="journal"):
                journal.record_admit(req2)


def _scenario_restart(chaos):
    """error x1 injected AT a supervised restart: the failed restart
    consumes budget like any named fatal (tracked, bounded — never an
    untracked crash loop), and the next attempt completes."""
    calls = []

    def target(attempt):
        calls.append(attempt)
        if attempt == 0:
            raise GraftFaultError("boom")
        return "ok"

    plan = FaultPlan([FaultRule("heal.restart", "error", times=1)])
    with armed(plan):
        sup = heal.Supervisor(target, max_restarts=2, backoff_s=0.0,
                              sleep=lambda s: None)
        assert sup.run() == "ok"
    assert plan.triggered() == 1
    assert sup.restarts == 2  # the faulted restart consumed budget
    assert calls == [0, 2]


def _scenario_wire_connect(chaos):
    """error x1 at the TCP connect: the lazy-connect retry path dials
    again and the (idempotent) call lands; unlimited connect errors
    fail fast as a NAMED WireDead — a replica that cannot be dialed
    is a lost replica, never a spin."""
    with wire.WireServer({"ping": lambda h, a: {}}) as server:
        plan = FaultPlan([FaultRule("wire.connect", "error", times=1)])
        with armed(plan):
            client = wire.WireClient(server.address, backoff_s=0.0)
            assert client.call("ping")[0]["ok"]
            client.close()
        assert plan.triggered() == 1
        with armed(FaultPlan([FaultRule("wire.connect", "error",
                                        times=0)])):
            client = wire.WireClient(server.address, backoff_s=0.0)
            with pytest.raises(wire.WireDead):
                client.call("ping")
            client.close()


def _scenario_wire_send(chaos):
    """error x1 at the frame send: an IDEMPOTENT verb reconnects and
    retries to success; a NON-idempotent verb fails fast as a named
    WireDead (commit-ambiguous — redelivery, not a retry, is the
    exactly-once recovery); a CORRUPT send is detected by the
    receiver's frame sanity checks, the connection drops, and the
    idempotent retry resends clean."""
    handlers = {"ping": lambda h, a: {}, "mutate": lambda h, a: {}}
    with wire.WireServer(handlers) as server:
        plan = FaultPlan([FaultRule("wire.send", "error", times=1)])
        with armed(plan):
            client = wire.WireClient(server.address, backoff_s=0.0)
            assert client.call("ping")[0]["ok"]
        assert plan.triggered() == 1
        client.close()
        with armed(FaultPlan([FaultRule("wire.send", "error",
                                        times=1)])):
            client = wire.WireClient(server.address, backoff_s=0.0)
            with pytest.raises(wire.WireDead,
                               match="not idempotent"):
                client.call("mutate")
            client.close()
        corrupt = FaultPlan([FaultRule("wire.send", "corrupt",
                                       times=1)])
        with armed(corrupt):
            client = wire.WireClient(server.address, backoff_s=0.0,
                                     call_deadline_s=5.0)
            assert client.call("ping")[0]["ok"]
            client.close()
        assert corrupt.triggered() == 1
        # corruption of the RESPONSE frame (after=1 skips the
        # client's request send and flips the server's reply): the
        # CLIENT's frame sanity checks raise WireError, the socket
        # drops, and the idempotent retry recovers — corruption
        # never escapes raw in either direction
        resp_corrupt = FaultPlan([FaultRule("wire.send", "corrupt",
                                            times=1, after=1)])
        with armed(resp_corrupt):
            client = wire.WireClient(server.address, backoff_s=0.0,
                                     call_deadline_s=5.0)
            assert client.call("ping")[0]["ok"]
            client.close()
        assert resp_corrupt.triggered() == 1


def _scenario_wire_recv(chaos):
    """error x1 at the frame receive (fires on whichever side reads
    the next arriving frame): the connection drops and the idempotent
    retry recovers; a HANG at recv is bounded by the per-call
    run_with_timeout deadline — recovered on the retry, never a
    distributed hang."""
    with wire.WireServer({"ping": lambda h, a: {}}) as server:
        plan = FaultPlan([FaultRule("wire.recv", "error", times=1)])
        with armed(plan):
            client = wire.WireClient(server.address, backoff_s=0.0)
            assert client.call("ping")[0]["ok"]
            client.close()
        assert plan.triggered() == 1
        hang = FaultPlan([FaultRule("wire.recv", "hang", times=1,
                                    hang_s=1.0)])
        with armed(hang):
            client = wire.WireClient(server.address, backoff_s=0.0,
                                     call_deadline_s=0.3)
            assert client.call("ping")[0]["ok"]
            client.close()
        assert hang.triggered() == 1


SCENARIOS = {
    "serving.decode_dispatch": _scenario_dispatch,
    "serving.horizon_readback": _scenario_readback,
    "serving.prefill": _scenario_prefill,
    "serving.prefill_chunk": _scenario_chunk,
    "serving.prefill_tok0": _scenario_tok0,
    "serving.slot_insert": _scenario_insert,
    "store.get": _scenario_store,
    "store.set": _scenario_store_set,
    "train.checkpoint_write": _scenario_checkpoint_write,
    "train.orbax_save": _scenario_orbax,
    "runtime.rendezvous": _scenario_rendezvous,
    "heartbeat.write": _scenario_heartbeat_write,
    "heartbeat.read": _scenario_heartbeat_read,
    "heal.journal_write": _scenario_journal_write,
    "heal.restart": _scenario_restart,
    "wire.connect": _scenario_wire_connect,
    "wire.send": _scenario_wire_send,
    "wire.recv": _scenario_wire_recv,
}


def test_matrix_covers_every_registered_site():
    """Registering a hazard point without a matrix scenario fails
    HERE — coverage of the sweep is itself pinned."""
    assert set(registered_sites()) == set(SCENARIOS)


@pytest.mark.parametrize("site", sorted(SCENARIOS))
def test_fault_matrix(site, chaos):
    SCENARIOS[site](chaos)


# ----------------------------------------- fault-domain behavior pins

def test_quarantine_on_poisoned_insert(chaos):
    """Retries exhausted at slot insert AFTER the slot was acquired:
    the request fails, its slot is scrubbed + recycled (the very next
    request runs through the same slot), everyone else token-exact."""
    engine, prompts, baseline = chaos
    plan = FaultPlan([FaultRule("serving.slot_insert", "error",
                                times=3)])
    with armed(plan):
        reqs = [engine.submit(p, 4) for p in prompts]
        for _ in engine.run():
            pass
    assert plan.triggered() == 3
    assert reqs[0].state == FAILED and reqs[0].error is not None
    assert [r.tokens for r in reqs[1:]] == baseline[1:]
    # pool fully recycled; the engine reused the scrubbed slot above
    assert engine.pool.occupancy == 0
    assert _serve(engine, prompts) == baseline


def test_fatal_fault_fails_fast_named():
    """kind='fatal' at dispatch: NOT retryable — the engine raises the
    named GraftFaultError immediately (no retry storm, no hang)."""
    model = _tiny()
    engine = ServingEngine(model, init_params(model, 1), max_slots=1,
                           s_max=32, min_bucket=8, decode_buckets=(),
                           retry_backoff_s=0.0)
    engine.submit(list(range(5)), 4)
    plan = FaultPlan([FaultRule("serving.decode_dispatch", "fatal")])
    with armed(plan):
        with pytest.raises(GraftFaultError, match="decode_dispatch"):
            for _ in engine.run():
                pass
    assert engine.metrics.dispatch_retries == 0  # fatal != transient


def test_pool_poisoned_on_donated_mid_call_failure():
    """A REAL mid-execution failure of a pool-donating program (TPU
    donation armed) is engine-fatal: the donated pool buffers were
    consumed when the launch started, so the named PoolPoisonedError
    propagates — NOT a one-request quarantine (which would keep
    "serving" everyone else from deleted buffers) and NOT a retry
    (which would replay against them)."""
    model = _tiny()
    engine = ServingEngine(model, init_params(model, 1), max_slots=1,
                           s_max=32, min_bucket=8, decode_buckets=(),
                           retry_backoff_s=0.0)
    engine.submit(list(range(5)), 4)
    engine._donate_cache = True  # CPU never donates; simulate TPU

    def exploding_decode(*a, **k):
        raise RuntimeError("simulated XlaRuntimeError mid-execution")

    engine._decode = exploding_decode
    with pytest.raises(PoolPoisonedError, match="pool-donating"):
        for _ in engine.run():
            pass
    assert engine.metrics.dispatch_retries == 0  # consumed => no retry


def test_watchdog_trips_on_hung_readback():
    """kind='hang' outliving readback_timeout_s: the watchdog fails
    fast with a FaultTimeout naming the readback, and the trip is
    counted — the failure mode retries cannot see."""
    model = _tiny()
    engine = ServingEngine(model, init_params(model, 2), max_slots=1,
                           s_max=32, min_bucket=8, decode_buckets=(),
                           decode_horizon=4, readback_timeout_s=0.2,
                           retry_backoff_s=0.0)
    engine.submit(list(range(5)), 4)
    plan = FaultPlan([FaultRule("serving.horizon_readback", "hang",
                                hang_s=5.0)])
    with armed(plan):
        with pytest.raises(FaultTimeout, match="readback"):
            for _ in engine.run():
                pass
    assert engine.metrics.watchdog_trips == 1


def test_deadline_eviction(chaos):
    """deadline_s=0: the request expires in the queue and fails as
    'deadline' with a DeadlineExceeded recorded — without ever
    touching a slot; concurrent normal requests are unaffected."""
    engine, prompts, baseline = chaos
    normal = [engine.submit(p, 4) for p in prompts[1:]]
    doomed = engine.submit(prompts[0], 4, deadline_s=0.0)
    for _ in engine.run():
        pass
    assert doomed.state == FAILED
    assert doomed.finish_reason == "deadline"
    assert isinstance(doomed.error, DeadlineExceeded)
    assert [r.tokens for r in normal] == baseline[1:]
    assert engine.metrics.requests_failed >= 1


def test_horizon_collapses_during_cooldown():
    """A recovered transient dispatch fault forces H=1 dispatches for
    the cooldown window (graceful degradation), visibly counted."""
    model = _tiny()
    engine = ServingEngine(model, init_params(model, 3), max_slots=1,
                           s_max=32, min_bucket=8, decode_buckets=(),
                           decode_horizon=4, fault_cooldown=4,
                           retry_backoff_s=0.0)
    prompt = list(range(5))
    engine.serve([(prompt, 13)])  # warm, fault-free: H=4 dispatches
    assert engine.metrics.horizon_collapses == 0
    plan = FaultPlan([FaultRule("serving.decode_dispatch", "error",
                                times=1)])
    with armed(plan):
        (request,) = engine.serve([(prompt, 13)])
    assert len(request.tokens) == 13  # token count unharmed
    assert engine.metrics.dispatch_retries == 1
    assert engine.metrics.horizon_collapses >= 1
    # both horizon rungs exist, bounded by the {1, H} ladder
    assert set(h for _, h in engine.decode_programs) == {1, 4}


def test_queue_shed_counted_and_submit_retrying(chaos):
    """QueueFull sheds are counted; submit_retrying steps the engine
    between attempts so the bounded queue drains — the tested retry
    path behind the 'shed load or retry' advice."""
    engine, prompts, baseline = chaos
    model = engine.model
    small = ServingEngine(model, engine.params, max_slots=1, s_max=32,
                          min_bucket=8, max_queue=1,
                          retry_backoff_s=0.0)
    first = small.submit(prompts[0], 2)
    with pytest.raises(QueueFull):
        small.submit(prompts[1], 2)
    assert small.metrics.requests_shed == 1
    # admission is two stages a step apart: one step dispatches the
    # first request's prefill (no token yet) and the queue has room
    small.step()
    small.submit(prompts[1], 2)
    # retrying submission drains the queue via step() and lands; the
    # drain steps' token events surface through events_out — an
    # event-driven caller would otherwise never see completions those
    # steps emitted
    events = []
    request = small.submit_retrying(prompts[2], 2, attempts=64,
                                    events_out=events)
    assert request.state in ("queued", "running", "done")
    assert events, "drain steps must surface their token events"
    assert all(ev[0] is first for ev in events)
    for _ in small.run():
        pass
    assert request.state == DONE
    assert small.metrics.requests_shed > 1  # rejected attempts counted


def test_tp_matrix_transient_dispatch():
    """The TP half of the acceptance pin: a transient dispatch fault
    on a 'model'-sharded engine (H>1, chunked prefill) recovers with
    every request byte-identical to the TP fault-free run."""
    from pytorch_multiprocessing_distributed_tpu.inference import (
        shard_params_for_tp_decode)
    from pytorch_multiprocessing_distributed_tpu.parallel import make_mesh

    model = _tiny()
    params = init_params(model, 1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, (n,)).tolist()
               for n in (3, 7, 12)]
    mesh = make_mesh(4, 2)
    engine = ServingEngine(model, shard_params_for_tp_decode(params, mesh),
                           max_slots=2, s_max=32, mesh=mesh, min_bucket=8,
                           decode_horizon=4, prefill_chunk=4,
                           retry_backoff_s=0.0)
    baseline = _serve(engine, prompts)
    plan = FaultPlan([FaultRule("serving.decode_dispatch", "error",
                                times=1, after=1)])
    with armed(plan):
        got = _serve(engine, prompts)
    assert plan.triggered() == 1
    assert got == baseline
    assert engine.metrics.dispatch_retries == 1


# ------------------------------------ checkpoint durability + recovery

class TestNanGuard:
    """The skip-and-count guard's selection semantics, pinned as pure
    functions (every train-step suite compiles the guard into its
    program; the sentinel suite pins its no-host-sync property)."""

    def test_finite_grads_predicate(self):
        from pytorch_multiprocessing_distributed_tpu.train.step import (
            finite_grads)

        clean = {"a": jnp.ones((3, 2)), "b": {"c": jnp.zeros(4)}}
        assert bool(finite_grads(clean))
        for bad in (jnp.nan, jnp.inf, -jnp.inf):
            poisoned = {"a": jnp.ones((3, 2)).at[1, 1].set(bad),
                        "b": {"c": jnp.zeros(4)}}
            assert not bool(finite_grads(poisoned))

    def test_guard_selects_carried_state_and_counts(self):
        from pytorch_multiprocessing_distributed_tpu.train.step import (
            guard_nonfinite)

        old = {"w": jnp.zeros(3)}
        new = {"w": jnp.ones(3)}
        guarded, m = guard_nonfinite(jnp.asarray(False), new, old, {})
        np.testing.assert_array_equal(np.asarray(guarded["w"]),
                                      np.zeros(3))  # carried through
        assert int(m["skipped"]) == 1
        guarded, m = guard_nonfinite(jnp.asarray(True), new, old, {})
        np.testing.assert_array_equal(np.asarray(guarded["w"]),
                                      np.ones(3))  # update kept
        assert int(m["skipped"]) == 0


class TestCheckpointIntegrity:
    @pytest.fixture(scope="class")
    def trained(self):
        from pytorch_multiprocessing_distributed_tpu.train import (
            create_train_state)
        from pytorch_multiprocessing_distributed_tpu.train.optim import sgd

        model = models.get_model("vit_tiny", num_classes=10)
        opt = sgd(learning_rate=0.1)
        return create_train_state(
            model, jax.random.PRNGKey(0), jnp.zeros((2, 32, 32, 3)), opt)

    def test_digest_sidecar_roundtrip(self, trained, tmp_path):
        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            digest_path, load_checkpoint, save_checkpoint,
            verify_checkpoint)

        path = save_checkpoint(str(tmp_path), trained, 3)
        assert os.path.exists(digest_path(path))
        assert verify_checkpoint(path) is True
        restored = load_checkpoint(path, trained)
        np.testing.assert_array_equal(
            jax.tree.leaves(jax.device_get(restored.params))[0],
            jax.tree.leaves(jax.device_get(trained.params))[0])

    def test_bitflip_detected_and_fallback(self, trained, tmp_path):
        """The acceptance pin end-to-end: bit-flipped newest checkpoint
        -> CheckpointCorruptError naming file + digests -> automatic
        fallback to the previous valid epoch -> resume at ITS epoch."""
        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            CheckpointCorruptError, load_checkpoint, load_with_fallback,
            save_checkpoint)

        save_checkpoint(str(tmp_path), trained.replace(
            epoch=jnp.asarray(4, jnp.int32)), 4)
        path5 = save_checkpoint(str(tmp_path), trained.replace(
            epoch=jnp.asarray(5, jnp.int32)), 5)
        blob = bytearray(open(path5, "rb").read())
        blob[len(blob) // 2] ^= 0x01  # one flipped bit
        open(path5, "wb").write(bytes(blob))
        with pytest.raises(CheckpointCorruptError) as err:
            load_checkpoint(path5, trained)
        msg = str(err.value)
        assert "model_5.pth" in msg and "sha256" in msg
        state, used = load_with_fallback(str(tmp_path), trained)
        assert used.endswith("model_4.pth")
        assert int(jax.device_get(state.epoch)) == 4  # resume point

    def test_anchor_caps_fallback_walk(self, trained, tmp_path):
        """A stale EXTRA checkpoint newer than the anchor epoch is
        ignored, not loaded: both CLIs' --resume auto pass
        checkpoint_epoch(primary-resolved path) as the anchor, so one
        host's leftover model_9.pth cannot shift that host's walk and
        get misdiagnosed as cross-host divergence."""
        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            checkpoint_epoch, load_with_fallback, save_checkpoint)

        save_checkpoint(str(tmp_path), trained.replace(
            epoch=jnp.asarray(8, jnp.int32)), 8)
        stale = save_checkpoint(str(tmp_path), trained.replace(
            epoch=jnp.asarray(9, jnp.int32)), 9)  # primary never saw it
        assert checkpoint_epoch(stale) == 9
        assert checkpoint_epoch("weights.bin") is None
        state, used = load_with_fallback(
            str(tmp_path), trained,
            anchor=checkpoint_epoch(str(tmp_path / "model_8.pth")))
        assert used.endswith("model_8.pth")
        assert int(jax.device_get(state.epoch)) == 8

    def test_truncation_detected(self, trained, tmp_path):
        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            CheckpointCorruptError, load_checkpoint, save_checkpoint)

        path = save_checkpoint(str(tmp_path), trained, 1)
        blob = open(path, "rb").read()
        open(path, "wb").write(blob[: len(blob) // 2])
        with pytest.raises(CheckpointCorruptError, match="model_1.pth"):
            load_checkpoint(path, trained)

    def test_all_corrupt_raises_last_error(self, trained, tmp_path):
        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            CheckpointCorruptError, load_with_fallback, save_checkpoint)

        for e in (1, 2):
            p = save_checkpoint(str(tmp_path), trained, e)
            open(p, "ab").write(b"rot")
        with pytest.raises(CheckpointCorruptError):
            load_with_fallback(str(tmp_path), trained)
        with pytest.raises(FileNotFoundError):
            load_with_fallback(str(tmp_path / "empty"), trained)

    def test_fallback_agreement_is_symmetric(self, trained, tmp_path,
                                             monkeypatch):
        """Divergent per-host fallback epochs raise on EVERY host —
        including one whose own walk succeeded. An asymmetric check
        (only the disagreeing peer dies) leaves the survivors wedged
        forever at their next training collective."""
        import jax.experimental.multihost_utils as mhu

        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            CheckpointCorruptError, load_with_fallback, save_checkpoint)

        save_checkpoint(str(tmp_path), trained.replace(
            epoch=jnp.asarray(2, jnp.int32)), 2)
        calls = []

        def fake_allgather(x):
            calls.append(int(x))
            return np.asarray([int(x), 1])  # peer verified only epoch 1

        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(mhu, "process_allgather", fake_allgather)
        with pytest.raises(CheckpointCorruptError, match="diverged"):
            load_with_fallback(str(tmp_path), trained)
        assert calls == [2]  # this host verified fine — raises anyway

    def test_fallback_exhausted_host_still_reaches_agreement(
            self, trained, tmp_path, monkeypatch):
        """A host whose WHOLE walk is corrupt still participates in
        the one agreement collective (with -1) instead of raising
        before it — peers blocked inside the all-gather would
        otherwise hang forever; unanimous exhaustion then surfaces
        the last corruption error."""
        import jax.experimental.multihost_utils as mhu

        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            CheckpointCorruptError, load_with_fallback, save_checkpoint)

        for e in (1, 2):
            p = save_checkpoint(str(tmp_path), trained, e)
            open(p, "ab").write(b"rot")
        calls = []

        def fake_allgather(x):
            calls.append(int(x))
            return np.asarray([int(x), int(x)])  # unanimous

        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(mhu, "process_allgather", fake_allgather)
        with pytest.raises(CheckpointCorruptError, match="sha256"):
            load_with_fallback(str(tmp_path), trained)
        assert calls == [-1]  # exhausted => sentinel, AFTER the walk

    def test_auto_resume_missing_peer_raises_on_every_host(
            self, trained, tmp_path, monkeypatch):
        """resolve_auto_resume's presence check is symmetric too: when
        ANY host lacks the resolved file, every host — including one
        that found it — raises, instead of the found-it hosts
        proceeding into load_with_fallback's collective with a dead
        peer."""
        import jax.experimental.multihost_utils as mhu

        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            resolve_auto_resume, save_checkpoint)

        save_checkpoint(str(tmp_path), trained, 2)  # THIS host has it
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(mhu, "broadcast_one_to_all", lambda x: x)
        monkeypatch.setattr(
            mhu, "process_allgather",
            lambda x: np.asarray([int(x), 0]))  # peer: missing
        with pytest.raises(FileNotFoundError, match="EVERY rank"):
            resolve_auto_resume(str(tmp_path))

    def test_legacy_checkpoint_without_sidecar_loads(self, trained,
                                                     tmp_path):
        from flax import serialization

        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            load_checkpoint)

        path = tmp_path / "model_1.pth"
        path.write_bytes(serialization.to_bytes(
            jax.device_get(trained)))
        restored = load_checkpoint(str(path), trained)  # no digest file
        np.testing.assert_array_equal(
            jax.tree.leaves(jax.device_get(restored.params))[0],
            jax.tree.leaves(jax.device_get(trained.params))[0])

    def test_resave_crash_window_never_pairs_stale_digest(
            self, trained, tmp_path, monkeypatch):
        """Re-save of the SAME epoch (preemption re-save, torn-epoch
        redo) that crashes between the checkpoint replace and the
        sidecar replace must degrade to 'valid checkpoint, no digest'
        (legacy load) — never the OLD digest paired with the NEW
        payload (a valid checkpoint reported corrupt)."""
        from pytorch_multiprocessing_distributed_tpu.train import (
            checkpoint as ckpt)

        ckpt.save_checkpoint(str(tmp_path), trained.replace(
            epoch=jnp.asarray(1, jnp.int32)), 1)
        real = ckpt.write_atomic_durable
        calls = {"n": 0}

        def crash_before_sidecar(path, payload):
            calls["n"] += 1
            if calls["n"] == 2:  # the sidecar write of the re-save
                raise OSError("simulated crash before sidecar replace")
            real(path, payload)

        monkeypatch.setattr(ckpt, "write_atomic_durable",
                            crash_before_sidecar)
        with pytest.raises(OSError, match="simulated crash"):
            ckpt.save_checkpoint(str(tmp_path), trained.replace(
                epoch=jnp.asarray(1, jnp.int32)), 1)
        monkeypatch.setattr(ckpt, "write_atomic_durable", real)
        path = ckpt.checkpoint_path(str(tmp_path), 1)
        assert not os.path.exists(ckpt.digest_path(path))  # stale gone
        state = ckpt.load_checkpoint(path, trained)  # legacy, valid
        assert int(jax.device_get(state.epoch)) == 1

    def test_prune_removes_sidecars(self, trained, tmp_path):
        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            digest_path, prune_checkpoints, save_checkpoint)

        paths = [save_checkpoint(str(tmp_path), trained, e)
                 for e in (1, 2, 3)]
        prune_checkpoints(str(tmp_path), keep=1)
        assert not os.path.exists(paths[0])
        assert not os.path.exists(digest_path(paths[0]))
        assert os.path.exists(paths[2])
        assert os.path.exists(digest_path(paths[2]))


# ------------------------------------------- preemption (SIGTERM) path

@pytest.mark.slow
def test_sigterm_preemption_checkpoints_and_exits(tmp_path):
    """In-process SIGTERM through the trainer's REAL handler chain:
    the signal lands mid-epoch, `_install_preemption_handler`'s flag
    is noticed at the next metrics window, `_checkpoint_if_preempted`
    writes a RESUMABLE checkpoint for epoch-1 and training exits
    cleanly (SystemExit 0) with the previous handler restored."""
    from pytorch_multiprocessing_distributed_tpu.data.pipeline import (
        ShardedLoader)
    from pytorch_multiprocessing_distributed_tpu.parallel import make_mesh
    from pytorch_multiprocessing_distributed_tpu.train import (
        create_train_state, load_checkpoint)
    from pytorch_multiprocessing_distributed_tpu.train.optim import sgd
    from pytorch_multiprocessing_distributed_tpu.train.trainer import (
        Trainer)

    rng = np.random.default_rng(0)
    images = rng.integers(0, 255, (64, 32, 32, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, (64,)).astype(np.int64)
    loader = lambda train: ShardedLoader(  # noqa: E731
        images, labels, batch_size=16, world_size=8, train=train,
        shuffle=False, with_valid=not train)
    mesh = make_mesh()
    model = models.get_model("vit_tiny", num_classes=10)
    opt = sgd(learning_rate=0.1)
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((2, 32, 32, 3)), opt)
    trainer = Trainer(
        model=model, optimizer=opt, mesh=mesh, state=state,
        train_loader=loader(True), test_loader=loader(False),
        save_path=str(tmp_path), epochs=50, print_freq=2)

    prev = signal.getsignal(signal.SIGTERM)
    orig_step = trainer.train_step
    calls = {"n": 0}

    def step_then_preempt(s, x, y):
        calls["n"] += 1
        if calls["n"] == 3:  # mid-epoch, mid-window: the real shape
            signal.raise_signal(signal.SIGTERM)
        return orig_step(s, x, y)

    trainer.train_step = step_then_preempt
    with pytest.raises(SystemExit) as exc:
        trainer.fit()
    assert exc.value.code == 0  # clean exit, not a crash
    assert calls["n"] >= 3  # the signal really fired mid-training
    # the resume artifact: epoch-1 = 0 (interrupted during epoch 1)
    path = tmp_path / "model_0.pth"
    assert path.exists()
    restored = load_checkpoint(str(path), state)
    assert int(jax.device_get(restored.epoch)) == 0  # resume redoes ep 1
    # handler restored: a later SIGTERM must not re-enter the trainer
    assert signal.getsignal(signal.SIGTERM) == prev
