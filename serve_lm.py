"""Continuous-batching LM serving CLI — the inference counterpart of
``train_lm.py``.

Loads a trained GPT checkpoint (msgpack ``model_<epoch>.pth`` or an
Orbax run directory — the same backends ``train_lm.py`` writes) and
serves a request stream through the slot-based
:class:`~pytorch_multiprocessing_distributed_tpu.serving.ServingEngine`:
requests join a persistent decode loop as KV slots free up, the jitted
decode step compiles once per length bucket (``--decode_buckets`` —
step cost tracks the longest ACTIVE sequence, not ``--s_max``), long
prompts can prefill in fixed chunks interleaved with decode
(``--prefill_chunk`` — no resident request stalls longer than one
chunk), steady-state decode can fuse H steps into one dispatched scan
with one readback per horizon (``--decode_horizon`` — host
syncs/token = 1/H; at every H, 1 included, the next block is
dispatched before this one is read back, so the readback and the
host's own work hide under a running decode program), speculative
decode can verify up to K
drafted tokens per target pass (``--draft_k`` [+ ``--draft_model``],
graftspec — greedy only, byte-identical streams, 1..K+1 tokens per
weight stream), and per-request tokens stream to stdout as they are
emitted.

Request sources (first match wins):
  --requests FILE   JSON Lines, one request per line:
                      {"prompt": [ids...], "max_new_tokens": 16}
                    or {"text": "byte-level prompt", ...} (ids 0..255,
                    matching train_lm.py's text tokenizer)
  --stdin           one prompt per line, byte-level tokens
  --synthetic N     N deterministic Zipf prompts (default; no assets
                    needed — smoke runs and benchmarks)

Observability (graftscope): ``--trace_out t.json`` (Chrome-trace/
Perfetto timeline), ``--events_out e.jsonl`` (raw event log with one
``request.timeline`` lifecycle summary per request), ``--stats_port N``
(live Prometheus ``/metrics`` + ``/snapshot.json`` + ``/healthz`` over
stdlib http.server), ``--flight_path f.jsonl`` (flight-recorder dump on
engine-fatal errors). The final metrics snapshot carries p50/p90/
p95/p99 for TTFT, queue wait, and decode step beside the averages.

Elastic runtime (graftheal): SIGTERM drains gracefully — admission
closes (``/healthz`` flips to 503 for the replica router), in-flight
requests finish up to ``--drain_deadline_s``, overdue ones fail named,
exit is 0. ``--journal wal.jsonl`` WALs every admitted request + its
emitted tokens so a restart redelivers the unfinished ones token-exact;
``--max_restarts N --restart_backoff S`` wraps the whole loop in the
bounded-backoff supervisor (named fatals rebuild the engine and replay
the journal; budget exhaustion fails loudly).

Examples (CPU mesh):
  PMDT_FORCE_CPU_DEVICES=8 python serve_lm.py --model gpt_tiny \\
      --random_init --synthetic 8 --max_slots 4 --max_new_tokens 16
  python serve_lm.py --model gpt_tiny --ckpt lm_run/model_2.pth \\
      --requests reqs.jsonl --max_slots 8 --tp 2 --metrics_out m.json \\
      --trace_out trace.json --stats_port 9100
"""

import argparse
import json
import sys

from pytorch_multiprocessing_distributed_tpu.runtime import (
    fleet, heal, scope as graftscope)
from pytorch_multiprocessing_distributed_tpu.utils.compile_cache import (
    CompileLog, enable_compilation_cache)

parser = argparse.ArgumentParser(
    description="TPU-native continuous-batching LM serving")
parser.add_argument('--model', default='gpt_tiny', type=str,
                    help='gpt_tiny | gpt_small | gpt_medium | '
                         'xing4_tiny | xing4_29b_a4b | '
                         'pangu_ultra_moe_tiny | pangu_ultra_moe_718b | '
                         'afmoe_tiny | trinity_large_preview | '
                         'mimo_v2_tiny | mimo_v2_5 | '
                         'lfm2_moe_tiny | lfm2_8b_a1b')
parser.add_argument('--model_kwargs', default='', type=str,
                    help='JSON object of keywords for the registry '
                         'constructor, e.g. the depth one serving stage '
                         'holds: \'{"num_layers": 5, "first_k_dense": 1}\', '
                         'or with it one chip\'s share of an '
                         'expert-parallel stage: \'{..., "experts_held": '
                         '16, "expert_offset": 0, "vocab_size": 19200}\'')
parser.add_argument('--ckpt', default='', type=str,
                    help='msgpack model_<epoch>.pth file, or an orbax '
                         'run directory (train_lm.py --save_path)')
parser.add_argument('--ckpt_backend', default='auto',
                    choices=['auto', 'msgpack', 'orbax'])
parser.add_argument('--ckpt_epoch', default=None, type=int,
                    help='orbax only: serve a specific epoch '
                         '(default latest)')
parser.add_argument('--random_init', action='store_true',
                    help='serve fresh random params (smoke/benchmark '
                         'runs; mutually exclusive with --ckpt)')
parser.add_argument('--max_slots', default=4, type=int,
                    help='concurrent requests decoded per step (the '
                         'KV slot pool size)')
parser.add_argument('--s_max', default=0, type=int,
                    help='per-slot token capacity (prompt + generated; '
                         '0 = model.max_seq_len)')
parser.add_argument('--max_queue', default=0, type=int,
                    help='queued-request bound; submissions beyond it '
                         'are REJECTED (0 = unbounded)')
parser.add_argument('--decode_buckets', default='auto', type=str,
                    help="decode attention-window ladder: 'auto' "
                         "(powers of two up to s_max), 'off' (always "
                         "the full s_max window — the pre-bucketing "
                         "behavior), or explicit sizes '64,128,512'. "
                         "Step cost tracks the longest ACTIVE "
                         "sequence's bucket instead of s_max. "
                         "COMPILE-LADDER COST MODEL: the decode "
                         "program set is buckets x {1, H} x {k off, "
                         "on} — one compile per (window bucket "
                         "touched) x (single-step and --decode_"
                         "horizon rung) x (plain and, with --draft_k, "
                         "speculative) — so an n-bucket ladder "
                         "compiles at most 4n decode programs, never "
                         "one per batch composition or prompt length")
parser.add_argument('--prefill_chunk', default=0, type=int,
                    help='admit prompts in fixed chunks of N tokens, '
                         'one chunk per engine step interleaved with '
                         'decode — bounds every resident request\'s '
                         'stall to one chunk (0 = whole-prompt '
                         'prefill-on-join)')
parser.add_argument('--decode_horizon', default=1, type=int,
                    help='fuse up to H decode steps into one '
                         'dispatched lax.scan with ONE token readback '
                         'per horizon — host syncs/token drops to 1/H '
                         '(the readback is hidden under the next '
                         'block at every H: that no longer depends on '
                         'this flag); the horizon collapses to 1 while '
                         'admission work is pending, so join latency '
                         'stays bounded (1 = per-step decode). '
                         'Compile cost: the {1, H} rung of the '
                         'buckets x {1, H} x {k off, on} decode '
                         'ladder (see --decode_buckets) — raising H '
                         'adds at most one program per bucket (x2 '
                         'with --draft_k armed), never a program per '
                         'horizon value (intermediate horizons snap '
                         'to 1)')
parser.add_argument('--decode_attn', default='auto',
                    choices=['auto', 'xla', 'pallas'],
                    help='decode-step attention: fused flash-decode '
                         'Pallas kernel or the XLA reference (auto = '
                         'pallas on single-shard TPU, xla elsewhere)')
parser.add_argument('--page_size', default=0, type=int,
                    help='columns per KV page (0 = min_bucket; '
                         'multiples of 8 on TPU): a request pins '
                         'ceil(total/page_size) pages, so HBM follows '
                         'real lengths')
parser.add_argument('--num_pages', default=0, type=int,
                    help='total KV pages incl. the scratch page (0 = '
                         'every slot at its worst case; size it '
                         'with `python -m ...analysis.meter --plan '
                         'MODEL --page_size N` to the real HBM '
                         'budget)')
parser.add_argument('--kv_dtype', default='model',
                    choices=['model', 'int8'],
                    help='graftquant KV element layout: model dtype, '
                         'or int8 lanes + one f32 scale per '
                         'head_dim group (~half the KV bytes at '
                         'bf16 — ~1.9x resident requests at fixed '
                         'HBM, size it with `python -m '
                         '...analysis.meter --plan MODEL --kv_dtype '
                         'int8`; greedy transcripts equal on the '
                         'pinned configs, logit delta budgeted in '
                         'tests — audited, not exact)')
parser.add_argument('--prefix_cache', default=0, type=int,
                    help='greedy mode: LRU entries of the '
                         'shared-prefix cache — identical prompts '
                         'prefill ONCE and re-join copy-on-write '
                         '(TTFT(hit) ~ one decode step); 0 = off')
parser.add_argument('--draft_k', default=0, type=int,
                    help='graftspec: arm speculative decode with up '
                         'to K draft tokens verified per target pass '
                         '(greedy serving only — rejected loudly with '
                         '--temperature > 0). Self-drafting n-gram '
                         'tables by default; token streams stay '
                         'byte-identical to the non-speculative '
                         'engine (0 = off)')
parser.add_argument('--draft_model', default='', type=str,
                    help='graftspec: registry name of a small DRAFT '
                         'model proposing the k tokens instead of '
                         'self-drafting (must share the vocab; pair '
                         'with --draft_ckpt for trained drafts)')
parser.add_argument('--draft_ckpt', default='', type=str,
                    help='msgpack checkpoint for --draft_model '
                         '(default: random init — correct but '
                         'low-acceptance; fine for smoke runs)')
parser.add_argument('--max_new_tokens', default=32, type=int,
                    help='default per-request budget (jsonl requests '
                         'override per line)')
parser.add_argument('--eos', default=-1, type=int,
                    help='stop token id (-1 = none; byte-level text '
                         'corpora use 256 as the doc separator)')
parser.add_argument('--tp', default=1, type=int,
                    help='model-axis size: heads/KV-slots/vocab head '
                         'sharded for single-host TP serving')
parser.add_argument('--temperature', default=0.0, type=float)
parser.add_argument('--top_k', default=0, type=int)
parser.add_argument('--top_p', default=0.0, type=float)
parser.add_argument('--seed', default=0, type=int)
parser.add_argument('--dtype', default='float32',
                    choices=['float32', 'bfloat16'])
parser.add_argument('--requests', default='', type=str,
                    help='JSON Lines request file')
parser.add_argument('--stdin', action='store_true',
                    help='read one byte-level prompt per stdin line')
parser.add_argument('--synthetic', default=0, type=int,
                    help='serve N synthetic Zipf prompts (default 8 '
                         'when no other source is given)')
parser.add_argument('--metrics_out', default='', type=str,
                    help='write the final metrics snapshot as JSON')
parser.add_argument('--quiet', action='store_true',
                    help='suppress per-token streaming lines')
# --- graftroute: fleet serving ---
parser.add_argument('--replicas', default=1, type=int,
                    help='graftroute: serve through an in-process '
                         'fleet of N engine replicas behind one load- '
                         'and cache-aware Router — per-replica '
                         'admission windows, cross-replica work '
                         'stealing, journal redelivery on replica '
                         'death (1 = the single-engine path)')
parser.add_argument('--role', default='both', type=str,
                    help="graftroute replica roles: 'both' (every "
                         "replica prefills AND decodes), 'split' "
                         "(replica 0 runs ONLY prefill and hands "
                         "finished KV page-blocks to the decode "
                         "replicas — prefill/decode disaggregation; "
                         "needs --replicas >= 2), or an explicit "
                         "comma list 'prefill,decode,decode' of "
                         "length --replicas (at least one "
                         "decode-capable role required)")
parser.add_argument('--router_port', default=0, type=int,
                    help='graftroute: serve the ROUTER-level stats/'
                         'health endpoint — merged fleet metrics '
                         '(redelivery-deduped) on /metrics + '
                         '/snapshot.json, aggregated per-replica '
                         'states on /healthz (0 = off)')
# --- graftwire: the socket transport behind the replica seam ---
parser.add_argument('--listen', default='', type=str,
                    metavar='HOST:PORT',
                    help='graftwire: host THIS engine as ONE replica '
                         'server behind the framed socket RPC surface '
                         '(a remote --connect router drives it with '
                         'in-process semantics). HOST defaults to '
                         '127.0.0.1, PORT 0 picks a free port — the '
                         'bound address is printed as "graftwire: '
                         'listening on HOST:PORT". The process exits '
                         '0 once a router drains it; SIGTERM flips it '
                         'DRAINING and, after an idle grace with no '
                         'router traffic, it drains itself. Pair with '
                         '--rid/--role (single role) and --journal '
                         '(the WAL a router redelivers from if this '
                         'process is killed)')
parser.add_argument('--rid', default='r0', type=str,
                    help='graftwire: replica id this server announces '
                         'in its hello (journal names, directory keys '
                         'and straggler reports use it)')
parser.add_argument('--connect', default='', type=str,
                    metavar='ADDR[,ADDR...]',
                    help='graftwire: build the fleet from REMOTE '
                         'replica servers at these host:port '
                         'addresses instead of in-process engines — '
                         'the same Router, placement, stealing and '
                         'redelivery logic runs over the socket '
                         'transport (streams byte-identical to the '
                         'in-process fleet). Omit it but pass '
                         '--fleet_store to bootstrap from the '
                         'store-published replica_directory roster')
parser.add_argument('--fleet_store', default='', type=str,
                    metavar='HOST:PORT',
                    help='graftwire: TCPStore control-plane address. '
                         'With --listen the server publishes {role, '
                         'state, address, published_at} there; with '
                         'neither --listen nor --connect it is the '
                         'roster the fleet bootstraps from '
                         '(stale entries TTL-filtered)')
parser.add_argument('--fleet_run', default='run', type=str,
                    help='graftwire: run uid namespacing the replica '
                         'directory keys on the fleet store')
parser.add_argument('--fleet_ttl', default=30.0, type=float,
                    help='graftwire: replica_directory staleness '
                         'filter — roster entries whose published_at '
                         'stamp is older than this many seconds are '
                         'skipped (a crashed publisher ages out '
                         'instead of being dialed forever; 0 = no '
                         'filter)')
# --- graftscale: traffic-driven autoscaling + rolling rollout ---
parser.add_argument('--autoscale', default='', type=str,
                    metavar='MIN,MAX',
                    help='graftscale: let TRAFFIC size the in-process '
                         'fleet between MIN and MAX decode-capable '
                         'replicas — sustained FleetSaturated sheds / '
                         'pending depth above the combined admission '
                         'windows scale UP, sustained idleness drains '
                         'the least-loaded replica DOWN (hysteresis + '
                         'cooldown: never flaps). --replicas seeds the '
                         'initial size; prefill-role replicas scale '
                         'independently')
parser.add_argument('--rollout', default='', type=str,
                    metavar='PARAMS',
                    help='graftscale: rolling weight rollout under '
                         'load — spawn new-version replicas '
                         '(model_tag v1) from this checkpoint, warm '
                         'them, drain the v0 fleet one replica at a '
                         'time; zero failed requests, every stream '
                         'served start-to-finish by exactly one '
                         'version. PARAMS is a checkpoint path, or '
                         "'seed:N' (random init, smoke runs). "
                         'Implies --autoscale 1,R+1 if not set')
# --- graftheal: elastic runtime ---
parser.add_argument('--drain_deadline_s', default=0.0, type=float,
                    help='graceful-drain bound: on SIGTERM (or source '
                         'exhaustion) in-flight requests get this many '
                         'seconds to finish; overdue ones are FAILED '
                         'named, then the engine exits 0 '
                         '(0 = unbounded drain)')
parser.add_argument('--journal', default='', type=str, metavar='JSONL',
                    help='request-redelivery WAL: admitted-but-'
                         'unfinished requests are journaled (fsync\'d '
                         'appends, atomic compaction) and a restarted '
                         'engine re-submits them token-exact — the '
                         'supervised-restart recovery path (greedy '
                         'decode only)')
parser.add_argument('--max_restarts', default=0, type=int,
                    help='supervised restart budget: catch named-fatal '
                         'errors (GraftFaultError family), rebuild the '
                         'engine, replay the --journal, and keep '
                         'serving — at most N times, with exponential '
                         '--restart_backoff (0 = die on first fatal)')
parser.add_argument('--restart_backoff', default=1.0, type=float,
                    help='first-restart delay in seconds (doubles per '
                         'restart, capped at 30s)')
graftscope.add_cli_args(parser, stats_port=True)


def _fleet_store(addr):
    """Dial the control-plane TCPStore behind --fleet_store."""
    from pytorch_multiprocessing_distributed_tpu.runtime.store import (
        TCPStore)

    host, _, port = addr.rpartition(':')
    if not port.isdigit():
        raise SystemExit(
            f"--fleet_store must be HOST:PORT, got {addr!r}")
    return TCPStore(host or '127.0.0.1', int(port))


def _load_requests(args, vocab_size, skipped):
    """Yield (prompt_ids, max_new_tokens) from the selected source;
    malformed jsonl lines are appended to ``skipped`` (one bad line
    must not kill the requests already being served)."""
    if args.requests:
        with open(args.requests) as f:
            for lineno, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                    if "prompt" in obj:
                        ids = [int(t) for t in obj["prompt"]]
                    elif "text" in obj:
                        ids = [min(b, vocab_size - 1)
                               for b in obj["text"].encode("utf-8")]
                    else:
                        raise ValueError("needs 'prompt' or 'text'")
                    max_new = int(obj.get("max_new_tokens",
                                          args.max_new_tokens))
                except (ValueError, TypeError, AttributeError) as e:
                    skipped.append(f"line {lineno}: {e}")
                    continue
                yield ids, max_new
    elif args.stdin:
        for line in sys.stdin:
            line = line.rstrip("\n")
            if line:
                yield ([min(b, vocab_size - 1)
                        for b in line.encode("utf-8")],
                       args.max_new_tokens)
    else:
        import numpy as np

        n = args.synthetic or 8
        rng = np.random.default_rng(args.seed)
        for i in range(n):
            length = int(rng.integers(4, 24))
            yield (rng.integers(0, vocab_size, (length,)).tolist(),
                   args.max_new_tokens)


def main():
    args = parser.parse_args()
    if args.ckpt and args.random_init:
        raise SystemExit("--ckpt and --random_init are mutually "
                         "exclusive")
    if not args.ckpt and not args.random_init:
        raise SystemExit("pass --ckpt PATH (trained params) or "
                         "--random_init (smoke run)")
    # arm BEFORE the engine exists: compile-phase prefill/insert spans
    # are part of the timeline (warm-up cost made visible, not hidden)
    graftscope.arm_from_args(args)
    from pytorch_multiprocessing_distributed_tpu.runtime import hbm

    if args.stats_port:
        # graftmeter HBM ledger: armed before the engine so the
        # params/KV-pool registrations land — /metrics then carries
        # hbm_* capacity gauges beside the serving meters
        hbm.arm()
    from pytorch_multiprocessing_distributed_tpu.utils.hostenv import (
        announce_done, announce_run, force_cpu_devices_from_env)

    force_cpu_devices_from_env()
    cache_dir = enable_compilation_cache()
    compile_log = CompileLog()

    import jax
    import jax.numpy as jnp

    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.inference import (
        shard_params_for_tp_decode)
    from pytorch_multiprocessing_distributed_tpu.parallel import make_mesh
    from pytorch_multiprocessing_distributed_tpu.serving import (
        QueueFull, Request, ServingEngine, init_params, load_params)

    dtype = jnp.bfloat16 if args.dtype == 'bfloat16' else jnp.float32
    platform = jax.devices()[0].platform
    model = models.get_model(
        args.model, dtype=dtype,
        attn_impl="flash" if platform == "tpu" else "xla",
        **(json.loads(args.model_kwargs) if args.model_kwargs else {}))
    if args.random_init:
        params = init_params(model, args.seed)
    else:
        params = load_params(model, args.ckpt, args.ckpt_backend,
                             args.ckpt_epoch)
    mesh = None
    if args.tp > 1:
        n_dev = len(jax.devices())
        if n_dev % args.tp:
            raise SystemExit(
                f"--tp {args.tp} does not divide {n_dev} devices (CPU "
                f"runs: PMDT_FORCE_CPU_DEVICES=8)")
        mesh = make_mesh(n_dev // args.tp, args.tp)
        params = shard_params_for_tp_decode(params, mesh)

    if args.decode_buckets == 'auto':
        decode_buckets = None
    elif args.decode_buckets == 'off':
        decode_buckets = ()
    else:
        decode_buckets = [int(b) for b in args.decode_buckets.split(',')]

    # graftspec: loud rejection BEFORE any compile — a sampled stream
    # cannot be verified by argmax matching
    if args.draft_k and args.temperature > 0:
        raise SystemExit(
            "--draft_k (speculative decode) is greedy-only: drop "
            "--temperature or disarm speculation")
    if args.draft_model and not args.draft_k:
        raise SystemExit("--draft_model needs --draft_k > 0")
    draft_model = draft_params = None
    if args.draft_k and args.draft_model:
        draft_model = models.get_model(
            args.draft_model, dtype=dtype,
            vocab_size=model.vocab_size, attn_impl="xla")
        if args.draft_ckpt:
            draft_params = load_params(draft_model, args.draft_ckpt,
                                       "msgpack", None)
        else:
            draft_params = init_params(draft_model, args.seed + 1)

    run_info = {}

    def build_engine(journal, params_override=None):
        engine = ServingEngine(
            model, params if params_override is None else params_override,
            max_slots=args.max_slots,
            s_max=args.s_max or None,
            mesh=mesh,
            max_queue=args.max_queue or None,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p,
            rng=(jax.random.PRNGKey(args.seed)
                 if args.temperature > 0 else None),
            eos_id=None if args.eos < 0 else args.eos,
            decode_buckets=decode_buckets,
            prefill_chunk=args.prefill_chunk or None,
            decode_horizon=args.decode_horizon,
            decode_attn=args.decode_attn,
            kv_dtype=args.kv_dtype,
            page_size=args.page_size or None,
            num_pages=args.num_pages or None,
            prefix_cache=args.prefix_cache,
            draft_k=args.draft_k,
            draft_model=draft_model,
            draft_params=draft_params,
            journal=journal)
        if not run_info:
            # what was chosen from the platform, printed once at
            # start-up and carried into the metrics snapshot
            run_info.update(announce_run(
                cache_dir, prefill_attn=model.attn_impl,
                decode_attn=engine.decode_attn,
                donate_cache=engine.donate_cache))
        return engine

    # ---- graftwire: host this engine as one replica server ----------
    if args.listen:
        if args.replicas > 1 or args.connect:
            raise SystemExit(
                "--listen hosts ONE replica server; run one process "
                "per replica and point a --connect router at them")
        if args.role not in ('both', 'prefill', 'decode'):
            raise SystemExit(
                "--listen needs a single role: --role both|prefill|"
                "decode (the 'split'/csv forms describe a whole "
                "fleet, which the --connect router owns)")
        from pytorch_multiprocessing_distributed_tpu.serving import (
            ReplicaServer)

        journal = (heal.RequestJournal(args.journal) if args.journal
                   else None)
        engine = build_engine(journal)
        store = (_fleet_store(args.fleet_store) if args.fleet_store
                 else None)
        host, _, port = args.listen.rpartition(':')
        if not port.isdigit():
            raise SystemExit(
                f"--listen must be HOST:PORT (PORT 0 = pick free), "
                f"got {args.listen!r}")
        server = ReplicaServer(
            engine, rid=args.rid, role=args.role,
            host=host or '127.0.0.1', port=int(port), store=store,
            run_uid=args.fleet_run)
        server.start()
        print(f"graftwire: listening on {server.address} "
              f"(rid={args.rid} role={args.role})", flush=True)
        prev_handler = heal.install_drain_handler(engine)
        stats_server = None
        if args.stats_port:
            engine.metrics.bound_samples(8192)

            def live_snapshot():
                snap = engine.metrics.snapshot()
                ledger = hbm.active_ledger()
                if ledger is not None:
                    snap.update(ledger.snapshot())
                from pytorch_multiprocessing_distributed_tpu.runtime \
                    import wire as graftwire

                snap.update(graftwire.wire_meter())
                return snap

            stats_server = graftscope.start_stats_server(
                live_snapshot, port=args.stats_port,
                health_fn=lambda: heal.healthz(
                    engine.health, heal.active_monitor()),
                events_fn=graftscope.scope_events_fn)
            print(f"stats: http://127.0.0.1:"
                  f"{stats_server.server_address[1]}/metrics "
                  f"(+ /healthz)", flush=True)
        try:
            with graftscope.flight_recorder("serve_lm replica server"):
                server.serve_forever(
                    drain_deadline_s=args.drain_deadline_s or None)
        finally:
            heal.restore_drain_handler(prev_handler)
            if stats_server is not None:
                stats_server.shutdown()
        from pytorch_multiprocessing_distributed_tpu.runtime import (
            wire as graftwire)

        snap = engine.metrics.snapshot()
        snap.update(graftwire.wire_meter())
        snap.update(run_info)
        print("metrics: " + json.dumps(snap, sort_keys=True),
              flush=True)
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump(snap, f, indent=2, sort_keys=True)
        graftscope.export_from_args(args)
        return

    def emit(events):
        if args.quiet:
            return
        for request, token, finished in events:
            print(f"req={request.uid} tok={token}"
                  + (f" done({request.finish_reason})" if finished
                     else ""),
                  flush=True)
            if finished:
                print(f"req={request.uid} tokens={request.tokens}",
                      flush=True)

    rejected = [0]
    skipped = []
    served = []
    # ONE source across restart attempts: a request consumed before a
    # crash is in the journal (redelivered), the rest stay unconsumed
    # here — an in-process restart never double-submits. Source
    # requests also get DETERMINISTIC uids (src-<index>, counted
    # across attempts), so a whole-PROCESS restart re-reading the same
    # source skips everything the journal already knows (done or
    # redelivered) instead of double-serving it.
    source = _load_requests(args, model.vocab_size, skipped)
    src_idx = [0]
    # the one item consumed from the generator but not yet admitted:
    # retained across restart attempts — a fatal striking between
    # next(source) and a successful enqueue must not make the request
    # vanish (the generator will never yield it again)
    pending_src = [None]

    def serve_once(attempt):
        """One engine incarnation: build (replaying the journal's
        unfinished requests token-exact), serve the source, drain
        gracefully. SIGTERM flips the engine to DRAINING — admission
        closes, in-flight work finishes up to --drain_deadline_s,
        exit is a clean 0. A named fatal propagates to the
        supervisor, which rebuilds and replays (--max_restarts)."""
        journal = (heal.RequestJournal(args.journal) if args.journal
                   else None)
        engine = build_engine(journal)
        if attempt:
            print(f"graftheal: restart {attempt}: engine rebuilt"
                  + (f", replaying {len(journal.unfinished())} "
                     f"journaled request(s)" if journal else ""),
                  flush=True)
        prev_handler = heal.install_drain_handler(engine)
        stats_server = None
        if args.stats_port:
            # live telemetry beside the serving loop: /metrics
            # (Prometheus) + /snapshot.json + /healthz (200 only while
            # READY — the replica router's probe); the graftmeter
            # hbm_* gauges and the graftfleet goodput_* gauges ride
            # the same snapshot. A live server's percentile meters
            # are CAPPED (graftfleet): exact tails over the most
            # recent window, bounded memory over an unbounded run.
            engine.metrics.bound_samples(8192)
            fleet.arm_goodput()

            def live_snapshot():
                snap = engine.metrics.snapshot()
                ledger = hbm.active_ledger()
                if ledger is not None:
                    snap.update(ledger.snapshot())
                    snap["hbm_per_slot_bytes"] = \
                        engine.pool.per_slot_bytes
                snap.update(fleet.goodput_gauges())
                return snap

            stats_server = graftscope.start_stats_server(
                live_snapshot, port=args.stats_port,
                health_fn=lambda: heal.healthz(
                    engine.health, heal.active_monitor()),
                # /events.json (graftfleet): the fleet collector's
                # merged-timeline feed — reads the ARMED scope live
                # (follows re-arms), ?since= cursor for incremental
                # scrapes
                events_fn=graftscope.scope_events_fn)
            print(f"stats: http://127.0.0.1:"
                  f"{stats_server.server_address[1]}/metrics "
                  f"(+ /healthz)", flush=True)
            # graftfleet: announce this replica's scrape address to
            # the fleet store (no-op unless PMDT_FLEET armed a
            # monitor at rendezvous)
            fleet.publish_endpoint(
                f"127.0.0.1:{stats_server.server_address[1]}")
        try:
            # a crash anywhere in the drive loop leaves the flight
            # ring on disk before propagating (engine-internal fatals
            # already dump; this covers the CLI's own loop)
            with graftscope.flight_recorder("serve_lm drive loop"):
                if journal is not None:
                    replay_events = []
                    served.extend(engine.redeliver(
                        journal.unfinished(),
                        events_out=replay_events))
                    emit(replay_events)
                while not engine.health.draining:
                    if pending_src[0] is None:
                        try:
                            prompt, max_new = next(source)
                        except StopIteration:
                            break
                        pending_src[0] = (f"src-{src_idx[0]}", prompt,
                                          max_new)
                        src_idx[0] += 1
                    uid, prompt, max_new = pending_src[0]
                    if journal is not None and journal.known(uid):
                        pending_src[0] = None  # served/redelivered
                        continue
                    request = Request(prompt, max_new, engine.eos_id,
                                      uid=uid)
                    handled = False
                    while True:
                        try:
                            engine.enqueue(request)
                            served.append(request)
                            handled = True
                            break
                        except QueueFull:
                            if engine.health.draining:
                                # admission CLOSED for good this
                                # incarnation — the item stays pending
                                # for a restart to pick up
                                break
                            # finite source + bounded queue =
                            # backpressure, not load shedding: drain a
                            # step, then re-enqueue the SAME request
                            # (its submit_time — and so its TTFT —
                            # keeps the first attempt's stamp)
                            emit(engine.step())
                        except ValueError as e:
                            rejected[0] += 1
                            print(f"rejected: {e}", file=sys.stderr)
                            handled = True  # permanently invalid
                            break
                    if handled:
                        pending_src[0] = None
                    if engine.health.draining:
                        break
                    if args.stdin:
                        # online source: serve while the producer is
                        # still typing (an offline file bulk-admits +
                        # drains below)
                        emit(engine.step())
                # serve while READY (healthz 200, admission open —
                # the replica is routable until the work is done or a
                # SIGTERM flips it); then the terminal drain: finish
                # anything still in flight up to the deadline, fail
                # overdue ones NAMED, compact the journal (empty
                # after a clean full drain), land DEAD, exit 0
                while engine.in_flight and not engine.health.draining:
                    emit(engine.step())
                emit(engine.drain(args.drain_deadline_s or None))
        finally:
            heal.restore_drain_handler(prev_handler)
            if stats_server is not None:
                stats_server.shutdown()
        return engine

    # ---- graftroute: fleet behind one router (in-process replicas,
    # or graftwire remote replica servers via --connect/--fleet_store)
    remote_mode = bool(args.connect or args.fleet_store)
    scale_mode = bool(args.autoscale or args.rollout)
    fleet_mode = (args.replicas > 1 or args.role != 'both'
                  or remote_mode or scale_mode)
    if fleet_mode:
        from pytorch_multiprocessing_distributed_tpu.serving import (
            FleetAutoscaler, FleetSaturated, EngineReplicaSpawner,
            RemoteReplica, RollingRollout, Router, ServingReplica,
            fleet_from_directory)

        # ---- graftscale arming: bounds, rollout weights ------------
        scale_min = scale_max = 0
        if scale_mode:
            if remote_mode:
                raise SystemExit(
                    "graftscale: --autoscale/--rollout drive the "
                    "in-process fleet (the subprocess spawner lives "
                    "in benchmarks/scale_smoke.py) — drop --connect/"
                    "--fleet_store")
            spec = args.autoscale or f"1,{args.replicas + 1}"
            try:
                scale_min, scale_max = (int(x) for x in
                                        spec.split(','))
            except ValueError:
                raise SystemExit(
                    f"--autoscale must be MIN,MAX (two ints), got "
                    f"{args.autoscale!r}")
        rollout_params = None
        if args.rollout:
            if args.rollout.startswith('seed:'):
                rollout_params = init_params(
                    model, int(args.rollout[5:]))
            else:
                rollout_params = load_params(
                    model, args.rollout, args.ckpt_backend, None)
            if mesh is not None:
                rollout_params = shard_params_for_tp_decode(
                    rollout_params, mesh)
        # per-version engine factory: the spawner's seam. v1 IS the
        # rollout checkpoint; anything else serves the base weights
        base_tag = 'v0' if scale_mode else None

        def build_tagged(model_tag, journal):
            override = (rollout_params if model_tag == 'v1'
                        else None)
            return build_engine(journal, params_override=override)

        roles = []
        if not remote_mode:
            if args.replicas < 1:
                raise SystemExit("--replicas must be >= 1")
            if args.role == 'both':
                roles = ['both'] * args.replicas
            elif args.role == 'split':
                if args.replicas < 2:
                    raise SystemExit(
                        "--role split needs --replicas >= 2 (one "
                        "prefill replica handing KV blocks to >= 1 "
                        "decode replica)")
                roles = ['prefill'] + ['decode'] * (args.replicas - 1)
            else:
                roles = [r.strip() for r in args.role.split(',')]
                if len(roles) != args.replicas:
                    raise SystemExit(
                        f"--role lists {len(roles)} role(s) for "
                        f"--replicas {args.replicas}")
            if not any(r in ('both', 'decode') for r in roles):
                raise SystemExit(
                    "at least one replica must be decode-capable "
                    "(role 'both' or 'decode') — a prefill-only "
                    "fleet can never emit a token")

        def build_fleet():
            """The fleet's replica handles: remote graftwire servers
            (roles/journals live server-side, announced in hello), or
            the classic in-process engines."""
            def require_decode(replicas):
                # the remote twin of the in-process roles check —
                # validated HERE, at build time, so a prefill-only
                # fleet exits named instead of burning the whole
                # supervisor restart budget on FleetDead loops
                if not any(r.role in ('both', 'decode')
                           for r in replicas):
                    raise SystemExit(
                        "graftwire: no decode-capable replica among "
                        "the remote servers (roles: "
                        + ", ".join(f"{r.rid}={r.role}"
                                    for r in replicas)
                        + ") — a prefill-only fleet can never emit "
                        "a token")
                return replicas

            if args.connect:
                addrs = [a.strip() for a in args.connect.split(',')
                         if a.strip()]
                return require_decode([RemoteReplica(a)
                                       for a in addrs])
            if args.fleet_store:
                replicas = fleet_from_directory(
                    _fleet_store(args.fleet_store),
                    run_uid=args.fleet_run,
                    ttl_s=args.fleet_ttl or None)
                if not replicas:
                    raise SystemExit(
                        "graftwire: the replica directory at "
                        f"{args.fleet_store!r} (run "
                        f"{args.fleet_run!r}) yielded no live "
                        "replica — are the --listen servers up and "
                        "publishing?")
                return require_decode(replicas)
            replicas = []
            for i, role in enumerate(roles):
                rid = f"r{i}"
                journal = None
                if args.journal and role != 'prefill':
                    journal = heal.RequestJournal(
                        f"{args.journal}.{rid}")
                replicas.append(ServingReplica(
                    rid, build_engine(journal), role=role,
                    journal=journal, model_tag=base_tag))
            return replicas

        def serve_fleet_once(attempt):
            """One fleet incarnation: build N replicas behind one
            router (replaying each replica's journal token-exact),
            pump the source through fleet placement, drain
            gracefully. A replica death mid-run is absorbed INSIDE
            the router (journal redelivery to peers); only a
            whole-fleet fatal (FleetDead) reaches the supervisor."""
            replicas = build_fleet()
            router = Router(replicas)
            scaler = rollout = None
            if scale_mode:
                # spawned replicas get the same per-rid WAL the seed
                # replicas get — an autoscaled/rollout replica must
                # not silently downgrade its crash recovery to
                # router-record reconstruction
                journal_for = None
                if args.journal:
                    journal_for = (lambda rid: heal.RequestJournal(
                        f"{args.journal}.{rid}"))
                scaler = FleetAutoscaler(
                    router,
                    EngineReplicaSpawner(build_tagged,
                                         journal_for=journal_for),
                    min_replicas=scale_min, max_replicas=scale_max,
                    min_prefill=roles.count('prefill'),
                    max_prefill=(scale_max if 'prefill' in roles
                                 else 0),
                    model_tag=base_tag)
                if rollout_params is not None:
                    rollout = RollingRollout(scaler, 'v1')

            def pump():
                emit(router.step())
                if scaler is not None:
                    scaler.tick()
                if rollout is not None:
                    rollout.tick()
            if attempt:
                print(f"graftheal: restart {attempt}: fleet rebuilt "
                      f"({len(replicas)} replica(s))", flush=True)
            prev_handler = heal.install_drain_handler(router)
            stats_server = None
            if args.router_port:
                for r in replicas:
                    r.engine.metrics.bound_samples(8192)
                fleet.arm_goodput()

                def fleet_snapshot():
                    snap = router.merged_metrics()
                    snap.update(fleet.fleet_serving_report(
                        snap.get("per_replica", {})))
                    snap.update(fleet.goodput_gauges())
                    return snap

                stats_server = graftscope.start_stats_server(
                    fleet_snapshot, port=args.router_port,
                    prefix="pmdt_fleet",
                    health_fn=router.healthz,
                    events_fn=graftscope.scope_events_fn)
                print(f"router stats: http://127.0.0.1:"
                      f"{stats_server.server_address[1]}/metrics "
                      f"(+ /healthz)", flush=True)
            try:
                with graftscope.flight_recorder(
                        "serve_lm fleet drive loop"):
                    replay_events = []
                    router.recover(events_out=replay_events)
                    emit(replay_events)
                    while not router.draining:
                        if pending_src[0] is None:
                            try:
                                prompt, max_new = next(source)
                            except StopIteration:
                                break
                            pending_src[0] = (f"src-{src_idx[0]}",
                                              prompt, max_new)
                            src_idx[0] += 1
                        uid, prompt, max_new = pending_src[0]
                        if router.known(uid):
                            pending_src[0] = None
                            continue
                        handled = False
                        while True:
                            try:
                                served.append(router.submit(
                                    prompt, max_new, uid=uid))
                                handled = True
                                break
                            except FleetSaturated:
                                pump()
                            except QueueFull:
                                break  # fleet draining: closed
                            except ValueError as e:
                                rejected[0] += 1
                                print(f"rejected: {e}",
                                      file=sys.stderr)
                                handled = True
                                break
                        if handled:
                            pending_src[0] = None
                        if router.draining:
                            break
                        if args.stdin or scaler is not None:
                            pump()
                    while ((router.in_flight
                            or (rollout is not None
                                and not rollout.done))
                           and not router.draining):
                        pump()
                    emit(router.drain(args.drain_deadline_s or None))
            finally:
                heal.restore_drain_handler(prev_handler)
                if scaler is not None:
                    scaler.shutdown()
                if stats_server is not None:
                    stats_server.shutdown()
            if scaler is not None:
                router.scale_metrics = scaler.metrics()
                if rollout is not None:
                    router.scale_metrics["rollout_duration_s"] = \
                        rollout.duration_s
                    router.scale_metrics["rollout_replaced"] = \
                        rollout.replaced
            return router

        if args.max_restarts:
            router = heal.Supervisor(
                serve_fleet_once, max_restarts=args.max_restarts,
                backoff_s=args.restart_backoff).run()
        else:
            router = serve_fleet_once(0)
        for msg in skipped:
            print(f"rejected: {msg}", file=sys.stderr)
        for request in router.records().values():
            graftscope.emit("request.timeline", cat="request",
                            **request.timeline())
        snap = router.merged_metrics()
        snap.update(getattr(router, "scale_metrics", {}))
        snap["rejected"] = rejected[0] + len(skipped)
        snap.update(fleet.fleet_serving_report(
            snap.get("per_replica", {})))
        snap["fleet_state"] = router.healthz()["state_name"]
        snap.update(fleet.goodput_gauges())
        if remote_mode:
            from pytorch_multiprocessing_distributed_tpu.runtime \
                import wire as graftwire

            snap.update(graftwire.wire_meter())
        snap.update(run_info)
        print("metrics: " + json.dumps(snap, sort_keys=True),
              flush=True)
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump(snap, f, indent=2, sort_keys=True)
        graftscope.export_from_args(args)
        return

    if args.max_restarts:
        engine = heal.Supervisor(
            serve_once, max_restarts=args.max_restarts,
            backoff_s=args.restart_backoff).run()
    else:
        engine = serve_once(0)
    for msg in skipped:
        print(f"rejected: {msg}", file=sys.stderr)
    rejected = rejected[0] + len(skipped)
    # one lifecycle summary event per terminal request: a JSONL
    # consumer reads complete per-request stories (queue wait, TTFT,
    # decode tail, finish reason) without re-deriving them from the
    # raw span stream. By uid, LAST record wins: a restart leaves the
    # crashed incarnation's stale non-terminal Request in `served`
    # and appends the redelivered one — two timelines for one uid
    # would be a contradictory lifecycle
    by_uid = {}
    for request in served:
        by_uid[request.uid] = request
    for request in by_uid.values():
        graftscope.emit("request.timeline", cat="request",
                        **request.timeline())

    snap = engine.metrics.snapshot()
    snap["rejected"] = rejected
    snap["decode_step_compiles"] = engine.decode_step_compiles
    snap["decode_buckets"] = list(engine.decode_buckets)
    snap["decode_windows"] = list(engine.decode_windows)
    snap["decode_horizon"] = engine.decode_horizon
    snap["decode_programs"] = [list(p) for p in engine.decode_programs]
    snap["prefill_compiles"] = engine.prefill_compiles
    snap["chunk_prefill_compiles"] = engine.chunk_prefill_compiles
    if hbm.active_ledger() is not None:
        snap.update(hbm.active_ledger().snapshot())
        snap["hbm_per_slot_bytes"] = engine.pool.per_slot_bytes
    # graftfleet: goodput fraction on the final record too ({} when
    # --stats_port never armed the ledger)
    snap.update(fleet.goodput_gauges())
    snap.update(run_info)
    snap.update(announce_done(compile_log))
    print("metrics: " + json.dumps(snap, sort_keys=True), flush=True)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
    graftscope.export_from_args(args)


if __name__ == "__main__":
    main()
