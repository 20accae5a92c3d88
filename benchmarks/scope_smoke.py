"""graftscope smoke: a synthetic engine run must emit every exporter's
artifact, and every artifact must PARSE.

The ``make scope`` target (and the tier-1 test that drives this module
in-process) runs a short synthetic serving workload with a full-log
scope armed, then asserts the whole observability surface end-to-end:

1. Chrome-trace JSON — loads as the Perfetto/chrome://tracing schema
   (required keys per event, microsecond timestamps from 0);
2. JSONL event log — every line parses; the per-request lifecycles are
   COMPLETE (each served uid has submit → admit → first_token → done,
   and a terminal ``request.timeline`` summary);
3. Prometheus text exposition — the same text ``serve_lm.py
   --stats_port`` serves at ``/metrics``; every sample line parses and
   the p50/p95/p99 TTFT gauges are present;
4. the stats endpoint itself — one live scrape of ``/metrics`` +
   ``/snapshot.json`` over stdlib ``http.server``.

Exit code 0 and a one-line ``graftscope smoke OK`` = the observability
stack is wired. Any schema drift fails loudly here, before a real
incident needs the artifacts.

Run: ``python benchmarks/scope_smoke.py [--out_dir DIR]``
(CPU-safe: gpt_tiny, a handful of requests, seconds of work).
"""

import argparse
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import benchmarks._common as _common  # noqa: E402


def run(out_dir: str) -> dict:
    """The smoke body; returns the parsed artifacts for the caller
    (the tier-1 test asserts on them in-process)."""
    import numpy as np

    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.runtime import (
        scope as graftscope)
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ServingEngine, init_params)

    os.makedirs(out_dir, exist_ok=True)
    trace_path = os.path.join(out_dir, "scope_trace.json")
    events_path = os.path.join(out_dir, "scope_events.jsonl")
    prom_path = os.path.join(out_dir, "scope_metrics.prom")

    model = models.get_model("gpt_tiny", attn_impl="xla")
    params = init_params(model, 0)
    engine = ServingEngine(model, params, max_slots=2, s_max=32,
                           min_bucket=8, decode_horizon=2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size,
                            (int(rng.integers(3, 12)),)).tolist()
               for _ in range(4)]

    scope = graftscope.arm(graftscope.Scope(
        keep=True, flight_path=os.path.join(out_dir, "flight.jsonl")))
    try:
        served = engine.serve([(p, 5) for p in prompts])
        for request in served:
            graftscope.emit("request.timeline", cat="request",
                            **request.timeline())
        snap = engine.metrics.snapshot()
        events = scope.events()
        graftscope.write_chrome_trace(trace_path, events, t0=scope.t0)
        graftscope.write_jsonl(events_path, events)
        with open(prom_path, "w") as fh:
            fh.write(graftscope.prometheus_text(snap))

        # live endpoint: one scrape of both routes
        server = graftscope.start_stats_server(engine.metrics.snapshot,
                                               port=0)
        try:
            port = server.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics") as resp:
                live_prom = resp.read().decode()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/snapshot.json") as resp:
                live_snap = json.loads(resp.read())
        finally:
            server.shutdown()
    finally:
        graftscope.disarm()

    # ---- assert: Chrome-trace schema
    trace = json.load(open(trace_path))
    assert trace["traceEvents"], "empty trace"
    for ev in trace["traceEvents"]:
        missing = {"name", "ph", "ts", "pid", "tid"} - set(ev)
        assert not missing, f"trace event missing {missing}: {ev}"
        assert ev["ts"] >= 0.0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0

    # ---- assert: JSONL lifecycles are complete
    log = graftscope.events_from_jsonl(events_path)
    assert len(log) == len(events)
    uids = {e["uid"] for e in log if e["name"] == "request.timeline"}
    assert len(uids) == len(prompts), "a request has no timeline record"
    for name in ("request.submit", "request.admit",
                 "request.first_token", "request.done"):
        reached = {e["req"] for e in log if e["name"] == name}
        assert reached == uids, (
            f"lifecycle incomplete: {name} missing for "
            f"{uids - reached}")

    # ---- assert: Prometheus exposition parses, tails present
    def parse_prom(text):
        samples = {}
        for line in text.splitlines():
            if not line or line.startswith("#"):
                if line.startswith("#"):
                    assert line.startswith("# TYPE "), line
                continue
            name, value = line.rsplit(" ", 1)
            samples[name] = float(value)  # every sample line parses
        return samples

    samples = parse_prom(open(prom_path).read())
    for q in ("p50", "p95", "p99"):
        assert f"pmdt_serving_ttft_{q}_s" in samples, q
    assert samples["pmdt_serving_requests_completed"] == len(prompts)
    live = parse_prom(live_prom)
    assert live["pmdt_serving_requests_completed"] == len(prompts)
    assert live_snap["requests_completed"] == len(prompts)

    return {"trace": trace, "log": log, "samples": samples,
            "snapshot": snap}


def main(argv=None):
    _common.enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--out_dir", default="/tmp/pmdt_scope_smoke",
                   help="artifact directory (trace/jsonl/prom)")
    args = p.parse_args(argv)
    out = run(args.out_dir)
    print(f"# {len(out['log'])} events, "
          f"ttft_p99_s={out['snapshot']['ttft_p99_s']:.4f}, "
          f"artifacts in {args.out_dir}")
    print("graftscope smoke OK")


if __name__ == "__main__":
    main()
