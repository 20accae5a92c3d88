#!/usr/bin/env python3
"""Measure one cell of BENCHMARK.json.

    python perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files, builds the system, warms up every shape the
cell's traffic reaches (all of it counted as ``setup_s``), measures for
``--seconds``, checks the outputs against the plain reference of the
configuration's family (``perf/families``), and prints ONE JSON object
as the last line of stdout:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
     ..., "checks": {..., "reference", "compared"}}

``checks.compared`` lists every number that decided ``correct`` as
``{what, value, limit}`` (within = ``value <= limit``); the same is
printed as the last lines of stderr.

``--trace 0``: ``metrics`` are the cell's end-to-end metrics.
``--trace 1``: ``metrics`` are its per-layer metrics, read by
``perf/readers.py`` from the run's spans, counters and a short profiler
trace taken after the window; ``device`` gains ``busy_s``/``window_s``,
the line a ``breakdown``, and ``checks.trace`` the device's
``programs``: ``[name, calls, seconds, median seconds of a call]``.

This process is the only one that touches jax. Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""

import time

T_PROCESS = time.time()     # set-up is counted from here

import argparse             # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import statistics           # noqa: E402
import sys                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def measure(workload: str, seed: int, seconds: float, trace: bool,
            *, cell=None, allow_cpu: bool = False) -> dict:
    """Run one cell and return the line as a dict. ``cell`` and
    ``allow_cpu`` are the rehearsal's entry (``perf/tests``): a cell
    built at a tiny size may run on the CPU, and what comes back then
    carries no metric at all."""
    from perf import harness, readers
    from perf.spans import Recording

    cell = cell or harness.load_cell(workload)
    rec = Recording()
    result = harness.load_driver(cell.kind).run(
        cell, seed=seed, seconds=seconds, trace=trace,
        t_process=T_PROCESS, rec=rec, allow_cpu=allow_cpu)
    devices = result["devices"]
    on_chip = devices[0].platform == "tpu"
    values = dict(result["end_to_end"])
    specs = cell.end_to_end
    if trace:
        specs = cell.per_layer
        values = {m["name"]: readers.read(cell.layer_files[m["name"]], rec)
                  for m in specs}
    line = {
        "correct": result["correct"],
        "attempted": result["attempted"],
        "failed": result["failed"],
        # a number from a CPU run is never written under a metric's name
        "metrics": {m["name"]: {"value": values[m["name"]],
                                "unit": m["unit"]}
                    for m in specs
                    if on_chip and values.get(m["name"]) is not None},
        "device": harness.device_report(
            devices, result["program_peak_bytes"],
            result.get("allocator_peak_bytes")),
        "workload": cell.name, "seed": seed, "seconds": seconds,
    }
    checks = dict(result["checks"])
    if trace and "busy_s" in rec.trace:
        line["device"]["busy_s"] = rec.trace["busy_s"]
        line["device"]["window_s"] = rec.trace["window_s"]
        line["breakdown"] = {"device_ops": rec.trace["device_ops"],
                             "idle_gaps": rec.trace["idle_gaps"]}
        checks["trace"] = {
            k: rec.trace[k] for k in ("steps", "planes", "collective_s",
                                      "collective_exposed_s")}
        # the device's programs, not under ``breakdown``, whose two
        # lists the ledger copies as they are
        checks["trace"]["programs"] = [
            [name, p["calls"], p["seconds"], statistics.median(p["call_s"])]
            for name, p in rec.trace["programs"].items()]
    if not on_chip:
        line["rehearsal"] = {"end_to_end": result["end_to_end"],
                             "per_layer": values if trace else None}
    # what was compared comes last, in the line and inside ``checks``:
    # a record that keeps only the end of a line keeps this
    checks["compared"] = checks.pop("compared")
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--keep-trace", default="",
                    help="copy the raw .xplane.pb into this directory")
    args = ap.parse_args(argv)

    from perf import harness, trace_reduce

    trace_reduce.KEEP_DIR = args.keep_trace or None
    try:
        line = measure(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except harness.NoAccelerator as e:
        print(f"perf/run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    # and as the last lines of standard error, each beside its limit
    print(f"perf/run.py: correct={line['correct']} against "
          f"{line['checks']['reference']}", file=sys.stderr)
    for c in line["checks"]["compared"]:
        print(f"  {c['what']} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
