"""Per-op TPU time breakdown for a bench config via jax.profiler.

Answers "where does the step time actually go" (the question behind the
resnet50 MFU gap: 0.29 vs 0.46+ for resnet18/152 on the same chip,
``benchmarks/baseline_record.json``). Traces a few steady-state steps
of the EXACT program ``bench.py`` times, then parses the raw
``*.xplane.pb`` with the tensorflow-bundled proto (no tensorboard UI in
this environment) and aggregates device-plane event durations by op
name and by HLO category.

Run (on chip):  python benchmarks/profile_step.py --config resnet50_imagenet
Artifacts:      benchmarks/profile_<config>.json  (top ops + categories)
"""

import argparse
import collections
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import benchmarks._common as _common  # noqa: E402  (platform guard)


def parse_xplanes(trace_dir):
    """-> [(plane_name, line_name, event_name, hlo_category,
    total_ps, count), ...] aggregated per (plane, line, op)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    rows = []
    for path in paths:
        space = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        for plane in space.planes:
            meta = {m_id: m.name for m_id, m in plane.event_metadata.items()}

            def stat_value(s, plane=plane):
                # category strings arrive inline (str_value) or as a
                # reference into the plane's stat_metadata string table
                if s.str_value:
                    return s.str_value
                if s.ref_value:
                    return plane.stat_metadata[s.ref_value].name
                return None

            cat = {
                m_id: next(
                    (
                        stat_value(s)
                        for s in m.stats
                        if plane.stat_metadata[s.metadata_id].name
                        == "hlo_category"
                    ),
                    None,
                )
                for m_id, m in plane.event_metadata.items()
            }
            for line in plane.lines:
                agg = collections.defaultdict(lambda: [0, 0])
                for ev in line.events:
                    a = agg[ev.metadata_id]
                    a[0] += ev.duration_ps
                    a[1] += 1
                for m_id, (ps, n) in agg.items():
                    rows.append(
                        (
                            plane.name,
                            line.name,
                            meta.get(m_id, str(m_id)),
                            cat.get(m_id),
                            ps,
                            n,
                        )
                    )
    return rows


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="resnet50_imagenet")
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--batch_size", type=int, default=0)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--remat", action="store_true")
    p.add_argument("--top", type=int, default=30)
    p.add_argument("--trace_dir", default="")
    args = p.parse_args()
    _common.enable_compile_cache()

    import tempfile

    import jax

    import bench

    devices, note = bench.init_devices()
    if devices[0].platform != "tpu":
        print(json.dumps({"error": f"no TPU ({note}); profiling needs "
                                   "the real chip"}))
        return 1

    from pytorch_multiprocessing_distributed_tpu.utils.profiler import sync

    # the EXACT program bench.py times — one shared builder, no drift
    step, state, batch_args, _, batch = bench.build_workload(
        args.config, args.dtype, args.batch_size, devices,
        remat=args.remat,
    )
    step, costs = bench.compile_step(step, state, *batch_args)
    for _ in range(3):  # steady state before the trace
        state, m = step(state, *batch_args)
    sync(m)

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="pmdt_trace_")
    with jax.profiler.trace(trace_dir):
        for _ in range(args.steps):
            state, m = step(state, *batch_args)
        sync(m)

    rows = parse_xplanes(trace_dir)
    # Device planes only; the busiest line is the op timeline.
    dev_rows = [r for r in rows if "TPU" in r[0] or "tpu" in r[0].lower()]
    if not dev_rows:
        dev_rows = rows
    by_line = collections.defaultdict(int)
    for _, line, _, _, ps, _ in dev_rows:
        by_line[line] += ps
    op_line = max(by_line, key=by_line.get)
    ops = [r for r in dev_rows if r[1] == op_line]
    total_ps = sum(r[4] for r in ops)
    ops.sort(key=lambda r: -r[4])
    cats = collections.defaultdict(int)
    for r in ops:
        cats[r[3] or "uncategorized"] += r[4]

    def fmt(r):
        _, _, name, c, ps, n = r
        return {
            "op": name[:120],
            "category": c,
            "ms_total": round(ps / 1e9, 3),
            "ms_per_step": round(ps / 1e9 / args.steps, 3),
            "pct": round(100 * ps / total_ps, 2),
            "count": n,
        }

    out = {
        "config": args.config,
        "global_batch": batch,
        "dtype": args.dtype,
        "remat": args.remat,
        "steps_traced": args.steps,
        "device_plane_line": op_line,
        "device_ms_per_step": round(total_ps / 1e9 / args.steps, 3),
        "flops_per_step": (costs or {}).get("flops"),
        "categories_pct": {
            k: round(100 * v / total_ps, 2)
            for k, v in sorted(cats.items(), key=lambda kv: -kv[1])
        },
        "top_ops": [fmt(r) for r in ops[: args.top]],
        "trace_dir": trace_dir,
    }
    rec = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       f"profile_{args.config}.json")
    with open(rec, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({k: out[k] for k in
                      ("config", "device_ms_per_step", "categories_pct")}))
    print(f"# full breakdown -> {rec}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
