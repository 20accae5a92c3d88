"""MFU tuning sweep: bench configs x XLA flag sets x batch sizes.

resnet50_imagenet sits at mfu 0.29 while resnet18/vit prove 0.46+ is
reachable on the same chip (``benchmarks/baseline_record.json``) — close
the gap with scheduler/fusion flags and batch geometry. Each combo runs
``bench.py`` in a FRESH subprocess (XLA flags only apply at backend
init), results are ranked by MFU and written to
``benchmarks/mfu_tune_results.json``. Flag sets that crash or regress
are recorded, not fatal.

Run (on chip): ``python benchmarks/mfu_tune.py --config resnet50_imagenet``
"""

import argparse
import itertools
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "mfu_tune_results.json")

# Public XLA:TPU knobs worth sweeping for dense conv workloads. Applied
# ON TOP of whatever XLA_FLAGS the environment already carries.
FLAG_SETS = {
    "baseline": "",
    "lhs": "--xla_tpu_enable_latency_hiding_scheduler=true",
    "lhs+aggr": ("--xla_tpu_enable_latency_hiding_scheduler=true "
                 "--xla_tpu_aggressive_opt_barrier_removal=ENABLED"),
    "flash_fusion": "--xla_tpu_enable_flash_attention=true",
    "bf16_sum": "--xla_tpu_rwb_fusion=false",
}


def run_one(config, flags, batch, timeout):
    env = dict(os.environ)
    base = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = f"{base} {flags}".strip()
    # one process per chip: this parent never imports jax, so each
    # bench.py child is the only process on the chip
    cmd = [sys.executable, os.path.join(REPO, "bench.py"),
           "--config", config]
    if batch:
        cmd += ["--batch_size", str(batch)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, cwd=REPO, env=env)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout {timeout}s"}
    lines = (proc.stdout or "").strip().splitlines()
    try:
        return json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        return {"error": f"no JSON (rc={proc.returncode}): "
                         f"{(proc.stderr or '')[-300:]}"}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="resnet50_imagenet")
    p.add_argument("--batches", default="0,128,512", type=str,
                   help="0 = config default")
    p.add_argument("--flag_sets", default=",".join(FLAG_SETS), type=str)
    p.add_argument("--timeout", default=1200, type=int)
    args = p.parse_args()

    combos = list(itertools.product(
        [b for b in (int(x) for x in args.batches.split(","))],
        [f for f in args.flag_sets.split(",") if f in FLAG_SETS],
    ))
    results = []
    for batch, name in combos:
        r = run_one(args.config, FLAG_SETS[name], batch, args.timeout)
        row = {
            "flag_set": name,
            "flags": FLAG_SETS[name],
            "batch": batch or "default",
            "value": r.get("value"),
            "mfu": r.get("mfu"),
            "platform": r.get("extra", {}).get("platform"),
            "error": r.get("error"),
        }
        results.append(row)
        print(json.dumps(row), flush=True)

    ranked = sorted(
        (r for r in results if r.get("mfu")),
        key=lambda r: -r["mfu"],
    )
    out = {"config": args.config, "results": results,
           "best": ranked[0] if ranked else None}
    with open(RESULTS, "w") as f:
        json.dump(out, f, indent=2)
    if ranked:
        print(f"# best: {json.dumps(ranked[0])}", file=sys.stderr)


if __name__ == "__main__":
    main()
