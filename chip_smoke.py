#!/usr/bin/env python3
"""chip_smoke.py — the standing check that the program starts on the chip.

Drives the main path once through the entry points a user would call,
at the full width of ``gpt_small`` (124M, bf16): the LM trainer takes a
few steps, the reference-parity ResNet trainer runs an epoch on a small
synthetic set, and the server answers a few requests through each of
its decode kernels. Each phase is ONE child process, one after the
other, so each child is the only process on the chip; this parent never
imports jax (a parent that has touched jax holds the chip, and a child
that needs it then fails or hangs). Children share the persistent
compile cache (``utils/compile_cache.py``: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``<checkout>/.jax_cache``).

    python chip_smoke.py              # one chip: every default phase
    python chip_smoke.py --chips 4    # ONLY data-parallel training on
                                      # four chips and its one-device twin
    python chip_smoke.py --platform cpu [--chips 4]
                                      # rehearsal: gpt_tiny on the CPU

Earlier stdout lines are one JSON object per phase; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it. Any failed phase makes the exit code
non-zero and the last line ``{"ok": false, ...}``. Without an
accelerator (and without ``--platform cpu``) the first phase fails and
nothing else runs.
"""

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PY = sys.executable

# Full width on the chip; the CPU rehearsal cuts the model, never the
# control flow. ``lr``: the CLI's default 0.1 diverges within ten SGD
# steps at gpt_small width (rehearsed on the CPU), 0.01 falls.
REAL = dict(model="gpt_small", dtype="bfloat16", batch=8, seq=1024,
            steps=10, lr=0.01, resnet_batch=512, resnet_samples=2048,
            max_new=32, dp_steps=4)
TINY = dict(model="gpt_tiny", dtype="float32", batch=8, seq=64,
            steps=10, lr=0.01, resnet_batch=64, resnet_samples=128,
            max_new=8, dp_steps=3)

# serve_lm.py variants: between them the paged decode and verify kernels
# and the int8 branch of each run once
SERVE_VARIANTS = {
    "paged": [],
    "paged_spec": ["--draft_k", "4"],
    "paged_int8": ["--kv_dtype", "int8"],
    "paged_int8_spec": ["--kv_dtype", "int8", "--draft_k", "4"],
}

# Greedy streams of a Pallas run against the XLA run of the same
# variant and seed. The two are NOT bit-identical in bf16 (the kernel
# rounds unnormalized probabilities to bf16 before the PV matmul, the
# reference rounds normalized ones), and a random-init model's logits
# are nearly flat, so a near-tie flips now and then and the streams
# part there. A wrong kernel or a wrong page/position plumbing parts
# EVERY stream at its first decoded token (agreement 0: the first
# token comes from prefill and is not counted). The share of DECODED
# tokens before the first divergence must clear this floor; exact
# kernel values are checked by the ``kernels`` phase.
MIN_STREAM_AGREEMENT = 0.1

LOG_ROW = re.compile(rb"\d{4} \d+\.\d{6} \d+\.\d{6}\n")


class PhaseFailed(Exception):
    pass


def need(cond, why):
    if not cond:
        raise PhaseFailed(why)


# ------------------------------------------------------------ children

def run_child(name, argv, out_dir, env=None, timeout=900):
    """Run one child to its end (its own process group, killed whole on
    timeout), logs under ``out_dir``; returns (stdout, seconds)."""
    os.makedirs(out_dir, exist_ok=True)
    full_env = dict(os.environ, PYTHONUNBUFFERED="1", **(env or {}))
    t0 = time.time()
    with open(os.path.join(out_dir, name + ".out"), "wb") as out, \
            open(os.path.join(out_dir, name + ".err"), "wb") as err:
        proc = subprocess.Popen(argv, cwd=HERE, env=full_env, stdout=out,
                                stderr=err, start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            raise PhaseFailed(f"{name}: no end after {timeout}s (killed)")
    seconds = time.time() - t0
    with open(os.path.join(out_dir, name + ".out"), errors="replace") as f:
        stdout = f.read()
    if rc != 0:
        with open(os.path.join(out_dir, name + ".err"),
                  errors="replace") as f:
            tail = f.read()[-1500:]
        raise PhaseFailed(f"{name}: exit code {rc}: {tail}")
    return stdout, seconds


def tagged(stdout, tag):
    """The JSON a CLI printed after ``[pmdt] <tag> ``."""
    lines = [ln for ln in stdout.splitlines()
             if ln.startswith(f"[pmdt] {tag} ")]
    need(lines, f"child printed no '[pmdt] {tag}' line")
    return json.loads(lines[-1].split(" ", 2)[2])


def run_facts(stdout, cfg, count):
    """Where the child ran and what it compiled, checked against what
    this run of the script expects."""
    run, done = tagged(stdout, "run"), tagged(stdout, "done")
    need(run["platform"] == cfg["platform"],
         f"child ran on {run['platform']!r}, not {cfg['platform']!r}")
    need(run["device_count"] == count,
         f"child saw {run['device_count']} devices, not {count}")
    return {**run, "compiles": done["compiles"],
            "compile_s": done["compile_s"],
            "cache_hits": done["cache_hits"],
            "longest_compile": done["longest"][:1],
            "peak_hbm_bytes": done["peak_hbm_bytes"]}


def log_row(out, log):
    """The one epoch row of a ``Logger`` file, held to the reference's
    byte format (``0001 <loss:.6f> <metric:.6f>\\n`` — digits only, so
    a nan or inf fails it too)."""
    with open(os.path.join(out, log), "rb") as f:
        row = f.read()
    need(LOG_ROW.fullmatch(row),
         f"{log} not in the reference byte format: {row!r}")
    return row.decode().split()


def drop_checkpoint(out):
    """The epoch-1 checkpoint was written — then it goes: at full width
    it is ~1 GB, and what stays under ``--out`` travels back from the
    chip machine."""
    path = os.path.join(out, "model_1.pth")
    need(os.path.getsize(path) > 0, "empty checkpoint")
    os.remove(path)


def device_env(cfg, count):
    """Environment that gives a child ``count`` devices: virtual CPU
    devices in the rehearsal; on the chip every device of the host, or
    only the first (the one-device twin of the four-chip run)."""
    if cfg["platform"] == "cpu":
        return {"JAX_PLATFORMS": "cpu", "XLA_FLAGS":
                f"--xla_force_host_platform_device_count={count}"}
    if count == 1 and cfg["chips"] > 1:
        return {"TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1"}
    return {}


# -------------------------------------------------------------- phases

def phase_probe(cfg):
    """Which device jax finds — in a child, so this parent stays off
    jax. Everything else is refused unless it is what was asked for."""
    code = ("import json, jax; d = jax.devices(); print(json.dumps("
            "{'platform': d[0].platform, 'kind': d[0].device_kind, "
            "'count': len(d)}))")
    stdout, _ = run_child("probe", [PY, "-c", code], cfg["out"],
                          env=device_env(cfg, cfg["chips"]), timeout=300)
    device = json.loads(stdout.strip().splitlines()[-1])
    need(device["platform"] == cfg["platform"],
         f"jax found platform {device['platform']!r}, not "
         f"{cfg['platform']!r}: no accelerator, nothing to check")
    need(device["count"] == cfg["chips"],
         f"jax found {device['count']} devices, not {cfg['chips']}")
    cfg["device"] = device
    return {"device": device,
            # runtime/store.py builds csrc/ with these on first use
            "toolchain": {t: shutil.which(t) for t in ("g++", "make")}}


def train_lm(cfg, name, count, batch, steps, extra=()):
    """One ``train_lm.py`` run; returns its facts and per-step losses."""
    out = os.path.join(cfg["out"], name)
    shutil.rmtree(out, ignore_errors=True)
    events = os.path.join(out, "events.jsonl")
    stdout, seconds = run_child(name, [
        PY, "train_lm.py", "--model", cfg["model"], "--dtype",
        cfg["dtype"], "--batch_size", str(batch), "--seq_len",
        str(cfg["seq"]), "--parallel", "dp", "--corpus_tokens",
        str(steps * batch * cfg["seq"]), "--epochs", "1", "--lr",
        str(cfg["lr"]), "--print_freq", "1", "--save_path", out,
        "--events_out", events, *extra], out,
        env=device_env(cfg, count))
    facts = run_facts(stdout, cfg, count)
    losses = [float(x) for x in re.findall(r"\tLoss (\S+)\t", stdout)]
    need(len(losses) == steps, f"{len(losses)} step losses, not {steps}")
    need(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    facts["train.log"] = log_row(out, "train.log")
    drop_checkpoint(out)
    with open(events) as f:
        by_name = {e["name"]: e for e in map(json.loads, f)
                   if e.get("name") in ("train.program", "train.placement")}
    program, placement = by_name["train.program"], by_name["train.placement"]
    need(facts["attn_impl"] == "flash", "the model does not use the kernel")
    if cfg["platform"] == "tpu":
        need(program["tpu_custom_calls"] > 0,
             "no Mosaic kernel in the compiled train step")
    facts.update(seconds=round(seconds, 1), steps=steps, losses=losses,
                 step_program={k: program[k] for k in (
                     "tpu_custom_calls", "all_reduces", "memory")},
                 placement={k: placement[k] for k in (
                     "param_devices", "batch_devices", "bytes_in_use")})
    return facts


def phase_train(cfg):
    facts = train_lm(cfg, "train", 1, cfg["batch"], cfg["steps"])
    losses = facts["losses"]
    need(min(losses[-3:]) < losses[0],
         f"loss does not fall at lr {cfg['lr']}: {losses}")
    return facts


def phase_resnet(cfg):
    """The reference-parity path: ``main.py`` with the reference's flags."""
    out = os.path.join(cfg["out"], "resnet")
    shutil.rmtree(out, ignore_errors=True)
    stdout, seconds = run_child("resnet", [
        PY, "main.py", "--model", "resnet18", "--synthetic",
        "--world_size", "1", "--batch_size", str(cfg["resnet_batch"]),
        "--dtype", cfg["dtype"], "--epochs", "1", "--save_path", out],
        out, env=dict(device_env(cfg, 1),
                      PMDT_SMALL_SYNTH=str(cfg["resnet_samples"])))
    facts = run_facts(stdout, cfg, 1)
    for log in ("train.log", "test.log"):
        facts[log] = log_row(out, log)
    drop_checkpoint(out)
    facts["seconds"] = round(seconds, 1)
    return facts


def serve_lm(cfg, name, flags):
    """One ``serve_lm.py`` run; returns (metrics snapshot, streams, s)."""
    out = os.path.join(cfg["out"], "serve")
    metrics = os.path.join(out, name + ".json")
    if os.path.exists(metrics):
        os.remove(metrics)
    stdout, seconds = run_child(name, [
        PY, "serve_lm.py", "--model", cfg["model"], "--random_init",
        "--dtype", cfg["dtype"], "--synthetic", "8", "--max_slots", "8",
        "--max_new_tokens", str(cfg["max_new"]), "--metrics_out", metrics,
        *flags], out, env=device_env(cfg, 1))
    with open(metrics) as f:
        snap = json.load(f)
    streams = {uid: json.loads(toks) for uid, toks in re.findall(
        r"^req=(\S+) tokens=(\[.*\])$", stdout, re.M)}
    need(snap["platform"] == cfg["platform"] and snap["device_count"] == 1,
         f"{name} ran on {snap['device_count']} x {snap['platform']!r}")
    need(snap["requests_failed"] == 0 and snap["rejected"] == 0
         and len(streams) == 8 == snap["requests_completed"],
         f"{name}: {len(streams)} streams, "
         f"{snap['requests_completed']} completed, "
         f"{snap['requests_failed']} failed, {snap['rejected']} rejected")
    need(all(len(t) == cfg["max_new"] for t in streams.values()),
         f"{name}: a stream is not {cfg['max_new']} tokens long")
    return snap, streams, seconds


def phase_serve(cfg, variant):
    flags = SERVE_VARIANTS[variant]
    # on the chip "auto" must itself resolve to the kernel; the CPU
    # rehearsal asks for it (interpret mode) since auto there is xla
    ask = [] if cfg["platform"] == "tpu" else ["--decode_attn", "pallas"]
    ref, ref_streams, ref_s = serve_lm(
        cfg, variant + "_xla", flags + ["--decode_attn", "xla"])
    snap, streams, seconds = serve_lm(cfg, variant + "_pallas", flags + ask)
    need(ref["decode_attn"] == "xla" and snap["decode_attn"] == "pallas",
         f"decode_attn {snap['decode_attn']!r} (reference "
         f"{ref['decode_attn']!r}): the kernel did not run")
    if cfg["platform"] == "tpu":
        need(snap["prefill_attn"] == "flash" and snap["donate_cache"],
             "prefill kernel or donation not chosen on the chip")
    if "--draft_k" in flags:
        need(snap["spec_verify_passes"] > 0 and ref["spec_verify_passes"] > 0,
             "no speculative verify pass ran")
    agree = total = equal = 0
    for uid, toks in streams.items():
        other = ref_streams[uid]
        need(toks[0] == other[0], f"{uid}: first (prefill) token differs "
             "— not the same weights, prompt or prefill program")
        same = next((i for i, (a, b) in enumerate(zip(toks, other))
                     if a != b), len(toks))
        agree, total, equal = agree + same - 1, total + len(toks) - 1, \
            equal + (same == len(toks))
    need(agree / total >= MIN_STREAM_AGREEMENT,
         f"streams part from the XLA run at once: {agree}/{total} decoded "
         f"tokens agree before the first divergence")
    return {
        "seconds": round(ref_s + seconds, 1),
        "platform": snap["platform"], "device_kind": snap["device_kind"],
        "decode_attn": snap["decode_attn"],
        "prefill_attn": snap["prefill_attn"],
        "compile_cache_dir": snap["compile_cache_dir"],
        "compile_s": [ref["compile_s"], snap["compile_s"]],
        "cache_hits": [ref["cache_hits"], snap["cache_hits"]],
        "tokens_served": snap["tokens_generated"],
        "spec_verify_passes": snap["spec_verify_passes"],
        "streams_equal": f"{equal}/8",
        "agreement": round(agree / total, 3),
        "peak_hbm_bytes": snap["peak_hbm_bytes"]}


def phase_kernels(cfg):
    """Each decode/verify kernel's VALUES against its XLA reference."""
    stdout, seconds = run_child(
        "kernels", [PY, os.path.abspath(__file__), "--child-kernels",
                    "--platform", cfg["platform"]],
        os.path.join(cfg["out"], "kernels"), env=device_env(cfg, 1))
    facts = json.loads(stdout.strip().splitlines()[-1])
    facts["seconds"] = round(seconds, 1)
    return facts


def phase_dp(cfg):
    """Data-parallel training across the four chips against the same
    seed and global batch on one device (grad-accum 4)."""
    batch, steps = 4 * cfg["batch"], cfg["dp_steps"]
    four = train_lm(cfg, "dp4", 4, batch, steps)
    one = train_lm(cfg, "dp1", 1, batch, steps, ["--grad_accum", "4"])
    need(four["placement"]["param_devices"] == 4
         and four["placement"]["batch_devices"] == 4,
         f"state or batch not laid out over 4 devices: {four['placement']}")
    need(four["step_program"]["all_reduces"] > 0,
         "no all-reduce in the compiled four-chip step")
    if cfg["platform"] == "tpu":
        need(all(b and b > 100e6 for b in four["placement"]["bytes_in_use"]),
             f"a device holds no state: {four['placement']['bytes_in_use']}")
    worst = max(abs(a - b) for a, b in zip(four["losses"], one["losses"]))
    need(worst <= 0.02 * max(four["losses"]),
         f"four-chip and one-device losses disagree by {worst}: "
         f"{four['losses']} vs {one['losses']}")
    return {"dp4": four, "dp1": one, "max_loss_diff": round(worst, 5)}


# --------------------------------------------- the kernels child (jax)

def child_kernels(platform):
    """Runs IN A CHILD (imports jax): every decode/verify kernel at
    gpt_small serving shapes, compiled on the chip (interpret mode in
    the CPU rehearsal), against the repo's own XLA reference of the
    same call, to a bf16 tolerance."""
    import functools
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_multiprocessing_distributed_tpu.ops.kv_quant import (
        flatten_heads, quantize_kv)
    da = importlib.import_module(
        "pytorch_multiprocessing_distributed_tpu.ops.pallas"
        ".decode_attention")

    need(jax.devices()[0].platform == platform,
         f"the kernels child runs on {jax.devices()[0].platform!r}")
    b, s, h, d = (8, 1024, 12, 64) if platform == "tpu" else (3, 64, 2, 16)
    rng = np.random.default_rng(0)
    worst = {}
    for k1 in (1, 5):
        q = jnp.asarray(rng.normal(size=(b, k1, h, d)), jnp.bfloat16)
        k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.bfloat16)
                for _ in range(2))
        # a short row, block edges, and (nearly) the whole window
        pos = jnp.asarray(([3, 15, 16, s // 2] + [s - k1] * b)[:b],
                          jnp.int32)
        # the verify pass exists on pages only
        paged = functools.partial(
            da.paged_decode_attention if k1 == 1
            else da.paged_verify_decode_attention, layer=1)
        for page in ((None,) if k1 == 1 else ()) + (
                16, 128 if platform == "tpu" else 32):
            for kv in ("bf16", "int8"):
                if page is None:
                    kk, vv = ((quantize_kv(k), quantize_kv(v))
                              if kv == "int8" else (k, v))
                    got, want = (da.decode_attention(q, kk, vv, pos,
                                                     impl=impl)
                                 for impl in ("pallas", "xla"))
                else:
                    def pages(x):  # slot j's block n is page j*n_win+n+1
                        # of layer 1 of a two-layer [L, P, ps, H * Dh]
                        # pool; the int8 pair keeps [L, P, ps, H] scales
                        x = x.reshape(-1, page, h, d)
                        x = jnp.stack([jnp.zeros_like(x), x])
                        x = jnp.concatenate(
                            [jnp.zeros_like(x[:, :1]), x], axis=1)
                        return flatten_heads(
                            quantize_kv(x) if kv == "int8" else x)
                    kk, vv = pages(k), pages(v)
                    tab = 1 + jnp.arange(b * (s // page),
                                         dtype=jnp.int32).reshape(b, -1)
                    got, want = (paged(q, kk, vv, tab, pos, impl=impl)
                                 for impl in ("pallas", "xla"))
                name = (f"{'verify' if k1 > 1 else 'decode'}-"
                        f"{'dense' if page is None else f'page{page}'}-{kv}")
                got, want = np.asarray(got), np.asarray(want)
                need(got.shape == want.shape == (b, k1, h, d)
                     and np.isfinite(got).all(),
                     f"{name}: wrong shape or not finite")
                worst[name] = float(np.abs(got - want).max())
                np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2,
                                           err_msg=name)
    print(json.dumps({"platform": platform, "shape": [b, s, h, d],
                      "kernels": len(worst),
                      "max_abs_err": max(worst.values()),
                      "worst": max(worst, key=worst.get)}))


# ---------------------------------------------------------------- main

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = ONLY the data-parallel phase and its "
                         "one-device comparison")
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                    help="cpu = rehearse the script at gpt_tiny size "
                         "(a switch of this script, not of the program)")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"))
    ap.add_argument("--child-kernels", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child_kernels:
        return child_kernels(args.platform)

    cfg = dict(TINY if args.platform == "cpu" else REAL,
               platform=args.platform, chips=args.chips, out=args.out)
    phases = [("probe", phase_probe)]
    if args.chips == 4:
        phases += [("dp", phase_dp)]
    else:
        phases += [("train", phase_train), ("resnet", phase_resnet),
                   ("kernels", phase_kernels)]
        phases += [("serve_" + v, lambda c, v=v: phase_serve(c, v))
                   for v in SERVE_VARIANTS]
    failed = []
    for name, phase in phases:
        t0 = time.time()
        try:
            line = {"phase": name, "ok": True, **phase(cfg)}
        except (PhaseFailed, OSError, KeyError, ValueError) as e:
            failed.append(name)
            line = {"phase": name, "ok": False,
                    "error": f"{type(e).__name__}: {e}"}
        line.setdefault("seconds", round(time.time() - t0, 1))
        print(json.dumps(line), flush=True)
        if name == "probe" and failed:
            break  # not the device that was asked for
    if failed:
        print(json.dumps({"ok": False, "failed": failed}))
        return 1
    device = cfg["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
