"""What every cell shares: the manifest and its files, the device, the
model, and the line a run prints.

Everything that belongs to ONE configuration, traffic mix, cell or
per-layer metric is a data file found by its name in BENCHMARK.json:

    perf/configs/<config>.json          sizes, source, departures
    perf/traffic/<traffic>.json         the mix's parameters
    perf/workloads/<cell>.json          config + traffic + chips + every
                                        trainer or engine option
    perf/layer_metrics/<metric>.json    what the metric reads and how
                                        it is reduced

so a later PR adds a cell, a configuration or a metric by adding files
and one entry, and edits nothing that is here.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
CELL_KEYS = {"name", "config", "traffic", "kind", "chips", "options",
             "sizing", "why"}
METRIC_FILE_KEYS = {"name", "layer", "unit", "better", "source", "moves",
                    "reads", "reducer", "args", "scale", "what"}


class ManifestError(ValueError):
    pass


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def data_path(kind: str, name: str) -> str:
    if not NAME_RE.match(name):
        raise ManifestError(f"{kind} name {name!r} is not a valid name")
    return os.path.join(PERF_DIR, kind, name + ".json")


@dataclass
class Cell:
    """One entry of ``workloads`` with every file it names, resolved."""
    name: str
    chips: int
    kind: str
    config: dict
    traffic: dict
    options: dict
    sizing: dict
    end_to_end: List[dict]
    per_layer: List[dict]           # manifest entries for this cell
    layer_files: Dict[str, dict] = field(default_factory=dict)


def _for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, manifest: Optional[dict] = None) -> Cell:
    manifest = manifest or load_manifest()
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise ManifestError(f"no workload {name!r} (known: {known})")
    entry = entries[0]
    spec = _load(data_path("workloads", name))
    unknown = set(spec) - CELL_KEYS
    if unknown:
        raise ManifestError(
            f"workloads/{name}.json has unknown keys {sorted(unknown)}")
    for key in ("config", "traffic", "chips"):
        if spec[key] != entry[key]:
            raise ManifestError(
                f"workloads/{name}.json says {key}={spec[key]!r}, "
                f"BENCHMARK.json says {entry[key]!r}")
    configs = {c["name"]: c for c in manifest["configs"]}
    config = _load(os.path.join(ROOT, configs[entry["config"]]["file"]))
    traffic = _load(data_path("traffic", entry["traffic"]))
    if traffic["kind"] != spec["kind"]:
        raise ManifestError(
            f"cell {name} is {spec['kind']!r} but its traffic "
            f"{entry['traffic']} is {traffic['kind']!r}")
    per_layer = [m for m in manifest["per_layer"] if _for_cell(m, name)]
    return Cell(
        name=name, chips=int(entry["chips"]), kind=spec["kind"],
        config=config, traffic=traffic, options=spec["options"],
        sizing=spec.get("sizing", {}),
        end_to_end=[m for m in manifest["end_to_end"]
                    if _for_cell(m, name)],
        per_layer=per_layer,
        layer_files={m["name"]: load_layer_metric(m["name"])
                     for m in per_layer})


def load_layer_metric(name: str) -> dict:
    spec = _load(data_path("layer_metrics", name))
    unknown = set(spec) - METRIC_FILE_KEYS
    if unknown:
        raise ManifestError(
            f"layer_metrics/{name}.json has unknown keys "
            f"{sorted(unknown)}")
    return spec


def take_options(options: dict, allowed: dict, where: str) -> dict:
    """``allowed`` (name -> default) overlaid with ``options``; a key
    that is not allowed is an error, never ignored."""
    unknown = set(options) - set(allowed)
    if unknown:
        raise ManifestError(f"{where}: unknown options {sorted(unknown)}")
    return {**allowed, **options}


# ------------------------------------------------------------- device

class NoAccelerator(RuntimeError):
    pass


def require_devices(chips: int, allow_cpu: bool = False):
    """The ``chips`` devices this cell runs on, or NoAccelerator: a
    cell is measured on a TPU or not at all."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise NoAccelerator(
            f"jax found platform {platform!r}: no accelerator, no number")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} chips, jax found {len(devices)}")
    return devices[:chips]


def load_peaks(device_kind: str) -> dict:
    peaks = _load(os.path.join(PERF_DIR, "peaks.json"))
    if device_kind not in peaks or device_kind.startswith("_"):
        raise ManifestError(
            f"device kind {device_kind!r} is not in perf/peaks.json; "
            "add it with its source, a default would be a guess")
    return peaks[device_kind]


def device_report(devices, program_peak_bytes: int = 0) -> dict:
    """The ``device`` key of the last line. ``memory_peak_bytes`` is
    the fullest chip's peak: the allocator's high-water mark, or — the
    allocator does not see a running program's temporaries (PERF.md
    section 5) — the largest compiled program's own memory analysis
    (arguments + outputs + temporaries + code - aliased), whichever is
    larger."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
             for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(max(peaks + [program_peak_bytes]))}


# -------------------------------------------------------------- model

def prng_key(seed: int):
    """A jax PRNG key for any whole-number ``--seed`` (the driver's
    pass 2**31, which a 32-bit key seed does not hold)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def build_model(config: dict, dtype_name: str, **extra):
    """The registry model this configuration names, held to the sizes
    in its file: a registry entry that drifted from the published
    widths fails here, not in a footnote."""
    import jax.numpy as jnp

    from pytorch_multiprocessing_distributed_tpu import models

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype_name]
    model = models.get_model(config["registry_name"], dtype=dtype,
                             **config.get("model_kwargs", {}), **extra)
    want = {"vocab_size": config["vocab_size"],
            "max_seq_len": config["n_positions"],
            "hidden_size": config["n_embd"],
            "num_layers": config["n_layer"],
            "num_heads": config["n_head"],
            "mlp_dim": config.get("n_inner") or 4 * config["n_embd"]}
    got = {k: getattr(model, k) for k in want}
    if got != want:
        raise ManifestError(
            f"registry model {config['registry_name']!r} is {got}, the "
            f"configuration file says {want}")
    return model


def program_peak_bytes(compiled) -> int:
    """A compiled program's resident high-water mark by XLA's own
    memory analysis (0 where the backend has none)."""
    stats = compiled.memory_analysis()
    try:
        return int(stats.argument_size_in_bytes
                   + stats.output_size_in_bytes
                   + stats.temp_size_in_bytes
                   + stats.generated_code_size_in_bytes
                   - stats.alias_size_in_bytes)
    except AttributeError:
        return 0


# ----------------------------------------------------- manifest check

def check_manifest(manifest: Optional[dict] = None) -> List[str]:
    """Everything wrong with BENCHMARK.json and the files it names, as
    sentences (empty = sound). Run by ``perf/tests``; the contract's
    own limits (names, units, lengths, the four-chip cap) and this
    harness's (every named file exists, metric files agree with their
    entries, each ``moves`` is reported wherever its metric is)."""
    manifest = manifest or load_manifest()
    bad: List[str] = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != want:
        bad.append(f"top-level keys are {sorted(manifest)}")
        return bad

    def name_ok(value, what):
        if not isinstance(value, str) or not NAME_RE.match(value):
            bad.append(f"{what} {value!r} is not a valid name")

    def line_ok(value, what):
        if (not isinstance(value, str) or not 1 <= len(value) <= 200
                or "\n" in value or "\t" in value):
            bad.append(f"{what} must be 1-200 characters on one line")

    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    if not 1 <= manifest["run_seconds"] <= 51:
        bad.append("run_seconds outside 1..51")
    for c in manifest["configs"]:
        name_ok(c["name"], "config")
        line_ok(c["source"], f"{c['name']}.source")
        line_ok(c["why"], f"{c['name']}.why")
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c['name']} has keys {sorted(c)}")
        if not c["file"].startswith(tuple(p + "/" for p in
                                          manifest["paths"])):
            bad.append(f"{c['file']} is not under paths")
        elif not os.path.exists(os.path.join(ROOT, c["file"])):
            bad.append(f"{c['file']} does not exist")
        else:
            held = _load(os.path.join(ROOT, c["file"]))
            if held.get("reduced") != c["reduced"]:
                bad.append(f"{c['file']}: reduced differs from manifest")
            if held.get("source") != c["source"]:
                bad.append(f"{c['file']}: source differs from manifest")
    seen_pairs = set()
    for w in manifest["workloads"]:
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], "traffic")
        line_ok(w["why"], f"{w['name']}.why")
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w['name']} has keys {sorted(w)}")
        if w["config"] not in configs:
            bad.append(f"{w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']}: chips must be 1 or 4")
        if (w["config"], w["traffic"]) in seen_pairs:
            bad.append(f"{w['name']}: config x traffic appears twice")
        seen_pairs.add((w["config"], w["traffic"]))
        for kind, name in (("workloads", w["name"]),
                           ("traffic", w["traffic"])):
            if not os.path.exists(data_path(kind, name)):
                bad.append(f"perf/{kind}/{name}.json does not exist")
    used = {w["config"] for w in manifest["workloads"]}
    bad += [f"config {c} is used by no cell" for c in configs
            if c not in used]
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} of {len(cells)} cells ask for four chips")

    def reported_in(metric):
        return set(metric.get("workloads", cells))

    names = set()
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        name_ok(m["name"], "metric")
        if m["name"] in names:
            bad.append(f"metric {m['name']} appears twice")
        names.add(m["name"])
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source {m['source']!r}")
        for cell in m.get("workloads", []):
            if cell not in cells:
                bad.append(f"{m['name']}: unknown workload {cell}")
    for m in manifest["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound",
                                      "source"}:
            bad.append(f"end_to_end {m['name']} has keys {sorted(m)}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: an end-to-end metric is taken by "
                       "the benchmark itself")
        if not 0.01 <= m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
    if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
        bad.append("setup_s must be an end-to-end metric of every cell")
    for m in manifest["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source",
                                      "layer", "moves"}:
            bad.append(f"per_layer {m['name']} has keys {sorted(m)}")
        line_ok(m["layer"], f"{m['name']}.layer")
        target = e2e.get(m["moves"])
        if target is None:
            bad.append(f"{m['name']} moves {m['moves']!r}, which is no "
                       "end-to-end metric")
        elif not reported_in(m) <= reported_in(target):
            bad.append(f"{m['name']} is reported in cells where "
                       f"{m['moves']} is not")
        path = data_path("layer_metrics", m["name"])
        if not os.path.exists(path):
            bad.append(f"perf/layer_metrics/{m['name']}.json does not "
                       "exist")
            continue
        held = load_layer_metric(m["name"])
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            if held.get(key) != m[key]:
                bad.append(f"layer_metrics/{m['name']}.json: {key} "
                           "differs from the manifest")
    for cell in cells:
        mine_e2e = [m for m in manifest["end_to_end"]
                    if cell in reported_in(m)]
        mine_layer = [m for m in manifest["per_layer"]
                      if cell in reported_in(m)]
        if len(mine_e2e) < 2 or not mine_layer:
            bad.append(f"{cell} reports too few metrics")
    if len(json.dumps(manifest)) > 64 * 1024:
        bad.append("BENCHMARK.json is over 64 KiB")
    return bad
