"""Host environment bootstrap shared by the CLIs.

``PMDT_FORCE_CPU_DEVICES=N`` virtualizes an N-device CPU mesh — the
chip-free way to run every multi-device code path (tests do the same in
conftest.py). Must run before the first backend init: ``XLA_FLAGS`` is
read when the backend comes up, and the platform is pinned through
``jax.config`` because importing this package has already imported jax
(which reads ``JAX_PLATFORMS`` at import).
"""

import json
import os


def force_cpu_devices_from_env() -> None:
    n = os.environ.get("PMDT_FORCE_CPU_DEVICES")
    if not n:
        return
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={int(n)}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def announce_run(cache_dir, **choices) -> dict:
    """Print, once at start-up, where the program runs and what it
    chose from that: the device as jax reports it, the compile cache
    directory, and the caller's platform-derived ``choices`` (attention
    implementation, donation). A run can then be SEEN to have used the
    chip and the kernel — nothing is chosen silently."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "compile_cache_dir": cache_dir, **choices}
    print("[pmdt] run " + json.dumps(info, sort_keys=True), flush=True)
    return info


def device_memory(key: str) -> list:
    """One allocator statistic (``bytes_in_use``, ``peak_bytes_in_use``)
    of every local device — None where the backend keeps none (CPU)."""
    import jax

    return [(d.memory_stats() or {}).get(key)
            for d in jax.local_devices()]


def announce_done(compile_log) -> dict:
    """Print, when the run ends, what it compiled (seconds apart from
    run time, persistent-cache hits) and each local device's peak
    memory — None where the backend keeps no such statistic (CPU)."""
    info = compile_log.summary()
    compile_log.close()
    info["peak_hbm_bytes"] = device_memory("peak_bytes_in_use")
    print("[pmdt] done " + json.dumps(info, sort_keys=True), flush=True)
    return info
