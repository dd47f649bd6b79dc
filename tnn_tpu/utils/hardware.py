"""Hardware introspection: host memory, accelerator inventory, HBM stats.

Parity: ``HardwareInfo`` CPU topology (include/utils/hardware_info.hpp:126) and
``get_memory_usage_kb`` RSS query (include/utils/memory.hpp, used src/nn/train.cpp:269).
On TPU the interesting inventory is the device list + per-device HBM, which PJRT
exposes via ``device.memory_stats()``.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List


def memory_usage_kb() -> int:
    """Current process RSS in KiB (parity: get_memory_usage_kb)."""
    try:
        with open("/proc/self/status", "r") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return 0


def cpu_info() -> Dict[str, Any]:
    """Host CPU summary (capability parity with HardwareInfo's topology report)."""
    info: Dict[str, Any] = {"logical_cores": os.cpu_count() or 0}
    try:
        with open("/proc/cpuinfo", "r") as f:
            for line in f:
                if line.startswith("model name"):
                    info["model"] = line.partition(":")[2].strip()
                    break
    except OSError:
        pass
    return info


def cpu_topology() -> Dict[str, Any]:
    """Deep host topology (parity: HardwareInfo, hardware_info.hpp:13-168 —
    sockets/cores/threads, P/E core census, cache hierarchy, frequency range).
    Reads /proc + sysfs; missing files simply omit their fields."""
    from . import affinity

    sys_cpu = "/sys/devices/system/cpu"
    cpus = affinity.available_cpus()
    topo: Dict[str, Any] = dict(cpu_info())
    packages, cores = set(), set()
    for c in cpus:
        base = f"{sys_cpu}/cpu{c}/topology"
        pkg = affinity._read_int(f"{base}/physical_package_id")
        core = affinity._read_int(f"{base}/core_id")
        if pkg is not None:
            packages.add(pkg)
        if pkg is not None and core is not None:
            cores.add((pkg, core))
    if packages:
        topo["sockets"] = len(packages)
    if cores:
        topo["physical_cores"] = len(cores)
        topo["threads_per_core"] = round(len(cpus) / len(cores), 2)
    types = affinity.core_types()
    topo["p_cores"] = sum(1 for t in types.values() if t == "P")
    topo["e_cores"] = sum(1 for t in types.values() if t == "E")
    # cache hierarchy of cpu0 (uniform on every machine we care about)
    caches = []
    idx = 0
    while True:
        base = f"{sys_cpu}/cpu{cpus[0] if cpus else 0}/cache/index{idx}"
        if not os.path.isdir(base):
            break
        entry = {}
        for key in ("level", "type", "size"):
            try:
                with open(os.path.join(base, key)) as f:
                    entry[key] = f.read().strip()
            except OSError:
                pass
        if entry:
            caches.append(entry)
        idx += 1
    if caches:
        topo["caches"] = caches
    fmin = affinity._read_int(f"{sys_cpu}/cpu0/cpufreq/cpuinfo_min_freq")
    fmax = affinity._read_int(f"{sys_cpu}/cpu0/cpufreq/cpuinfo_max_freq")
    if fmax:
        topo["freq_khz"] = {"min": fmin or 0, "max": fmax}
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    topo["mem_total_kb"] = int(line.split()[1])
                    break
    except OSError:
        pass
    return topo


def device_info() -> List[Dict[str, Any]]:
    """Accelerator inventory (parity: DeviceManager discovery,
    include/device/device_manager.hpp:16)."""
    import jax

    out = []
    for d in jax.devices():
        out.append({
            "id": d.id,
            "platform": d.platform,
            "kind": getattr(d, "device_kind", "unknown"),
            "process_index": d.process_index,
        })
    return out


def hbm_stats(device=None) -> Dict[str, int]:
    """Per-device HBM usage in bytes; empty on a backend that reports none
    (the CPU backend's ``memory_stats()`` is None). Errors propagate."""
    import jax

    d = device or jax.devices()[0]
    stats = d.memory_stats() or {}
    return {k: int(v) for k, v in stats.items()
            if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def device_line() -> str:
    """What this process actually runs on, for an entry point's one startup
    line: platform, device kind and count as JAX reports them, and whether
    the Pallas kernels will interpret instead of compiling (they do on every
    backend but ``tpu`` — a run that silently landed on the CPU says so)."""
    import jax

    from ..ops.pallas.runtime import interpret_default

    devs = jax.devices()
    return (f"platform={devs[0].platform} device_kind={devs[0].device_kind!r} "
            f"devices={len(devs)} pallas_interpret={interpret_default()}")
