#!/usr/bin/env python
"""Print the host + accelerator inventory (parity: the reference's
hardware_info_example / device_manager_example executables).

    python -m tnn_tpu.cli.hardware_info [--json]
"""
import argparse
import json


from tnn_tpu.utils import affinity
from tnn_tpu.utils.hardware import (cpu_topology, device_info,
                                    hbm_stats, memory_usage_kb)


from tnn_tpu.cli import console_entry


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    info = {
        "cpu": cpu_topology(),
        "io_cpu_set": affinity.io_cpu_set(),
        "process_rss_kb": memory_usage_kb(),
        "devices": device_info(),
    }
    for d in info["devices"]:
        stats = hbm_stats()
        if stats:
            d["hbm"] = stats
        break  # one probe is enough for the summary
    if args.json:
        print(json.dumps(info, indent=2))
        return info
    cpu = info["cpu"]
    print(f"CPU: {cpu.get('model', '?')} — {cpu['logical_cores']} logical"
          + (f" / {cpu['physical_cores']} physical" if "physical_cores" in cpu
             else ""))
    print(f"  P-cores: {cpu['p_cores']}  E-cores: {cpu['e_cores']}  "
          f"IO cpu set: {info['io_cpu_set']}")
    for c in cpu.get("caches", []):
        print(f"  L{c.get('level', '?')} {c.get('type', ''):12s} "
              f"{c.get('size', '?')}")
    if "freq_khz" in cpu:
        f = cpu["freq_khz"]
        print(f"  freq: {f['min'] / 1e3:.0f}-{f['max'] / 1e3:.0f} MHz")
    if "mem_total_kb" in cpu:
        print(f"  RAM: {cpu['mem_total_kb'] / 1048576:.1f} GiB "
              f"(process RSS {info['process_rss_kb'] / 1024:.0f} MiB)")
    for d in info["devices"]:
        line = f"device {d['id']}: {d['platform']} ({d['kind']})"
        if "hbm" in d:
            h = d["hbm"]
            line += (f" — HBM {h.get('bytes_in_use', 0) / 1e9:.2f}"
                     f"/{h.get('bytes_limit', 0) / 1e9:.1f} GB")
        print(line)
    return info


cli = console_entry(main)


if __name__ == "__main__":
    main()
