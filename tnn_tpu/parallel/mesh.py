"""Device mesh construction and sharding helpers.

The TPU-native replacement for the reference's device/topology bookkeeping
(DeviceManager, include/device/device_manager.hpp:16; Coordinator topology init,
include/distributed/coordinator.hpp:368-456). On TPU the "topology" is a logical mesh
over chips; parallelism = sharding annotations over named axes, XLA inserts the
collectives that the reference hand-rolls over TCP/RoCE.

Canonical axis names:
  data   — data parallelism (batch sharded, grads all-reduced)
  fsdp   — parameter sharding on top of dp (ZeRO-style; beyond the reference)
  model  — tensor parallelism (Megatron-style; beyond the reference)
  pipe   — pipeline stages (parity with the reference's PP)
  seq    — sequence/context parallelism (ring attention; beyond the reference)
  expert — expert parallelism (MoE dispatch/combine; beyond the reference)
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXES = ("data", "fsdp", "model", "pipe", "seq", "expert")


def make_mesh(data: int = 1, fsdp: int = 1, model: int = 1, pipe: int = 1,
              seq: int = 1, expert: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a logical mesh with the canonical axis order.

    Any axis of size 1 is kept (zero cost, lets sharding specs stay uniform).
    """
    sizes = {"data": data, "fsdp": fsdp, "model": model, "pipe": pipe,
             "seq": seq, "expert": expert}
    devices = list(devices) if devices is not None else jax.devices()
    need = math.prod(sizes.values())
    if need > len(devices):
        raise ValueError(f"mesh needs {need} devices, have {len(devices)}")
    arr = np.array(devices[:need]).reshape(*sizes.values())
    return Mesh(arr, axis_names=AXES)


def data_mesh(n: Optional[int] = None, devices=None) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    n = n or len(devices)
    return make_mesh(data=n, devices=devices)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh: Mesh, extra_axes: Tuple[str, ...] = ()) -> NamedSharding:
    """Shard the leading (batch) dim over the data axis (+any extra non-degenerate axes)."""
    axes = ["data"] + [a for a in extra_axes if a in mesh.axis_names]
    present = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
    if not present:
        return NamedSharding(mesh, PartitionSpec())
    return NamedSharding(mesh, PartitionSpec(present))


def axis_size(mesh: Mesh, name: str) -> int:
    return int(mesh.shape.get(name, 1))


def seq_shard_map(body, mesh: Mesh, axis: str, batch_axis=None):
    """Wrap a per-device (q, k, v) -> out body for context-parallel attention.

    Shared by ring_attention and ulysses_attention so the two schemes stay
    drop-in interchangeable: activations are (B, H, S, D) with S sharded over
    ``axis``; ``batch_axis`` (one name or a tuple, e.g. ("data", "fsdp"))
    additionally shards B so each batch shard runs its own ring/all-to-all
    group — without it, a batch-sharded input would be all-gathered at the
    shard_map boundary. Degenerate (size-1) batch axes are dropped.
    """
    if batch_axis is None:
        ba = None
    else:
        names = (batch_axis,) if isinstance(batch_axis, str) else tuple(batch_axis)
        live = tuple(n for n in names if axis_size(mesh, n) > 1)
        ba = live or None
    spec = PartitionSpec(ba, None, axis, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)


def local_mesh_info() -> Dict[str, int]:
    """Device census (parity: HardwareInfo intent, utils/hardware_info.hpp:126)."""
    devs = jax.devices()
    return {
        "device_count": len(devs),
        "local_device_count": jax.local_device_count(),
        "process_count": jax.process_count(),
        "platform": devs[0].platform if devs else "none",
    }
