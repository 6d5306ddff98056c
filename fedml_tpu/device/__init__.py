"""Device layer: ``fedml_tpu.device.get_device(args)``.

Parity: reference ``python/fedml/device/`` — ``get_device(args):6`` branches
on training_type/backend; MPI mode reads a YAML ``gpu_mapping_file`` mapping
hosts x GPU slots -> process ranks (``gpu_mapping_mpi.py:8``, asserting
Σprocs == worker_num); hierarchical has per-silo files. Redesign: "device"
for a rank is a *mesh slice* — the YAML maps ranks to device index groups,
and the returned handle is (devices, mesh) rather than a torch.device
string; on one host with one chip everything collapses to jax.devices()[0].
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax


def get_device(args=None):
    """Default device for this process (reference ``device.py:6``)."""
    devices = jax.devices()
    rank = int(getattr(args, "rank", 0) or 0) if args is not None else 0
    mapping_file = getattr(args, "gpu_mapping_file", None) if args is not None else None
    if mapping_file:
        mapping = load_device_mapping(
            mapping_file, getattr(args, "gpu_mapping_key", "mapping_default")
        )
        idxs = mapping_for_rank(mapping, rank)
        return [devices[i] for i in idxs if i < len(devices)]
    if rank >= len(devices):
        # a TPU chip belongs to one process: ranks that wrap onto a device
        # another rank already maps to cannot both open it
        logging.warning(
            "get_device: rank %d wraps onto device %d of %d (%s) — more "
            "ranks than devices share a device, which a TPU refuses; give "
            "each process its own chip or a gpu_mapping_file",
            rank, rank % len(devices), len(devices), devices[0].platform)
    return devices[rank % len(devices)]


def load_device_mapping(path: str, key: str = "mapping_default") -> Dict[str, List[int]]:
    """YAML format parity with the reference gpu-mapping files::

        mapping_default:
          host1: [2, 2]     # 2 processes on device slot 0, 2 on slot 1

    Returns {host: [procs_per_slot, ...]}.
    """
    import yaml

    with open(path) as f:
        cfg = yaml.safe_load(f)
    if key not in cfg:
        raise KeyError(f"mapping key '{key}' not in {path} (has {list(cfg)})")
    return {str(h): [int(x) for x in slots] for h, slots in cfg[key].items()}


def mapping_for_rank(mapping: Dict[str, List[int]], rank: int) -> List[int]:
    """Resolve a global rank to its device slot indices (reference asserts
    total process count covers worker_num the same way)."""
    r = rank
    for _host, slots in mapping.items():
        for slot_idx, n_procs in enumerate(slots):
            if r < n_procs:
                return [slot_idx]
            r -= n_procs
    raise ValueError(f"rank {rank} beyond mapping capacity "
                     f"({sum(sum(s) for s in mapping.values())} processes)")


def total_processes(mapping: Dict[str, List[int]]) -> int:
    return sum(sum(slots) for slots in mapping.values())
