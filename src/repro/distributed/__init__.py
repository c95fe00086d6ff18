"""Distributed runtime: sharded DBSCAN over a device mesh
(``dbscan_dist``), checkpointing (``checkpoint``) and the serving tier's
shard placement (``shard_devices``)."""
from __future__ import annotations


def shard_devices(n_shards: int, devices=None) -> list:
    """Round-robin device placement for serving shards (DESIGN.md §15.2).

    Shard ``j`` lives on device ``j % D`` — the sharded tier
    ``device_put``s each shard's frozen snapshot (and its replicas) onto
    its slot, so on a multi-device host the scatter phase's per-shard
    ``cross_sweep`` programs run on distinct accelerators while the
    single-device case degenerates gracefully (shards still isolate
    plans, deltas, WALs, and checkpoint namespaces).
    """
    import jax
    devs = list(devices) if devices is not None else jax.devices()
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    return [devs[j % len(devs)] for j in range(n_shards)]
