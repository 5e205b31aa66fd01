"""Mesh construction and owner→shard assignment.

Owners are the data-parallel unit (each owner's message log and Merkle
tree are independent by construction — the relay keys everything by
userId, apps/server/src/index.ts:64-75), so the mesh has one axis,
`owners`. Multi-host pods get the same axis laid over all devices; XLA
routes the XOR-combine collectives over ICI.
"""

from __future__ import annotations

from typing import Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

OWNERS_AXIS = "owners"


def create_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """A 1-D mesh over `n_devices` (default: all available)."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    # Push the device kind into the jax-free stage-anatomy plane
    # (ISSUE 16): obs/anatomy.py prices floors from COST_LAWS keyed by
    # `device_kind` but must never import jax itself, so the one place
    # that already holds a device tells it.
    from evolu_tpu.obs import anatomy

    anatomy.set_device_kind(devices[0].device_kind)
    return Mesh(np.array(devices), (OWNERS_AXIS,))


def sharding(mesh: Mesh) -> NamedSharding:
    """Shard a 1-D array's leading axis over the owners axis."""
    return NamedSharding(mesh, PartitionSpec(OWNERS_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def put_sharded(arr: np.ndarray, shd: NamedSharding):
    """Host array → sharded device array, for every sharded dispatch
    site. Single-process: plain device_put. Multi-process (a
    jax.distributed cluster — the DCN topology): each process serves
    its ADDRESSABLE shards from the same host-built global layout via
    make_array_from_callback; jax assembles the global array without
    any process addressing foreign devices."""
    if jax.process_count() > 1:
        return jax.make_array_from_callback(arr.shape, shd, lambda idx: arr[idx])
    return jax.device_put(arr, shd)


def require_single_process(what: str) -> None:
    """Loud guard for fan-ins that index kernel outputs with GLOBAL
    positions: on a multi-process cluster `to_host` returns only the
    ADDRESSABLE shards, so global indexing would be silently wrong.
    The multi-process pattern is `reconcile_columns_sharded` +
    `multihost.local_owners`, each process consuming its own shards
    (see tests/_multihost_worker.py)."""
    if jax.process_count() > 1:
        raise NotImplementedError(
            f"{what} assembles per-owner results from GLOBAL output positions "
            "and runs single-process only; on a jax.distributed cluster use "
            "reconcile_columns_sharded + multihost.local_owners per process"
        )


def owner_shard(owner_id, n_shards: int) -> int:
    """STABLE owner→device placement (crc32, the same family as
    `ShardedRelayStore.shard_index` and `engine.owner_process`): an
    owner's rows land on the same mesh device every batch, which is
    what lets per-owner device-resident state (sharded winner-cache
    slots, write-behind serving trees fed from sharded deltas) survive
    across batches. Pure function of (owner, n_shards) — every
    process/relay sharing a mesh computes the same placement."""
    import zlib

    if not isinstance(owner_id, (bytes, bytearray)):
        owner_id = str(owner_id).encode("utf-8")
    return zlib.crc32(owner_id) % n_shards


class MeshContext:
    """ONE device-mesh context shared by every sharded-engine consumer
    in the process (engine passes, the sharded winner cache, scheduler
    pools serving several relays): the mesh object is the jit-cache key
    for every compiled shard_map kernel, so sharing the context means
    one compiled pipeline per bucket for the whole process — not one
    per relay — and `place`/`assign_stable` give all consumers the same
    stable owner→device placement.

    Per-batch LPT (``assign_owners_to_shards``) balances better but
    re-places owners every batch; the sharded engine trades that for
    placement stability and measures the cost honestly instead
    (`evolu_mesh_shard_rows` occupancy and `evolu_mesh_padding_waste_rows`
    histograms, docs/OBSERVABILITY.md)."""

    def __init__(self, mesh: Optional[Mesh] = None, n_devices: Optional[int] = None):
        self.mesh = mesh if mesh is not None else create_mesh(n_devices)
        self.n_shards = int(self.mesh.devices.size)
        from evolu_tpu.obs import metrics

        metrics.set_gauge("evolu_mesh_devices", self.n_shards)

    def place(self, owner_id) -> int:
        return owner_shard(owner_id, self.n_shards)

    def assign_stable(self, unit_sizes: Dict[Hashable, int]) -> List[List[Hashable]]:
        """Placement-stable layout with the `assign_owners_to_shards`
        return shape. Units are owner ids or (owner, chunk-index)
        tuples (the engine's hot-owner row-split): chunk j of owner o
        lands on shard (place(o) + j) % n — chunk 0 always on the
        owner's home shard, later chunks spilling round-robin so a hot
        owner still uses the whole mesh (safe wherever the decoder
        XOR-merges repeated (owner, minute) partials, which every
        engine delta decoder does)."""
        shards: List[List[Hashable]] = [[] for _ in range(self.n_shards)]
        for u in unit_sizes:
            if isinstance(u, tuple) and len(u) == 2:
                owner, j = u
            else:
                owner, j = u, 0
            shards[(self.place(owner) + int(j)) % self.n_shards].append(u)
        return shards

    def record_occupancy(self, loads: Sequence[int], shard_size: int) -> None:
        """Per-device batch-occupancy / padding-waste telemetry for one
        sharded dispatch (`evolu_mesh_*`, docs/OBSERVABILITY.md): two
        observations a device, and the dispatch's rows and slots
        (devices x bucket) as counters, so that occupancy over any
        window is a ratio of two deltas. One acquisition of the
        registry's lock a dispatch."""
        from evolu_tpu.obs import metrics

        observations = []
        for load in loads:
            observations.append(("evolu_mesh_shard_rows", load, {}))
            observations.append(("evolu_mesh_padding_waste_rows",
                                 max(shard_size - load, 0), {}))
        metrics.observe_many(observations, buckets=metrics.COUNT_BUCKETS, also_inc=(
            ("evolu_mesh_dispatches_total", 1, {}),
            ("evolu_mesh_rows_total", sum(loads), {}),
            ("evolu_mesh_slot_rows_total", len(loads) * shard_size, {}),
        ))

    def record_xdev_reduce(self, kind: str) -> None:
        """Count one cross-device reduction (the digest XOR all-reduce
        of a sharded dispatch, or a host XOR-merge of per-owner delta
        partials that spanned devices)."""
        from evolu_tpu.obs import metrics

        metrics.inc("evolu_mesh_xdev_reduce_total", kind=kind)


_process_ctx: Optional[MeshContext] = None


def get_mesh_context(n_devices: Optional[int] = None) -> MeshContext:
    """The process-wide MeshContext singleton (relay/scheduler wiring —
    embedders and tests pass explicit contexts instead). Lazy: calling
    this touches the jax backend, so it must only run on device-side
    paths (the scheduler's first batch), never at relay import.

    FIRST CREATION WINS: placement (`owner_shard` is mod n_shards) must
    be one function per process — two contexts of different sizes would
    place the same owner on different devices for different consumers.
    A later call with a mismatched `n_devices` therefore returns the
    existing context (logged), never a second pool."""
    global _process_ctx
    if _process_ctx is None:
        _process_ctx = MeshContext(n_devices=n_devices)
    elif n_devices is not None and _process_ctx.n_shards != n_devices:
        from evolu_tpu.utils.log import log

        log("server", "mesh context size mismatch ignored (first wins)",
            have=_process_ctx.n_shards, requested=n_devices)
    return _process_ctx


def assign_owners_to_shards(
    owner_sizes: Dict[Hashable, int], n_shards: int
) -> List[List[Hashable]]:
    """Greedy LPT balance: work units (with their message counts) onto
    shards, heaviest first. A unit is never split across shards. Units
    are usually whole owners (keyed by owner id), keeping merge/Merkle
    work device-local — but callers may pre-split a hot owner into
    finer units, e.g. `engine.deltas_from_columns` passes
    (owner, chunk-index) tuples whose partial digests are XOR-merged
    after the pass; this function only balances whatever units it is
    given."""
    shards: List[List[Hashable]] = [[] for _ in range(n_shards)]
    loads = [0] * n_shards
    for owner in sorted(owner_sizes, key=owner_sizes.get, reverse=True):
        i = loads.index(min(loads))
        shards[i].append(owner)
        loads[i] += owner_sizes[owner]
    return shards
