"""Where the mesh path's FL workers run.

:class:`WorkerShardMap` maps FL workers onto the mesh's worker shards, and
:class:`HostShardMap` partitions those shards among the hosts of the
``hosts=H`` level.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["WorkerShardMap", "HostShardMap"]


@dataclass(frozen=True)
class WorkerShardMap:
    """Maps FL workers onto the mesh's worker shards (the mesh-path unit of
    program dispatch, device placement, and device-cache pooling).

    A *shard* is one slice of the mesh along its FL-worker axes — on a real
    multi-device mesh each shard owns a device group (see
    :func:`repro.launch.mesh.fl_shard_devices`); on a single-device host all
    shards share the one device but still partition the cache pools and the
    per-worker program dispatch.  Workers map to shards by ``wid % n_shards``
    so a worker keeps its shard — and therefore its cached clients' pool —
    across elastic fail/join churn of *other* workers.
    """

    n_shards: int
    shard_of_wid: dict       # wid -> shard index
    devices: tuple = ()      # shard -> jax.Device ( () = default device )

    @classmethod
    def build(cls, workers, n_shards: int, *, devices=None) -> "WorkerShardMap":
        """``workers``: WorkerInfo list (any order); ``devices``: optional
        shard->device list, cycled when shorter than ``n_shards``."""
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        mapping = {w.wid: w.wid % n_shards for w in workers}
        dev = ()
        if devices:
            dev = tuple(devices[s % len(devices)] for s in range(n_shards))
        return cls(n_shards=n_shards, shard_of_wid=mapping, devices=dev)

    def shard_of(self, wid: int) -> int:
        return self.shard_of_wid.get(wid, wid % self.n_shards)

    def device_for(self, wid: int):
        """The jax device worker ``wid``'s program runs on (None = default)."""
        if not self.devices:
            return None
        return self.devices[self.shard_of(wid)]

    def workers_in(self, shard: int) -> list:
        return sorted(w for w, s in self.shard_of_wid.items() if s == shard)

    def live_shards(self) -> set:
        """Shards with at least one live worker.  A shard outside this set
        executes nothing this round: cache affinity must not steer clients
        toward it, and the device cache reclaims its stranded pool
        (:meth:`repro.data.device_cache.DeviceBatchCache.rebalance`)."""
        return set(self.shard_of_wid.values())

    def merge_groups(self) -> dict:
        """The hierarchical-combine topology (``combine_mode="tree"``):
        shard → its live workers in dispatch (wid) order.  Each group is
        one shard-local partial-merge program on that shard's device; the
        cross-shard combine then reduces one partial per group — §3.3's
        node→server tree, with mesh shards as the nodes."""
        return {s: self.workers_in(s) for s in sorted(self.live_shards())}


@dataclass(frozen=True)
class HostShardMap:
    """Partitions the K mesh shards into H contiguous host blocks — the host
    level of the combine hierarchy (``EngineConfig.hosts``).

    Host ``h`` owns shards ``[h*B, (h+1)*B)`` with ``B = n_shards //
    n_hosts``.  The bit-identity invariant across host counts rests on the
    blocks being *aligned subtrees* of one canonical reduction tree: the
    engine combines partials with :meth:`pairwise_reduce` — an iterative
    bottom-up pairing over POSITIONAL slots — and when B is a power of two,
    the first ``log2(B)`` levels of the K-slot tree never cross a block
    boundary, so each host's local reduction IS its subtree and the root's
    pairing over the H host results continues the same tree.  ``hosts=1``
    computes the whole tree in one place (the reference the H-host paths
    are bit-compared against); hence :meth:`build` requires ``K % H == 0``
    and, for ``H >= 2``, a power-of-two block.

    Dead shards (churn emptied them) stay in the slot list as ``None``
    HOLES rather than being compacted away: pairing is positional, so a
    hole must keep occupying its position or the tree shape — and with it
    the result bits — would depend on which shards happen to be live.
    """

    n_hosts: int
    n_shards: int

    @classmethod
    def build(cls, n_shards: int, n_hosts: int) -> "HostShardMap":
        if n_hosts < 1:
            raise ValueError(f"n_hosts must be >= 1, got {n_hosts}")
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if n_shards % n_hosts != 0:
            raise ValueError(
                f"n_shards ({n_shards}) must be divisible by n_hosts "
                f"({n_hosts}): host blocks are equal contiguous shard "
                "ranges")
        block = n_shards // n_hosts
        if n_hosts >= 2 and block & (block - 1):
            raise ValueError(
                f"shards-per-host ({block}) must be a power of two for "
                f"hosts >= 2: only aligned pow2 blocks are exact subtrees "
                "of the canonical pairwise reduction, which is what makes "
                "results bit-identical across host counts")
        return cls(n_hosts=n_hosts, n_shards=n_shards)

    @property
    def block(self) -> int:
        """Shards per host."""
        return self.n_shards // self.n_hosts

    def host_of(self, shard: int) -> int:
        return shard // self.block

    def shards_of(self, host: int) -> range:
        return range(host * self.block, (host + 1) * self.block)

    @staticmethod
    def pairwise_reduce(slots: list, merge):
        """Canonical bottom-up pairwise reduction over positional slots.

        At each level, adjacent pairs ``(0,1), (2,3), ...`` merge; an odd
        trailing slot carries up unmerged.  ``None`` slots are holes: a
        hole merged with a value yields the value (position preserved), two
        holes stay a hole.  Returns the root slot (``None`` when every slot
        is a hole).  Deterministic by construction — the association tree
        depends only on ``len(slots)`` and which positions are holes."""
        if not slots:
            return None
        slots = list(slots)
        while len(slots) > 1:
            nxt = []
            for i in range(0, len(slots) - 1, 2):
                a, b = slots[i], slots[i + 1]
                if a is None:
                    nxt.append(b)
                elif b is None:
                    nxt.append(a)
                else:
                    nxt.append(merge(a, b))
            if len(slots) % 2:
                nxt.append(slots[-1])
            slots = nxt
        return slots[0]
