"""Device mesh context — the single source of truth for parallel topology.

TPU-native re-design of the reference mesh stack
(reference: nemo_automodel/components/distributed/mesh.py:42 `MeshAxisName`,
:66 `ParallelismSizes`, :82 `MeshContext`, mesh_utils.py:276
`_create_fsdp2_device_mesh`, :374 `_create_moe_mesh`). Where the reference
builds a torch DeviceMesh plus a separate 2-D MoE mesh, here there is ONE
`jax.sharding.Mesh` whose axes carry the reference's canonical vocabulary:

    (pp, dp_replicate, dp_shard, ep, cp, tp)     # outermost → innermost

- `pp`           pipeline stages (microbatched stage loop, see parallel/pp.py)
- `dp_replicate` HSDP replication groups (outermost → rides DCN multi-host)
- `dp_shard`     FSDP parameter/optimizer sharding (the fully_shard analog)
- `ep`           expert parallelism; also shards the batch outside MoE blocks
- `cp`           context/sequence parallelism (ring attention over ICI)
- `tp`           tensor parallelism (innermost → fastest ICI hops)

Flattened aliases mirror mesh_utils.py:311-325: `dp = (dp_replicate,
dp_shard)`, `dp_shard_cp = (dp_shard, cp)`, `dp_cp`, and the batch axis for
token sharding `batch = (dp_replicate, dp_shard, ep)` (the analog of the
reference carving the MoE mesh out of the same ranks, mesh_utils.py:374-415).
In GSPMD a flattened alias is just a tuple inside a PartitionSpec — no
separate mesh object is needed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


class MeshAxisName:
    """Canonical axis names (reference: distributed/mesh.py:42-59)."""

    PP = "pp"
    DP_REPLICATE = "dp_replicate"
    DP_SHARD = "dp_shard"
    EP = "ep"
    CP = "cp"
    TP = "tp"

    ALL = (PP, DP_REPLICATE, DP_SHARD, EP, CP, TP)

    # Flattened aliases (reference: mesh_utils.py:311-325). Resolved inside
    # PartitionSpecs — order matters (outer axis first = major order).
    ALIASES = {
        "dp": (DP_REPLICATE, DP_SHARD),
        "dp_shard_cp": (DP_SHARD, CP),
        "dp_cp": (DP_REPLICATE, DP_SHARD, CP),
        "dp_shard_cp_ep": (DP_SHARD, CP, EP),
        "batch": (DP_REPLICATE, DP_SHARD, EP),
        "batch_cp": (DP_REPLICATE, DP_SHARD, EP, CP),
        "ep_shard": (DP_REPLICATE, DP_SHARD),  # FSDP axis for expert params
    }


@dataclasses.dataclass
class MeshConfig:
    """Parallelism sizes; -1 on dp_shard means "infer from device count".

    The analog of the reference's `ParallelismSizes` + `DistributedSetup`
    (distributed/mesh.py:66, distributed/config.py:96).
    """

    pp: int = 1
    dp_replicate: int = 1
    dp_shard: int = -1
    ep: int = 1
    cp: int = 1
    tp: int = 1

    def build(self, devices: Sequence[Any] | None = None) -> "MeshContext":
        devices = list(devices if devices is not None else jax.devices())
        n = len(devices)
        fixed = self.pp * self.dp_replicate * self.ep * self.cp * self.tp
        dp_shard = self.dp_shard
        if dp_shard == -1:
            if n % fixed != 0:
                raise ValueError(
                    f"{n} devices not divisible by pp*dp_replicate*ep*cp*tp={fixed}"
                )
            dp_shard = n // fixed
        if fixed * dp_shard != n:
            raise ValueError(
                f"Mesh sizes pp={self.pp} dp_replicate={self.dp_replicate} "
                f"dp_shard={dp_shard} ep={self.ep} cp={self.cp} tp={self.tp} "
                f"multiply to {fixed * dp_shard}, but there are {n} devices"
            )
        shape = (self.pp, self.dp_replicate, dp_shard, self.ep, self.cp, self.tp)
        dev_array = np.asarray(devices).reshape(shape)
        mesh = Mesh(dev_array, MeshAxisName.ALL)
        return MeshContext(mesh=mesh, config=dataclasses.replace(self, dp_shard=dp_shard))

    @classmethod
    def from_config(cls, node: Any) -> "MeshConfig":
        """Build from a ConfigNode/dict `distributed:` section."""
        kwargs = {}
        for f in dataclasses.fields(cls):
            if node is not None and f.name in node:
                kwargs[f.name] = int(node[f.name] if not hasattr(node, "get") else node.get(f.name))
        return cls(**kwargs)


@dataclasses.dataclass
class MeshContext:
    """A built mesh plus spec/sharding helpers (reference: mesh.py:82)."""

    mesh: Mesh
    config: MeshConfig

    # -- sizes ---------------------------------------------------------------
    def axis_size(self, name: str) -> int:
        if name in MeshAxisName.ALIASES:
            return int(math.prod(self.mesh.shape[a] for a in MeshAxisName.ALIASES[name]))
        return int(self.mesh.shape[name])

    @property
    def sizes(self) -> dict:
        return {a: int(self.mesh.shape[a]) for a in MeshAxisName.ALL}

    @property
    def num_devices(self) -> int:
        return self.mesh.size

    @property
    def dp_size(self) -> int:
        return self.axis_size("dp")

    @property
    def batch_size_divisor(self) -> int:
        """Global batch must divide by this (all token-sharding axes)."""
        return self.axis_size("batch")

    # -- specs ---------------------------------------------------------------
    def resolve_axes(self, axes) -> tuple:
        """Expand aliases; axes may be a str, tuple of str, or None."""
        if axes is None:
            return ()
        if isinstance(axes, str):
            axes = (axes,)
        out: list[str] = []
        for a in axes:
            if a in MeshAxisName.ALIASES:
                out.extend(MeshAxisName.ALIASES[a])
            else:
                if a not in MeshAxisName.ALL:
                    raise ValueError(f"Unknown mesh axis '{a}'")
                out.append(a)
        return tuple(out)

    def spec(self, *dim_axes) -> PartitionSpec:
        """PartitionSpec from per-dimension axis names (aliases resolved).

        `None` means replicated on that dim. Axes whose mesh size is 1 are
        kept (harmless) so specs are topology-independent.
        """
        parts = []
        for axes in dim_axes:
            resolved = self.resolve_axes(axes)
            if not resolved:
                parts.append(None)
            elif len(resolved) == 1:
                parts.append(resolved[0])
            else:
                parts.append(tuple(resolved))
        return PartitionSpec(*parts)

    def sharding(self, *dim_axes) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*dim_axes))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def __enter__(self):
        self._ctx = jax.set_mesh(self.mesh)
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        return self._ctx.__exit__(*exc)
