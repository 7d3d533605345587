"""MeshTransport — collectives over an ICI device mesh.

The TPU-native re-expression of the reference's transport matrix
(SURVEY.md §5.8): instead of per-peer sockets, peers form a mesh and data
moves through XLA collectives compiled onto the interconnect. The API is
shaped by what the RPC layers above need:

- ``scatter``/``gather``: host staging ↔ sharded device residency (the
  PartitionChannel data path);
- ``all_gather``/``reduce_scatter``/``psum``: fan-out merge semantics
  (ParallelChannel's ResponseMerger, reduced on-device);
- ``ring_shift``/``ring_exchange``: neighbor schedules (streaming windows
  and ring-attention building blocks);
- ``all_to_all``: resharding between partition schemes
  (DynamicPartitionChannel's migration).

All programs are built once per (shape, dtype) via jit caching; static
shapes keep XLA happy (SURVEY.md lesson: no data-dependent control flow
inside jit).
"""

from __future__ import annotations

import functools
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

from ..butil.endpoint import EndPoint

_jax = None
# RLock: global_mesh_transport() holds it while MeshTransport.__init__
# re-enters via _jax_mod()
_lock = threading.RLock()


def _jax_mod():
    """Late import so pure-RPC users never pay for (or require) JAX."""
    global _jax
    with _lock:
        if _jax is None:
            import jax
            _jax = jax
        return _jax


def _shard_map(jax):
    """``jax.shard_map`` with the VMA check off: collective outputs
    (psum/all_gather) are intentionally replicated across the axis."""
    return functools.partial(jax.shard_map, check_vma=False)


def default_mesh(axis_name: str = "ici", devices=None):
    """1-D mesh over all local devices — the 'every chip is a peer' view."""
    jax = _jax_mod()
    from jax.sharding import Mesh
    devs = np.array(devices if devices is not None else jax.devices())
    return Mesh(devs, (axis_name,))


class MeshTransport:
    """Collective schedules over one mesh axis.

    ≈ role of Socket+RdmaEndpoint for peers on the interconnect: the unit
    of addressing is the device coordinate (EndPoint ``ici://mesh/i``),
    the unit of transfer is an array shard, and "flow control" is XLA's
    static schedule rather than window+ack (SURVEY.md §5.8)."""

    def __init__(self, mesh=None, axis: str = "ici", name: str = "mesh0"):
        jax = _jax_mod()
        self.jax = jax
        self.mesh = mesh if mesh is not None else default_mesh(axis)
        self.axis = axis if axis in self.mesh.axis_names \
            else self.mesh.axis_names[0]
        self.name = name

    # -- addressing --------------------------------------------------------

    @property
    def n_peers(self) -> int:
        return int(self.mesh.devices.size)

    def endpoint(self, index: int) -> EndPoint:
        return EndPoint(mesh=self.name, device_index=index)

    def endpoints(self) -> Sequence[EndPoint]:
        return [self.endpoint(i) for i in range(self.n_peers)]

    # -- residency ---------------------------------------------------------

    def scatter(self, array, axis: int = 0):
        """Host/replicated array → sharded along ``axis`` across peers
        (the PartitionChannel write path)."""
        jax = self.jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = [None] * np.ndim(array)
        spec[axis] = self.axis
        return jax.device_put(array, NamedSharding(self.mesh, P(*spec)))

    def replicate(self, array):
        jax = self.jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(array, NamedSharding(self.mesh, P()))

    def gather(self, array) -> np.ndarray:
        """Sharded → host (the PartitionChannel read path)."""
        return np.asarray(self.jax.device_get(array))

    # -- collective programs (jit-cached per shape) -----------------------

    @functools.lru_cache(maxsize=256)
    def _ring_shift_fn(self, steps: int):
        jax = self.jax
        from jax.sharding import PartitionSpec as P
        n = self.mesh.shape[self.axis]
        perm = [(i, (i + steps) % n) for i in range(n)]

        def shift(x):
            return jax.lax.ppermute(x, self.axis, perm)

        return jax.jit(_shard_map(jax)(shift, mesh=self.mesh,
                                 in_specs=P(self.axis),
                                 out_specs=P(self.axis)))

    def ring_shift(self, x, steps: int = 1):
        """Every peer passes its shard ``steps`` neighbors down the ring
        (CollectivePermute on ICI — the streaming/pipeline primitive)."""
        return self._ring_shift_fn(steps)(x)

    @functools.lru_cache(maxsize=256)
    def _all_gather_fn(self):
        jax = self.jax
        from jax.sharding import PartitionSpec as P

        def ag(x):
            return jax.lax.all_gather(x, self.axis, tiled=True)

        return jax.jit(_shard_map(jax)(ag, mesh=self.mesh,
                                 in_specs=P(self.axis), out_specs=P()))

    def all_gather(self, x):
        """Each peer ends with every shard (fan-in broadcast merge)."""
        return self._all_gather_fn()(x)

    @functools.lru_cache(maxsize=256)
    def _psum_fn(self):
        jax = self.jax
        from jax.sharding import PartitionSpec as P

        def ps(x):
            return jax.lax.psum(x, self.axis)

        return jax.jit(_shard_map(jax)(ps, mesh=self.mesh,
                                 in_specs=P(self.axis), out_specs=P()))

    def psum(self, x):
        """Sum of all shards, replicated everywhere (ResponseMerger-as-
        reduction, computed on-device)."""
        return self._psum_fn()(x)

    @functools.lru_cache(maxsize=256)
    def _reduce_scatter_fn(self):
        jax = self.jax
        from jax.sharding import PartitionSpec as P

        def rs(x):
            # x per-device: (1, L). Sum across peers, each keeps chunk i
            # of the result (global out: (n, L/n)).
            return jax.lax.psum_scatter(x[0], self.axis,
                                        scatter_dimension=0,
                                        tiled=True)[None]

        return jax.jit(_shard_map(jax)(rs, mesh=self.mesh,
                                 in_specs=P(self.axis),
                                 out_specs=P(self.axis)))

    def reduce_scatter(self, x):
        """Row-sharded (n, L) input: result (n, L/n) — peer i holds the
        i-th chunk of the element-wise sum of all rows."""
        return self._reduce_scatter_fn()(x)

    @functools.lru_cache(maxsize=256)
    def _all_to_all_fn(self, split_axis: int, concat_axis: int):
        jax = self.jax
        from jax.sharding import PartitionSpec as P

        def a2a(x):
            return jax.lax.all_to_all(x, self.axis, split_axis=split_axis,
                                      concat_axis=concat_axis, tiled=True)

        return jax.jit(_shard_map(jax)(a2a, mesh=self.mesh,
                                 in_specs=P(self.axis),
                                 out_specs=P(self.axis)))

    def all_to_all(self, x, split_axis: int = 1, concat_axis: int = 0):
        """Transpose which dimension is sharded — the re-partitioning
        move (and the Ulysses-style sequence↔head exchange)."""
        return self._all_to_all_fn(split_axis, concat_axis)(x)

    # lru_cache on methods holds self; fine — transports are process-wide
    # singletons like the reference's EventDispatchers

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


_default_transport: Optional[MeshTransport] = None


def global_mesh_transport() -> MeshTransport:
    global _default_transport
    with _lock:
        if _default_transport is None:
            _default_transport = MeshTransport()
        return _default_transport
