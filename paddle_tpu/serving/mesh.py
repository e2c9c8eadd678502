"""Mesh-sharded serving: one SPMD decode step over a device mesh.

The serving answer to ParallelExecutor: instead of rewriting the
decode/prefill/verify programs per chip and hand-dispatching N copies,
the SAME whole-block jit compiles once over a `jax.sharding.Mesh` and
GSPMD partitions it — the page pool shards on its heads axis
([pages, page_tokens, heads/tp, dk]), weights keep whatever sharding
they were pinned with, and every host-visible feed (tokens, page
tables, positions, COW plans) replicates. Each decode step is ONE
compiled SPMD program across the mesh; the greedy argmax reduces the
(replicated-by-then) logits on device, so only token ids ever leave.

Bit-exactness vs single-chip is a LAYOUT discipline, not luck: only
column-style weight shardings survive to serve time
(DecodeSpec.serve_param_specs), every sharded contraction input is
gathered whole first (the builders' replicated sharding_constraints),
and the K/V state pins to the same heads-sharded NamedSharding in
in_shardings AND out_shardings — so the donated pool round-trips with
a stable layout and compile-once holds (jit_cache_stats).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec

from ..executor import Executor
from ..parallel.mesh import MeshConfig

__all__ = ['serving_mesh', 'mesh_shape_str', 'MeshDecodeExecutor']


def mesh_shape_str(mesh):
    """Canonical 'ax=n,...' string for a built jax Mesh (the form
    stats/SRV_HEALTH carry so routers and benches stay mesh-aware)."""
    return ','.join('%s=%d' % (ax, n)
                    for ax, n in zip(mesh.axis_names, mesh.devices.shape))


def serving_mesh(mesh=None):
    """Resolve a prepare_decoding mesh argument -> (jax.Mesh | None,
    shape_str). Accepts None (read FLAGS_serve_mesh_shape; '' keeps the
    single-chip path), an axis-spec string ('tp=2'), a MeshConfig, or a
    built jax Mesh."""
    from ..flags import get_flag
    if mesh is None:
        mesh = str(get_flag('serve_mesh_shape', '') or '').strip()
        if not mesh:
            return None, ''
    if isinstance(mesh, str):
        if not mesh.strip():
            return None, ''
        mesh = MeshConfig.from_spec(mesh)
    if isinstance(mesh, MeshConfig):
        mesh = mesh.build()
    return mesh, mesh_shape_str(mesh)


class MeshDecodeExecutor(Executor):
    """Executor whose whole-block jits compile as SPMD programs over a
    serving mesh.

    state_shardings maps the K/V pool var names to their
    heads-sharded NamedSharding; those vars are pinned in BOTH
    in_shardings (they arrive donated from the Scope) and out_shardings
    (the donated update leaves with the identical layout — a host
    round-trip through save/restore_pages can't silently flip the
    layout and trigger a recompile). Feeds replicate; everything else
    (weights) passes None = inherit the committed sharding the
    predictor pinned at construction."""

    def __init__(self, place, mesh, state_shardings=None):
        super(MeshDecodeExecutor, self).__init__(place)
        self.mesh = mesh
        self._replicated = NamedSharding(mesh, PartitionSpec())
        self._state = dict(state_shardings or {})

    @property
    def mesh_devices(self):
        return int(self.mesh.devices.size)

    def state_sharding(self, name):
        """The pinned NamedSharding for a pool var (replicated
        for anything unpinned) — paged.py re-places host-restored pools
        with this before writing them back into the Scope."""
        return self._state.get(name, self._replicated)

    # -- Executor hooks ----------------------------------------------------
    def _put_feed(self, name, arr):
        # every decode feed is host-computed control state (tokens,
        # positions, page tables, COW plans): tiny, and the SPMD program
        # needs it whole on every device
        return jax.device_put(arr, self._replicated)

    def _emit_mesh(self):
        return self.mesh

    def _jit_options(self, segment, feed_names):
        feed_set = set(feed_names)
        out_set = set(segment.out_names)
        donated_keys = [n for n in segment.in_names
                        if n in out_set and n not in feed_set]
        const_keys = [n for n in segment.in_names
                      if n not in set(donated_keys)]

        def spec(name):
            explicit = self._state.get(name)
            if explicit is not None:
                return explicit
            if name in feed_set:
                return self._replicated
            # weights: None = inherit the sharding the predictor
            # committed (column-sharded or replicated per
            # serve_param_specs) — never force a host round-trip
            return None

        in_shardings = (
            {n: spec(n) for n in donated_keys},
            {n: spec(n) for n in const_keys},
            self._replicated,
        )
        out_shardings = tuple(self._state.get(n)
                              for n in segment.out_names)
        return {'in_shardings': in_shardings,
                'out_shardings': out_shardings}

    def place_state(self, name, value):
        """Place (or re-place) a pool value under the var's
        pinned sharding. Host arrays upload sharded; device-resident
        jax arrays reshard without a host round-trip — the
        restore_pages `.at[].set` result re-pins in place."""
        return jax.device_put(value, self.state_sharding(name))
