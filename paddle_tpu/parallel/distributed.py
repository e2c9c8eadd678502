"""Multi-host runtime: DCN coordination service + global device mesh.

TPU-native replacement of the reference's multi-node NCCL bootstrap:
`gen_nccl_id_op` (reference paddle/fluid/operators/distributed/
gen_nccl_id_op.cc:31) has rank 0 run a throwaway RPC server handing the
ncclUniqueId to peers, after which `NCCLContextMap` builds communicators
over num_trainers*places ranks (reference platform/nccl_helper.h:118,
ncclCommInitRank). Here the JAX/PJRT coordination service plays the
out-of-band-exchange role: `jax.distributed.initialize(coordinator,
num_processes, process_id)` connects every trainer over DCN, after which
`jax.devices()` is the GLOBAL device list and XLA collectives ride ICI
within a slice / DCN across slices.

Env contract kept from the reference (trainer.py:329-377, SURVEY §5.6):

  PADDLE_TRAINER_ID          this process's rank
  PADDLE_TRAINERS_NUM        world size (PADDLE_TRAINERS also accepted)
  PADDLE_TRAINER_ENDPOINTS   comma list host:port; first is coordinator
  PADDLE_CURRENT_ENDPOINT    this process's endpoint (optional)

A reference script that ran `transpiler nccl2` mode under these env vars
runs here unmodified with `ParallelExecutor(num_trainers=..., trainer_id=...)`.
"""
from __future__ import annotations

import functools
import os

import numpy as np

import jax

__all__ = ['init_parallel_env', 'is_initialized', 'trainer_id',
           'num_trainers', 'local_batch_to_global', 'host_value_to_global',
           'shard_rows_for_process']

_initialized = False


def _coordination_client_up():
    """True if jax.distributed is already connected. Checked WITHOUT
    touching the backend (jax.process_count() would initialize it, making
    a later initialize() impossible)."""
    try:
        from jax._src import distributed as _jdist
        return _jdist.global_state.client is not None
    except Exception:
        return False


def is_initialized():
    return _initialized or _coordination_client_up()


def _backend_already_live():
    """True if some JAX backend has been created — then querying
    process_index/count is side-effect free (covers multi-process TPU pods
    where PJRT is multi-process without jax.distributed.initialize)."""
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:
        return False


def trainer_id():
    # Consult the backend only when it is already live (coordination client
    # connected, or backend created some other way): jax.process_index()
    # on a cold process initializes the backend, which would permanently
    # prevent a later init_parallel_env() from connecting.
    if _coordination_client_up() or _backend_already_live():
        if jax.process_count() > 1:
            return jax.process_index()
    return int(os.environ.get('PADDLE_TRAINER_ID', 0))


def num_trainers():
    if _coordination_client_up() or _backend_already_live():
        if jax.process_count() > 1:
            return jax.process_count()
    return int(os.environ.get('PADDLE_TRAINERS_NUM',
                              os.environ.get('PADDLE_TRAINERS', 1)))


def init_parallel_env(trainer_id=None, num_trainers=None, endpoints=None,
                      coordinator=None):
    """Connect this process to the trainer job. Arguments override the
    PADDLE_* env contract. No-op when world size is 1 or already connected.

    MUST run before the first JAX computation (the coordination client and
    the collectives-capable CPU backend can only be created at backend
    init; same constraint as the reference requiring gen_nccl_id before
    NCCLContextMap construction)."""
    global _initialized
    if _initialized or _coordination_client_up():
        return
    if trainer_id is None:
        trainer_id = int(os.environ.get('PADDLE_TRAINER_ID', 0))
    if num_trainers is None:
        num_trainers = int(os.environ.get(
            'PADDLE_TRAINERS_NUM', os.environ.get('PADDLE_TRAINERS', 1)))
    if num_trainers <= 1:
        return
    if endpoints is None:
        eps = os.environ.get('PADDLE_TRAINER_ENDPOINTS', '')
        endpoints = [e for e in eps.split(',') if e]
    if coordinator is None:
        if not endpoints:
            raise ValueError(
                'multi-trainer init needs PADDLE_TRAINER_ENDPOINTS (or an '
                'explicit coordinator address)')
        coordinator = endpoints[0]
    # CPU backend needs an explicit cross-process collectives impl; on TPU
    # the PJRT plugin brings its own (ICI/DCN).
    try:
        jax.config.update('jax_cpu_collectives_implementation', 'gloo')
    except Exception:
        pass
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_trainers,
                               process_id=trainer_id)
    _initialized = True


# -- host<->global array helpers (the BCast/feed-split analogs) ------------

def local_batch_to_global(arr, mesh, pspec):
    """This process's LOCAL batch -> global Array sharded per pspec over
    the (possibly multi-host) mesh. Single-process: plain device_put.
    The analog of feed_and_split_tensor_into_local_scopes (reference
    parallel_executor.py:168) at multi-host scale."""
    from jax.sharding import NamedSharding
    if jax.process_count() == 1:
        return jax.device_put(arr, NamedSharding(mesh, pspec))
    from jax.experimental import multihost_utils
    return multihost_utils.host_local_array_to_global_array(
        np.asarray(arr), mesh, pspec)


def host_value_to_global(arr, mesh, pspec):
    """A host value PRESENT IDENTICALLY on every process (startup params
    run from one seed) -> global Array with the given sharding, whatever
    dimension it splits: each process hands its own devices their
    slices (the ncclBcast analog, reference parallel_executor.cc:210)."""
    from jax.sharding import NamedSharding
    sharding = NamedSharding(mesh, pspec)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    arr = np.asarray(arr)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda index: arr[index])


def shard_rows_for_process(arr, mesh, axis_entry):
    """Rows of the full array that THIS process's host-local view covers
    when dim 0 is sharded over `axis_entry` (an axis name or tuple of axis
    names from a PartitionSpec).

    Derived from the mesh's actual device->process mapping rather than
    assuming the axis spans processes contiguously in process-index order:
    each dim-0 shard index is owned by the devices at that coordinate along
    the sharding axes; this process's view is the union of shards its
    local devices sit on (which host_local_array_to_global_array requires
    to be one contiguous range — asserted)."""
    names = axis_entry if isinstance(axis_entry, tuple) else (axis_entry,)
    lo, nmine, total = _process_shard_range(mesh, names)
    rows = arr.shape[0]
    if rows % total != 0:
        raise ValueError('dim0=%d not divisible by %d shards along %r'
                         % (rows, total, names))
    per = rows // total
    return arr[lo * per:(lo + nmine) * per]


@functools.lru_cache(maxsize=64)
def _process_shard_range(mesh, names):
    """(lo_shard, n_shards, total_shards) for this process along `names`.
    Depends only on (mesh, names) within a process — memoized, since the
    device walk is O(mesh size) and startup broadcast calls this per
    parameter."""
    pid = jax.process_index()
    axes = list(mesh.axis_names)
    dev_arr = np.asarray(mesh.devices)
    total = 1
    for nm in names:
        total *= mesh.shape[nm]
    mine = set()
    for idx in np.ndindex(*dev_arr.shape):
        coord = 0
        for nm in names:
            coord = coord * mesh.shape[nm] + idx[axes.index(nm)]
        if dev_arr[idx].process_index == pid:
            mine.add(coord)
    if not mine:
        raise ValueError(
            'process %d owns no devices in the mesh (axes %r) — every '
            'participating process must contribute devices' % (pid, names))
    lo = min(mine)
    if sorted(mine) != list(range(lo, lo + len(mine))):
        raise ValueError(
            'axis %r maps to non-contiguous dim-0 shards %s for process %d; '
            'reorder the mesh so dim-0 sharding is contiguous per host'
            % (names, sorted(mine), pid))
    return (lo, len(mine), total)
