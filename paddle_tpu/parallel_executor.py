"""ParallelExecutor: data-parallel training over a TPU mesh via GSPMD.

TPU-native re-design of the reference multi-device engine
(paddle/fluid/framework/parallel_executor.cc:119, details/
multi_devices_graph_pass.cc, details/all_reduce_op_handle.cc:48,
details/threaded_ssa_graph_executor.cc:36). The reference replicates the op
graph per GPU, hand-inserts scale_loss_grad + NCCL AllReduce op-handles, and
schedules them with a threadpool. Here the SAME single-program block is jit
compiled over a `jax.sharding.Mesh`: the batch feeds are sharded on the 'dp'
axis, and XLA's SPMD partitioner inserts the gradient AllReduce over ICI
automatically -- the entire threaded SSA scheduler collapses into one XLA
executable. On a mesh whose dp axis is larger than 1 what an optimizer op
updates (the float32 masters and their accumulators) lives as dp shards
(state_sharding), so the update is computed once and not once a device, and
a weight is gathered where it is used; BuildStrategy.kReduce, the reference's
reduce strategy, asked for the accumulators' half of that: it is accepted
and changes nothing.

Loss scaling: the reference inserts scale_loss_grad (1/ndev). Here the loss
is a global-batch mean over a sharded array, so XLA computes the exact global
mean -- no explicit scaling op is needed (GradientScaleStrategy.kCoeffNumDevice
semantics fall out for free).

BCastParamsToDevices (parallel_executor.cc:210, ncclBcast per param) maps to
re-laying-out the startup-initialized state into its place on the mesh on
first run (_bcast_params).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .executor import Executor, TPUPlace, global_scope
from .framework import default_main_program
from .obs import telemetry as _tm

__all__ = ['ParallelExecutor', 'ExecutionStrategy', 'BuildStrategy']

# How XLA's TPU compiler schedules the sums GSPMD places behind a mesh
# program's gradients. By default it combines them into groups of up to
# 120 MiB, each a plain all-reduce: the chip does nothing else while it
# runs. A sum can instead ride inside a compute fusion (an "async
# collective fusion": its steps run beside a matmul of the backward),
# but only a sum of ONE array. So: the first two options allow the
# fusion (either alone changes nothing), and the combiner's threshold
# leaves every gradient of 16 MiB or more a sum of its own. They say
# how collectives are scheduled, never what is summed or in which
# dtype. Per executable (jax.jit compiler_options), so a one-device
# program is not touched; the CPU compiler rejects them, so they go
# only to a mesh of more than one TPU device (_overlap_options).
# Measured, and what was tried and not kept: PERF.md section 6, PR 32.
# The fourth is for the state a dp mesh holds as shards (state_sharding):
# left to itself the compiler sums a gradient whose update is sharded by
# a custom fusion of its own (all-reduce-scatter) on the product's
# float32 result, which no reader of collectives sees (not
# profiler.collective_audit, not the benchmark's exposed share); without
# that fusion the gradients are summed as a replicated step sums them,
# in the same dtype (bf16 all-reduces of bf16 gradients), and each chip
# then updates its slice. PERF.md section 6, PR 47.
_OVERLAP_OPTIONS = {
    'xla_enable_async_all_reduce': True,
    'xla_tpu_enable_async_collective_fusion_fuse_all_reduce': True,
    'xla_jf_crs_combiner_threshold_in_bytes': 16 << 20,
    'xla_tpu_enable_all_reduce_scatter_fusion': False,
}
_OVERLAP_COMPILES = _tm.counter('parallel.overlap_compiles')
# bytes of optimizer-updated state (masters, moments) that _bcast_params
# placed as dp shards and as replicas: whether the sharded update engaged
_UPDATE_SHARDED = _tm.gauge('parallel.update_sharded_bytes')
_UPDATE_REPLICATED = _tm.gauge('parallel.update_replicated_bytes')


class ExecutionStrategy(object):
    """Knobs of the reference details/execution_strategy.h. Thread counts and
    op-delay do not exist in the XLA execution model; they are accepted and
    recorded for API compatibility. num_iteration_per_drop_scope is honored
    as a host-side GC cadence."""

    class ExecutorType:
        Default = 0
        Experimental = 1

    def __init__(self):
        self.num_threads = 0
        self.use_cuda = True
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 100
        self.type = ExecutionStrategy.ExecutorType.Default


class BuildStrategy(object):
    """Knobs of the reference details/build_strategy.h."""

    class ReduceStrategy:
        # Accepted, as scripts pass them, and read by nothing: a dp mesh
        # shards what its optimizer updates by itself (ParallelExecutor.
        # state_sharding), accumulators and parameters alike, under
        # either value.
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.debug_graphviz_path = ''
        self.enable_data_balance = False
        # per-device batch_norm statistics under data parallelism — the
        # reference's semantics (multi_devices_graph_pass.cc replicates
        # batch_norm per device). Default False = SyncBN (GSPMD reduces
        # stats over the sharded batch: numerically stronger, but one
        # latency-bound all-reduce per BN per direction per step).
        # Maps onto FLAGS_bn_local_stats at construction.
        self.bn_local_stats = False


class ParallelExecutor(Executor):
    """(reference python/paddle/fluid/parallel_executor.py:32)

    use_cuda is accepted for script compatibility and means "use the
    accelerator backend"; device selection is the JAX default backend.
    """

    def __init__(self, use_cuda=True, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None,
                 build_strategy=None, num_trainers=1, trainer_id=0,
                 scope=None, devices=None, strategy=None, **kwargs):
        # multi-trainer: connect to the coordination service BEFORE any
        # device lookup (the gen_nccl_id/NCCLContextMap analog; reference
        # nccl_helper.h:118). After this, jax.devices() is global.
        from .parallel import distributed as dist
        if num_trainers > 1:
            dist.init_parallel_env(trainer_id=trainer_id,
                                   num_trainers=num_trainers)
        super(ParallelExecutor, self).__init__(TPUPlace())
        self._main_program = main_program or default_main_program()
        self._loss_name = loss_name
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._build_strategy = build_strategy or BuildStrategy()
        # per-executor BN-stats override: True forces local stats for THIS
        # executor's programs only; False (default) inherits the global
        # FLAGS_bn_local_stats — no process-global state is mutated
        self._bn_local_stats = (
            True if getattr(self._build_strategy, 'bn_local_stats', False)
            else None)
        self._num_trainers = num_trainers
        self._trainer_id = trainer_id
        self._scope = scope or global_scope()
        if share_vars_from is not None:
            self._scope = share_vars_from._scope

        if devices is None:
            devices = jax.devices()
        self._devices = list(devices)
        self._strategy = strategy
        if strategy is not None:
            # multi-axis mesh (dp/tp/sp/pp/ep) from a DistributedStrategy
            self.mesh = strategy.mesh_config(self._devices).build()
        else:
            self.mesh = Mesh(np.array(self._devices), ('dp',))
        self._dp_size = (self.mesh.shape['dp']
                         if 'dp' in self.mesh.axis_names else 1)
        self._replicated = NamedSharding(self.mesh, P())
        self._batch_sharded = NamedSharding(
            self.mesh, P('dp' if 'dp' in self.mesh.axis_names else None))
        self._params_placed = False
        # the executor whose scope this one shares, if both span one
        # mesh: state_sharding and _bcast_params follow its placement
        self._owner = share_vars_from if share_vars_from is not None \
            and share_vars_from.mesh == self.mesh else None
        self._updated = None
        self._state_shardings = {}
        self._run_count = 0
        if self._build_strategy.debug_graphviz_path:
            from .debugger import program_to_dot
            with open(self._build_strategy.debug_graphviz_path, 'w') as f:
                f.write(program_to_dot(self._main_program))

    @property
    def device_count(self):
        return len(self._devices)

    # -- Executor hooks ----------------------------------------------------
    def _var_sharding(self, name):
        """NamedSharding for an annotated var, else None."""
        var = self._main_program.global_block().vars.get(name)
        spec = getattr(var, 'dist_attr', None) if var is not None else None
        if spec is None:
            return None
        from .parallel.mesh import named_sharding
        return named_sharding(self.mesh, spec)

    def _put_feed(self, name, arr):
        """Shard the global batch on dim 0 over 'dp' (the analog of
        feed_and_split_tensor_into_local_scopes,
        reference parallel_executor.py:168). Vars with explicit dist_attr
        annotations are placed per annotation.

        Multi-trainer: each process feeds its LOCAL batch; the global
        batch is their dp-order concatenation."""
        from .parallel import distributed as dist
        from jax.sharding import PartitionSpec
        multihost = jax.process_count() > 1
        explicit = self._var_sharding(name)
        if explicit is not None:
            if multihost:
                return dist.host_value_to_global(
                    np.asarray(arr), self.mesh, explicit.spec)
            return jax.device_put(arr, explicit)
        if arr.ndim == 0:
            if multihost:
                return dist.local_batch_to_global(
                    np.asarray(arr), self.mesh, PartitionSpec())
            return jax.device_put(arr, self._replicated)
        if multihost:
            local_dp = self._dp_size // jax.process_count()
            if local_dp and np.asarray(arr).shape[0] % local_dp != 0:
                raise ValueError(
                    'local batch size %d not divisible by local dp degree %d'
                    % (np.asarray(arr).shape[0], local_dp))
            return dist.local_batch_to_global(
                np.asarray(arr), self.mesh, self._batch_sharded.spec)
        if arr.shape[0] % self._dp_size != 0:
            raise ValueError(
                'batch size %d not divisible by dp degree %d'
                % (arr.shape[0], self._dp_size))
        return jax.device_put(arr, self._batch_sharded)

    def _emit_mesh(self):
        return self.mesh

    def _overlap_options(self):
        """Compiler options for a segment of this mesh, or None: only a
        mesh of more than one TPU device has collectives the options
        know (observed from the devices, no flag)."""
        if len(self._devices) > 1 and \
                all(d.platform == 'tpu' for d in self._devices):
            return dict(_OVERLAP_OPTIONS)
        return None

    def _jit_options(self, segment, feed_names):
        feed_set = set(feed_names)
        out_set = set(segment.out_names)
        donated_keys = [n for n in segment.in_names
                        if n in out_set and n not in feed_set]
        const_keys = [n for n in segment.in_names
                      if n not in set(donated_keys)]

        block_vars = self._main_program.global_block().vars
        # on a dp mesh state enters and leaves a step where
        # state_sharding holds it: left to the partitioner, some came
        # back from the chip's first step in another layout and the
        # second step compiled again (2.3 s of set-up), and a fresh
        # value put in the scope (uncommitted) is laid out here, not
        # wherever the partitioner likes
        pinned = self._dp_size > 1

        def state(name):
            var = block_vars.get(name)
            if pinned and var is not None and var.persistable:
                return self.state_sharding(name)
            return None

        def spec(name):
            explicit = self._var_sharding(name)
            if explicit is not None:
                return explicit
            if name in feed_set:
                var = block_vars.get(name)
                if var is not None and var.shape:
                    return self._batch_sharded
                return self._replicated
            # non-annotated state with dp 1 (and what is no persistable
            # variable): None = inherit the argument's current sharding
            return state(name)

        in_shardings = (
            {n: spec(n) for n in donated_keys},
            {n: spec(n) for n in const_keys},
            self._replicated,
        )
        options = {'in_shardings': in_shardings}
        if pinned:
            options['out_shardings'] = tuple(
                state(n) for n in segment.out_names)
        overlap = self._overlap_options()
        if overlap:
            # one call a compiled segment (both paths of _compile_segment)
            _OVERLAP_COMPILES.inc()
            options['compiler_options'] = overlap
        return options

    def _compile_segment(self, segment, block, program, feed_names=(),
                         donate=True):
        """pp-annotated segments lower through the pipeline engine
        (parallel/pp_lowering.py); everything else takes the standard
        whole-block emission path. Both paths count into
        jit_cache_stats()['compiled_segments'] — the SPMD/pipeline
        executor keeps full stats parity with the base Executor."""
        if self._strategy is not None and self._strategy.pp > 1:
            from .parallel.pp_lowering import (segment_has_pp,
                                               build_pp_segment_fn)
            if segment_has_pp(segment):
                seg_fn = build_pp_segment_fn(self, segment, block, program)
                self._compile_count += 1
                return jax.jit(seg_fn,
                               donate_argnums=(0,) if donate else (),
                               **self._jit_options(segment, feed_names))
        return super(ParallelExecutor, self)._compile_segment(
            segment, block, program, feed_names, donate)

    # -- placement -------------------------------------------------------
    def _dp_shard(self, shape):
        """`shape` split over dp on its first dimension that divides, or
        None."""
        for axis, dim in enumerate(shape):
            if dim and dim > 0 and dim % self._dp_size == 0:
                spec = [None] * len(shape)
                spec[axis] = 'dp'
                return NamedSharding(self.mesh, P(*spec))
        return None

    def _updated_state(self):
        """Names of the variables the rule of state_sharding shards: what
        an optimizer op of the program writes (the float32 masters and
        their accumulators). An op whose Param is annotated (tp/sp/ep)
        is left out whole: its update is partitioned by that axis, and
        accumulators laid out against their parameter would be re-laid
        every step."""
        if self._updated is None:
            self._updated = frozenset(
                n for op in self._main_program.global_block().ops
                if op.attr('op_role', None) == 'optimize'
                and not any(self._var_sharding(p) is not None
                            for p in op.input('Param'))
                for n in op.output_arg_names())
        return self._updated

    def state_sharding(self, name):
        """Where persistable variable `name` lives on this mesh (what
        _bcast_params places, and what tools/mesh_schedule.py compiles
        for). An annotation (tp/sp/ep) wins. On a mesh whose dp axis is
        larger than 1, a variable that an optimizer op updates is a dp
        shard on its first dimension that divides, so that each chip
        updates 1/dp of every tensor and GSPMD gathers a weight where
        it is used, after AMP's cast (what no dimension of divides stays
        a replica). Everything else is a replica. An executor built with
        share_vars_from over the same mesh asks the scope's owner about
        the owner's variables: a test program has no optimizer op, and
        the state it reads stays where the training step holds it."""
        if name not in self._state_shardings:
            # one object a variable: a step's arguments then carry the
            # very sharding its jit options name, which jit's dispatch
            # sees without comparing specs
            owner = self._owner
            if owner is not None and \
                    name in owner._main_program.global_block().vars:
                sharding = owner.state_sharding(name)
            else:
                sharding = self._place_state(name)
            self._state_shardings[name] = sharding
        return self._state_shardings[name]

    def _place_state(self, name):
        explicit = self._var_sharding(name)
        if explicit is not None:
            return explicit
        var = self._main_program.global_block().vars[name]
        if self._dp_size > 1 and var.shape and \
                name in self._updated_state():
            return self._dp_shard(var.shape) or self._replicated
        return self._replicated

    def _bcast_params(self):
        """Re-place startup-initialized persistable state where
        state_sharding says it lives on the mesh (analog of
        BCastParamsToDevices ncclBcast, reference
        parallel_executor.cc:210). What the owner of a shared scope
        has placed already is left where it is."""
        updated = self._updated_state()
        placed = {True: 0, False: 0}
        owner = self._owner
        owner_vars = owner._main_program.global_block().vars \
            if owner is not None and owner._params_placed else ()
        block = self._main_program.global_block()
        for name, var in block.vars.items():
            if not var.persistable or name in owner_vars:
                continue
            val = self._scope.find_var(name)
            if val is None:
                continue
            target = self.state_sharding(name)
            if name in updated:
                placed[not target.is_fully_replicated] += \
                    int(getattr(val, 'nbytes', 0))
            if jax.process_count() > 1:
                from .parallel import distributed as dist
                self._scope.set_var(name, dist.host_value_to_global(
                    np.asarray(val), self.mesh, target.spec))
            else:
                # device-resident values reshard on device; np.asarray
                # here would round-trip every parameter through the host
                if not isinstance(val, jax.Array):
                    val = np.asarray(val)
                self._scope.set_var(name, jax.device_put(val, target))
        if updated:
            _UPDATE_SHARDED.set(placed[True])
            _UPDATE_REPLICATED.set(placed[False])
        self._params_placed = True

    # -- public API --------------------------------------------------------
    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        feed = feed if feed is not None else feed_dict
        if not self._params_placed:
            self._bcast_params()
        result = super(ParallelExecutor, self).run(
            program=self._main_program, feed=feed, fetch_list=fetch_list,
            scope=self._scope, return_numpy=return_numpy)
        self._run_count += 1
        drop_every = self._exec_strategy.num_iteration_per_drop_scope
        if drop_every and self._run_count % drop_every == 0:
            self._scope.drop_kids()
        return result
