"""DistributedStrategy: one config object for the whole parallel stack
(the TPU-era analog of the reference's BuildStrategy/ExecutionStrategy
pair plus the transpiler's config, SURVEY.md §2.6)."""
from __future__ import annotations

from .mesh import MeshConfig

__all__ = ['DistributedStrategy']


class DistributedStrategy(object):
    """Axis sizes plus engine knobs.

    dp/tp/sp/pp/ep: parallel degrees (product must divide device count)
    sharded_optimizer, sharded_params: accepted, as scripts pass them,
        and read by nothing. With dp > 1 ParallelExecutor shards what
        an optimizer op updates over dp by itself (state_sharding:
        accumulators AND parameters on their first dimension that
        divides, per-device memory for them drops ~dp-fold, GSPMD
        gathers a weight where it is used), which is all that either
        (the reference BuildStrategy.kReduce analog, and ZeRO-3-style
        parameter sharding on top) was for. What no dimension of
        divides stays replicated.
    micro_batches: pipeline microbatch count, consumed by the pp engine
        (parallel/pipeline.py pipeline_apply's n_micro)
    """

    def __init__(self, dp=1, tp=1, sp=1, pp=1, ep=1,
                 sharded_optimizer=False, sharded_params=False,
                 micro_batches=1):
        self.dp, self.tp, self.sp, self.pp, self.ep = dp, tp, sp, pp, ep
        self.sharded_optimizer = sharded_optimizer or sharded_params
        self.sharded_params = sharded_params
        self.micro_batches = micro_batches

    def mesh_config(self, devices=None):
        return MeshConfig(devices=devices, dp=self.dp, tp=self.tp,
                          sp=self.sp, pp=self.pp, ep=self.ep)

    @property
    def world_size(self):
        return self.dp * self.tp * self.sp * self.pp * self.ep
