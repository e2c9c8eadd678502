"""paddle_tpu: a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid (reference at /root/reference, see SURVEY.md).

Usage mirrors the reference's `import paddle.fluid as fluid`:

    import paddle_tpu as fluid
    x = fluid.layers.data(name='x', shape=[13])
    y_pred = fluid.layers.fc(input=x, size=1)
    ...
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    loss_val, = exe.run(feed={...}, fetch_list=[loss])

Architecture: a deferred Program/Block/Operator IR (framework.py) built by
layers, differentiated by backward.py, and compiled *whole-block* to XLA by
executor.py -- one jitted computation per training step, not per-op kernel
dispatch. Data parallelism is GSPMD sharding over a jax Mesh
(parallel_executor.py), not threaded op handles + NCCL.
"""
import os as _os

import jax as _jax

# The persistent XLA compile cache, placed from outside. JAX reads
# JAX_COMPILATION_CACHE_DIR itself, so when it is set nothing is set
# here; otherwise the cache sits at a fixed path beside the package
# (the path is part of the cache key: a directory that moves never
# hits). This is the only place the repository sets it.
if 'JAX_COMPILATION_CACHE_DIR' not in _os.environ:
    _jax.config.update('jax_compilation_cache_dir', _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        '.jax_cache'))

from . import ops            # registers all operators (import side effect)
from . import framework
from .framework import (Program, Block, Operator, Variable, Parameter,
                        default_main_program, default_startup_program,
                        program_guard, name_scope, grad_var_name,
                        get_var)
from . import layers
from . import initializer
from . import unique_name
from . import backward
from .backward import append_backward, calc_gradient  # noqa: F401
from . import optimizer
from . import regularizer
from . import clip
from .param_attr import ParamAttr, WeightNormParamAttr
from . import executor
from .executor import (Executor, Scope, global_scope, scope_guard,
                       _switch_scope, CPUPlace, TPUPlace, XLAPlace,
                       CUDAPlace, CUDAPinnedPlace, fetch_var)
from . import lod_tensor
from .lod_tensor import LoDTensor, create_lod_tensor, \
    create_random_int_lodtensor
Tensor = LoDTensor      # reference alias: fluid.Tensor is LoDTensor
                        # (pybind.cc binds Tensor as the LoD-less view)
from . import parallel
from . import reader
from .batch import batch  # noqa: F401
from . import dataset
from . import io
from . import nets
from . import metrics
from . import profiler
from .data_feeder import DataFeeder
from . import parallel_executor
from .parallel_executor import (ParallelExecutor, ExecutionStrategy,
                                BuildStrategy)
from . import core
from . import contrib
from . import transpiler
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig
from . import distributed
from . import checkpoint
from . import flags
from .flags import set_flags, get_flags
from . import recordio
from .recordio import (convert_reader_to_recordio_file,
                       convert_reader_to_recordio_files)
from . import memory
from . import channels
from .channels import make_channel
from . import trainer
from .trainer import (Trainer, CheckpointConfig, BeginEpochEvent,
                      EndEpochEvent, BeginStepEvent, EndStepEvent,
                      FaultEvent)
from . import average
from . import evaluator
from . import inferencer
from .inferencer import Inferencer
from . import annotations
from . import concurrency
from .concurrency import Go
from . import default_scope_funcs
from . import graphviz
from . import net_drawer
from . import op
from . import recordio_writer
from .transpiler import (InferenceTranspiler, memory_optimize,
                         release_memory)

__version__ = '0.1.0'

__all__ = [
    'Program', 'Block', 'Operator', 'Variable', 'Parameter',
    'default_main_program', 'default_startup_program', 'program_guard',
    'name_scope', 'grad_var_name', 'get_var', 'layers', 'initializer',
    'unique_name',
    'backward', 'append_backward', 'optimizer', 'regularizer', 'clip',
    'ParamAttr', 'WeightNormParamAttr', 'Executor', 'Scope', 'global_scope',
    'scope_guard', '_switch_scope', 'CPUPlace', 'TPUPlace', 'XLAPlace',
    'CUDAPlace',
    'fetch_var', 'LoDTensor', 'create_lod_tensor',
    'create_random_int_lodtensor', 'io', 'nets', 'metrics', 'profiler',
    'DataFeeder', 'ParallelExecutor', 'ExecutionStrategy', 'BuildStrategy',
    'core', 'average', 'evaluator', 'Inferencer', 'InferenceTranspiler',
    'memory_optimize', 'release_memory', 'Go',
]
