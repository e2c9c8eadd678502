"""Program transpilers (reference python/paddle/fluid/transpiler/).

DistributeTranspiler rewrites a local program into trainer + pserver
programs for parameter-server mode. InferenceTranspiler folds
batch-norm into convs for deployment. DecodeTranspiler turns a loaded
decoder-only LM into a KV-cached prefill + decode program pair for the
serving engine (paddle_tpu/serving/). The memory-optimize transpiler
computes the reference's liveness/reuse plan while delegating actual
buffer sharing to XLA buffer assignment (see its module docstring).
"""
from .distribute_transpiler import (DistributeTranspiler,
                                    DistributeTranspilerConfig)
from .ps_dispatcher import PSDispatcher, RoundRobin, HashName
from .inference_transpiler import InferenceTranspiler
from .decode_transpiler import (DecodeTranspiler, DecodeTranspileError,
                                PagedDecodePair, extract_decode_spec)
from .memory_optimization_transpiler import (memory_optimize,
                                             release_memory)

__all__ = ['DistributeTranspiler', 'DistributeTranspilerConfig',
           'PSDispatcher', 'RoundRobin', 'HashName',
           'InferenceTranspiler', 'DecodeTranspiler',
           'DecodeTranspileError', 'PagedDecodePair', 'extract_decode_spec',
           'memory_optimize', 'release_memory']
