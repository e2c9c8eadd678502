"""Measure the Pallas fused matmul+BN-stats kernel vs the unfused XLA
path on the real chip.

Two levels:
1. micro: the (y, colsum, colsumsq) primitive at ResNet-50 1x1-conv
   shapes (the bandwidth-bound early stages PERF.md names);
2. model: full framework ResNet-50 train step, FLAGS_use_pallas_fused_ops
   on vs off.

Sync discipline: every timed region ends with one host fetch.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def sync(x):
    np.asarray(jax.device_get(jax.tree_util.tree_leaves(x)[0]
                              .ravel()[:1]))


def time_fn(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / iters


def micro():
    import paddle_tpu as fluid
    from paddle_tpu.pallas.conv_bn import _pallas_impl, _xla_impl
    rng = np.random.RandomState(0)
    # (M, K, N): ResNet-50 bs256 1x1 convs by stage
    shapes = [
        (256 * 56 * 56, 64, 256),     # stage1 expand
        (256 * 56 * 56, 256, 64),     # stage1 reduce
        (256 * 28 * 28, 512, 128),    # stage2 reduce
        (256 * 14 * 14, 1024, 256),   # stage3 reduce
        (256 * 7 * 7, 2048, 512),     # stage4 reduce
    ]
    print('%-28s %10s %10s %7s' % ('shape (M,K,N)', 'xla ms', 'pallas ms',
                                   'speedup'))
    for M, K, N in shapes:
        x = jnp.asarray(rng.rand(M, K).astype(np.float32),
                        dtype=jnp.bfloat16)
        w = jnp.asarray(rng.rand(K, N).astype(np.float32) * 0.1,
                        dtype=jnp.bfloat16)
        xla = jax.jit(_xla_impl)
        t_x = time_fn(xla, x, w)
        t_p = time_fn(lambda a, b: _pallas_impl(a, b), x, w)
        # numerics spot check
        y1, s1, q1 = xla(x, w)
        y2, s2, q2 = _pallas_impl(x, w)
        serr = float(jnp.max(jnp.abs(s1 - s2) / (jnp.abs(s1) + 1e3)))
        print('%-28s %10.3f %10.3f %6.2fx  (s rel err %.1e)'
              % ((M, K, N), t_x * 1e3, t_p * 1e3, t_x / t_p, serr))


def model():
    """Full ResNet-50 train step fused vs unfused — exactly bench.py's
    measurement path (py_reader device-resident feed, AMP decorate,
    ParallelExecutor, async loop), flag toggled between runs."""
    import paddle_tpu as fluid
    import bench
    from paddle_tpu import unique_name
    from paddle_tpu.framework import (Program, switch_main_program,
                                      switch_startup_program)
    results = {}
    for fused in (False, True):
        fluid.set_flags({'use_pallas_fused_ops': fused})
        unique_name.switch()
        switch_main_program(Program())
        switch_startup_program(Program())
        out = bench.bench_resnet()
        results[fused] = out['value']
        print('fused=%s: %s img/s (mfu %s)'
              % (fused, out['value'], out.get('mfu')), flush=True)
    print('model speedup: %.3fx' % (results[True] / results[False]))


if __name__ == '__main__':
    which = sys.argv[1] if len(sys.argv) > 1 else 'micro'
    print('backend:', jax.default_backend(), jax.devices()[0].device_kind)
    if which in ('micro', 'all'):
        micro()
    if which in ('model', 'all'):
        model()
