"""Probe: is exp2 cheaper than exp on this chip's VPU (Mosaic lowering)?

The flash kernel's dominant VPU cost is jnp.exp over [bq, bk] score
blocks (PERF.md round-4 flash ladder). If the hardware exponent unit
makes 2^x cheaper than e^x, folding log2(e) into the softmax scale
converts every exp site to exp2 for free. This probe times a chain of
dependent exp/exp2 applications on a VMEM-resident block inside one
pallas_call (chain-length differencing cancels launch + load/store), on
the real chip.

Run: python tools/probe_exp2.py
"""
from __future__ import annotations

import functools
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, o_ref, *, reps, fn):
    x = x_ref[...]
    for _ in range(reps):
        # keep the argument in a range where neither overflows; the
        # subtraction keeps a data dependence so Mosaic cannot hoist
        x = fn(-(x * 0.5 + 0.25))
    o_ref[...] = x


BLOCKS, BQ, BK, ITERS = 64, 512, 512, 20


def _run(fn, reps, blocks=BLOCKS, bq=BQ, bk=BK, iters=ITERS):
    x = jnp.asarray(
        np.random.RandomState(0).rand(blocks, bq, bk).astype('f4'))
    call = pl.pallas_call(
        functools.partial(_kernel, reps=reps, fn=fn),
        grid=(blocks,),
        in_specs=[pl.BlockSpec((1, bq, bk), lambda b: (b, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, bq, bk), lambda b: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )

    @jax.jit
    def loop(x):
        def body(c, _):
            return call(c), None
        y, _ = jax.lax.scan(body, x, None, length=iters)
        # the scalar fetch waits for the device
        return y[0, 0, 0]

    np.asarray(loop(x))
    t0 = time.perf_counter()
    np.asarray(loop(x))
    return time.perf_counter() - t0


def main():
    print('backend:', jax.default_backend())
    for name, fn in [('exp', jnp.exp), ('exp2', jnp.exp2)]:
        t1 = _run(fn, reps=4)
        t2 = _run(fn, reps=8)
        per_rep = (t2 - t1) / 4  # 4 extra reps between the two runs
        elems = ITERS * BLOCKS * BQ * BK
        print('%s: 4rep %.4fs  8rep %.4fs  -> %.3f ns/elem  %.1f Gexp/s'
              % (name, t1, t2, per_rep / elems * 1e9,
                 elems / per_rep / 1e9))


if __name__ == '__main__':
    main()
