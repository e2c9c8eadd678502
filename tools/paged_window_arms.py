"""The sliding layers' decode attention alone, on the chip, one process:
the Pallas kernel with a window (pallas/paged_attention.paged_attention
with window > 0: a lane's walk starts at its window's first page) against the
op's reference lowering (gather the whole table, mask a band) at 4 K/V
heads under 28 query heads, over lanes and contexts, beside the same
kernel without a window over the full table. Prints ms a call, the
largest difference, and the rate at which the kernel reads the rows it
has to (K and V of the live window, benchmarks/harness/
costs_smallthinker.kv_bytes_per_token) against the HBM peak.

    python tools/paged_window_arms.py [--window 4096] [--quick]

--quick walks the same code here on the CPU at a tiny size (interpret
mode: the harness, not a time).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, *args, reps=20):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--window', type=int, default=4096)
    ap.add_argument('--quick', action='store_true')
    args = ap.parse_args(argv)
    if args.quick:
        os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.attention_ops import _paged_attention_reference
    from paddle_tpu.pallas import paged_attention as pa
    H, KVH, dh, pt = 28, 4, 128, 16
    window = 32 if args.quick else args.window
    cases = [(2, 20), (3, 70)] if args.quick else \
        [(1, 300), (1, 15000), (8, 4090), (30, 300), (30, 8000),
         (48, 300), (48, 4096), (48, 15000)]
    reps = 1 if args.quick else 20
    hbm = 819e9
    rng = np.random.default_rng(0)
    for lanes, tokens in cases:
        pos = np.full((lanes,), tokens - 1, np.int32)
        wide = -(-(window + 256) // pt) + 1          # a window table's width
        first = np.maximum(0, pos - window + 1) // pt
        held = int((pos // pt - first).max()) + 1
        n_pages = lanes * held + 1
        k, v = (jnp.asarray(rng.standard_normal(
            (n_pages, pt, KVH, dh)).astype('f4')) for _ in 'kv')
        q = jnp.asarray(rng.standard_normal((lanes, H, dh)).astype('f4'))
        table = np.zeros((lanes, max(wide, held)), np.int32)
        for s in range(lanes):
            table[s, :held] = 1 + s * held + np.arange(held)
        rel = jnp.asarray(pos - first * pt)          # from the table's row 0
        table = jnp.asarray(table)
        kernel = jax.jit(lambda *a: pa.paged_attention(
            *a, sm_scale=dh ** -0.5, window=window,
            interpret=args.quick))
        lowered = jax.jit(lambda q, k, v, t, p: _paged_attention_reference(
            q[:, None], k, v, t, p, dh ** -0.5, lambda x: x, window)[:, 0])
        t_k, got = _time(kernel, q, k, v, table, rel, reps=reps)
        t_l, want = _time(lowered, q, k, v, table, rel, reps=reps)
        rows = int(np.minimum(pos + 1, window).sum())
        need = rows * 2 * KVH * dh * 4
        print('lanes %2d tokens %5d window %d: kernel %.3f ms (%.0f GB/s of '
              'the live window, %.0f %% of the HBM peak), lowering %.3f ms; '
              'max |diff| %.2e of %.2e'
              % (lanes, tokens, window, 1e3 * t_k, need / t_k / 1e9,
                 100 * need / t_k / hbm, 1e3 * t_l,
                 float(jnp.abs(got - want).max()),
                 float(jnp.abs(want).max())), flush=True)


if __name__ == '__main__':
    main(sys.argv[1:])
