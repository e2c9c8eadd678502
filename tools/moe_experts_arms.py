"""The held experts' part of op moe_experts alone, on the chip, one
process: the batched product over the whole held stack
(ops/moe_ops.held_experts / held_gated_experts, what a prefill chunk's
rows take and every row off a TPU) against the kernel that reads the
touched experts only (pallas/moe_experts.py, what a step's rows take on
a TPU), at the six expert cells' shapes (five decode steps of 32-64
rows and SDAR's block step of 32 x 4), with every held expert touched
and with the share of them a step of the cell touches (ledger, PRs 53
and 57; SDAR's second share is its light load's). Prints ms a call, the
bytes read (the whole stack for the product, the touched experts for the
kernel) as a share of the chip's HBM peak, and each arm's distance from
the same sum at Precision.HIGHEST.

    python tools/moe_experts_arms.py [--cells solar2,nemo3s] [--tiles 128,256]
        [--rows 16] [--quick]

--tiles times the kernel at those tile widths beside the one the shapes
give; --rows overrides the cell's slots. --quick walks the same code here
on the CPU at a tiny size (interpret mode: the harness, not a time).
"""
import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_S = 819e9     # one v5e chip (benchmarks/harness/peaks.py)

# cell: (rows, L, F, held, matrices, act, the shares of the held experts a
# step touches: the cell's, then a lighter load's where one was read)
CELLS = {
    'solar2': (48, 4096, 1280, 10, 3, 'silu', (0.235,)),
    'nemo3s': (64, 1024, 2688, 64, 2, 'relu2', (0.654,)),
    'axk1': (32, 7168, 2048, 8, 3, 'silu', (0.66,)),
    'granite4hs': (64, 4096, 768, 9, 3, 'silu', (0.859,)),
    'sthink21b': (64, 2560, 768, 64, 3, 'relu', (0.911,)),
    'sdar30b': (128, 2048, 768, 16, 3, 'silu', (0.78, 0.53)),
}
QUICK = {'solar2': (16, 256, 256, 5, 3, 'silu', (0.4,)),
         'nemo3s': (8, 128, 384, 6, 2, 'relu2', (0.5,)),
         'sdar30b': (32, 128, 256, 4, 3, 'silu', (0.75, 0.5))}


def _weights(rng, rows, held, touched):
    """w [rows, held]: `touched` experts, chosen at random, each by one
    to three rows."""
    import numpy as np
    w = np.zeros((rows, held), 'f4')
    for e in rng.choice(held, touched, replace=False):
        mine = rng.choice(rows, rng.integers(1, 4), replace=False)
        w[mine, e] = rng.uniform(0.05, 0.5, len(mine))
    return w


def _ms(fn, *args, calls=40):
    """ms a call of the jitted `fn`, `calls` of them dispatched one behind
    the other and waited for once: the device runs them back to back (a
    call's dispatch, tens of microseconds, hides behind the call before
    it). Not a loop inside one program: the compiler would round the
    stack to bfloat16 once, outside the loop, and every pass would read
    half the bytes."""
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--cells', default=','.join(CELLS))
    ap.add_argument('--tiles', default='')
    ap.add_argument('--rows', type=int, default=0)
    ap.add_argument('--quick', action='store_true')
    args = ap.parse_args()
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.obs import perf
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.pallas import moe_experts as me
    if not args.quick:
        perf.require_tpu()
    cells = QUICK if args.quick else CELLS
    tiles = [int(t) for t in args.tiles.split(',') if t]
    rng = np.random.default_rng(0)
    for name in args.cells.split(','):
        if name not in cells:
            continue
        rows, L, F, held, matrices, act, shares = cells[name]
        rows = args.rows or rows
        lat = jnp.asarray(rng.normal(size=(rows, L)), jnp.float32)
        key = jax.random.PRNGKey(1)
        mats = [jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[1])
                for k, shape in zip(
                    jax.random.split(key, 3),
                    [(held, L, F), (held, L, F), (held, F, L)])]
        stack = (mats[0], mats[1] if matrices == 3 else None, mats[2])
        del mats
        expert_bytes = matrices * L * F * 4

        def product(x, w, stack):
            w1, w3, w2 = stack
            if w3 is None:
                return moe_ops.held_experts(x, w, w1, w2)
            return moe_ops.held_gated_experts(x, w, w1, w3, w2, act)

        def kernel(x, w, stack, tile=None):
            w1, w3, w2 = stack
            ids, n = me.touched_ids(jnp.any(w != 0, axis=0))
            return me.moe_experts(x, w, ids, n, w1, w3, w2, act=act,
                                  tile=tile, interpret=args.quick)

        loads = [('all', held)] + [
            (label, max(1, round(share * held)))
            for label, share in zip(('cell', 'light'), shares)]
        for label, touched in loads:
            w = jnp.asarray(_weights(rng, rows, held, touched))
            with jax.default_matmul_precision('highest'):
                exact = np.asarray(jax.jit(product)(lat, w, stack))
            scale = np.abs(exact).max()
            arms = [('product', product, held)] + [
                ('kernel tf=%d' % (t or me.tile_width(L, F, matrices)),
                 functools.partial(kernel, tile=t), touched)
                for t in [None] + [t for t in tiles if F % t == 0]]
            for arm, fn, read in arms:
                fn = jax.jit(fn)
                err = np.abs(np.asarray(fn(lat, w, stack)) - exact).max()
                ms = _ms(fn, lat, w, stack)
                print('%-11s rows %d L %d F %d held %d  %-5s touched %2d  '
                      '%-14s %7.3f ms  %5.1f %% of the HBM peak  err %.1e'
                      % (name, rows, L, F, held, label, touched, arm, ms,
                         100 * read * expert_bytes / (ms / 1e3) / HBM_BYTES_S,
                         err / scale), flush=True)
        del stack


if __name__ == '__main__':
    main()
