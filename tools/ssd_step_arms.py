"""Op ssd_step alone, on the chip, one process: the Pallas kernel that
walks the live lanes' state (pallas/ssd.py, what a decode step takes on
a TPU) against the plain composition (ops/ssd_ops.ssd_step_reference,
every other backend's path and the reference), at the two cells' shapes
(128 heads of [64, 128] float32, 4.19 MB a lane) and at live lanes of
none, a quarter, the cell's mean (ledger, PR 55) and all. Prints ms an
op and the live lanes' state, read once and written once, as a share
of the chip's HBM peak; for a kernel arm, how far its y stands from the
first kernel arm's.

    python tools/ssd_step_arms.py [--cells nemo3s,granite4hs]
        [--lives 0,27,64] [--parent DIR[,DIR]] [--blocks 32,64] [--quick]

Beside the kernel as it stands, two arms of it that separate the copies
from the compute: `walk`, the same grid, blocks and wrapper with a body
that hands each live block back as it came (what the copies and the
grid's steps cost), and `no sum`, the body with the sums along the
state's lanes left out. The kernel against its `walk` is what says
whether a body is hidden behind its copies: the compiler's static
schedule does not (PERF.md section 6, PR 56). --parent times the kernel
of another checkout's pallas/ssd.py beside them (the parent commit,
unpacked with `git archive`; several, with commas) and its `walk`;
--blocks the kernel and its `walk` at those heads a block beside what
the shapes give; --lives at those counts of live lanes.

The state is an ARGUMENT of a program that runs once, donated, so that
the kernel updates it where it lies: closed over, or looped over inside
one program, the compiler is free to move work out of the timed region
(PERF.md section 6, PR 54, two readings thrown away). A kernel's program
is `OPS` ops one behind the other on the one state, each with inputs of
its own, as a decode step's 5 or 9 layers are: a call of one op is over
before the host has dispatched the next (0.31 ms a call here), and what
was timed then was the host (PERF.md section 6, PR 56, one reading
thrown away). The composition is timed one op a call: it is longer than
a dispatch, and XLA could fuse a chain of it.

--quick walks the same code here on the CPU at a tiny size (interpret
mode: the harness, not a time).
"""
import argparse
import functools
import importlib.util
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_S = 819e9     # one v5e chip (benchmarks/harness/peaks.py)

# cell: (slots, H, P, N, groups, lanes live in a step of the cell)
CELLS = {'nemo3s': (64, 128, 64, 128, 8, 27),
         'granite4hs': (32, 128, 64, 128, 1, 15)}
QUICK = {'nemo3s': (4, 8, 8, 128, 2, 2),
         'granite4hs': (4, 4, 8, 128, 1, 2)}
OPS = 6                 # ops a program of a kernel arm


def _ms(fn, state, *args, calls=40):
    """ms a call of the jitted `fn`, which takes the state first, donated,
    and returns (y, state): `calls` of them dispatched one behind the other,
    each on the state the one before left, and waited for once."""
    import jax
    y, state = fn(state, *args)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(calls):
        y, state = fn(state, *args)
    jax.block_until_ready((y, state))
    return (time.perf_counter() - t0) / calls * 1e3, y, state


def _walk_body(idx_ref, n_ref, *refs, **static):
    """A body for either kernel's grid (head blocks, lanes): a live step
    hands its state block back as it came and writes zeros for y. The
    state's block in is the first of the largest refs, y and the state's
    block out follow it."""
    from jax.experimental import pallas as pl
    import jax.numpy as jnp
    import numpy as np
    first = int(np.argmax([np.prod(r.shape) for r in refs]))
    s_ref, o_ref, so_ref = refs[first:first + 3]

    @pl.when(pl.program_id(1) < n_ref[0])
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _load(path, k):
    """pallas/ssd.py of another checkout, as a module of this package (its
    relative imports are this tree's)."""
    spec = importlib.util.spec_from_file_location(
        'paddle_tpu.pallas._other_ssd_%d' % k, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--cells', default=','.join(CELLS))
    ap.add_argument('--parent', default='')
    ap.add_argument('--blocks', default='')
    ap.add_argument('--quick', action='store_true')
    ap.add_argument('--lives', default='')
    args = ap.parse_args()
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.obs import perf
    from paddle_tpu.ops import ssd_ops
    from paddle_tpu.pallas import ssd
    if not args.quick:
        perf.require_tpu()
    cells = QUICK if args.quick else CELLS
    kernels = [('kernel', ssd)]
    for k, other in enumerate(d for d in args.parent.split(',') if d):
        kernels.append((os.path.basename(os.path.normpath(other)), _load(
            os.path.join(other, 'paddle_tpu', 'pallas', 'ssd.py'), k)))

    def composition(state, x, b, c, dt, log_a, d, live):
        return ssd_ops.ssd_step_reference(state, x[0], b[0], c[0], dt[0],
                                          log_a[0], d, live)

    def kernel(mod, state, x, b, c, dt, log_a, d, live):
        y = 0.0
        for k in range(OPS):
            y_k, state = mod.ssd_step.__wrapped__(
                state, x[k], b[k], c[k], dt[k], jnp.exp(log_a[k]), d, live,
                interpret=args.quick)
            y = y + y_k
        return y, state

    rng = np.random.default_rng(0)
    for name in args.cells.split(','):
        if name not in cells:
            continue
        S, H, P, N, G, mean = cells[name]
        arms = [('composition', composition, ())]

        def arm(label, mod, *patches):
            # a partial of its own: jit caches by the function it is handed
            arms.append((label, functools.partial(kernel, mod), patches))

        def walking(mod):
            return mock.patch.object(mod, '_kernel', _walk_body)

        for label, mod in kernels:
            arm(label, mod)
            arm(label + ' walk', mod, walking(mod))
            if hasattr(mod, '_lane_sums'):
                arm(label + ' no sum', mod, mock.patch.object(
                    mod, '_lane_sums', lambda tiles: tiles[0]))
        for hb in (int(v) for v in args.blocks.split(',') if v):
            block = mock.patch.object(ssd, 'heads_per_block',
                                      lambda *a, hb=hb: hb)
            arm('kernel hb=%d' % hb, ssd, block)
            arm('kernel hb=%d walk' % hb, ssd, block, walking(ssd))
        lane_bytes = H * P * N * 4
        f32 = jnp.float32
        x = jnp.asarray(rng.normal(size=(OPS, S, H, P)), f32)
        b = jnp.asarray(rng.normal(size=(OPS, S, G, N)), f32)
        c = jnp.asarray(rng.normal(size=(OPS, S, G, N)), f32)
        dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(OPS, S, H)), f32)
        log_a = -dt * jnp.asarray(rng.uniform(1, 16, size=H), f32)
        d = jnp.asarray(rng.normal(size=H), f32)
        lives = [int(v) for v in args.lives.split(',') if v] \
            or sorted({0, S // 4, mean, S})
        for n in lives:
            live = np.zeros(S, bool)
            live[rng.choice(S, n, replace=False)] = True
            live = jnp.asarray(live)
            want = None
            for arm, fn, patches in arms:
                # a patched body is traced under its patch
                jitted = jax.jit(fn, donate_argnums=(0,))
                state = jax.random.normal(jax.random.PRNGKey(1),
                                          (S, H, P, N), f32)
                for p in patches:
                    p.start()
                try:
                    ms, y, _ = _ms(jitted, state, x, b, c, dt, log_a, d,
                                   live, calls=2 if args.quick else 20)
                    if fn is not composition:
                        ms /= OPS
                finally:
                    for p in patches:
                        p.stop()
                err = ''
                if not any(v in arm
                           for v in ('walk', 'no sum', 'composition')):
                    # the same ops on the same state
                    y = np.asarray(y)[np.asarray(live)]
                    if want is None:
                        want = y
                    elif n:
                        err = '  y off by %.1e' % (
                            np.abs(y - want).max() / np.abs(want).max())
                print('%-10s slots %2d groups %d  live %2d  %-18s %7.3f ms  '
                      '%5.1f %% of the HBM peak%s'
                      % (name, S, G, n, arm, ms,
                         100 * 2 * n * lane_bytes / (ms / 1e3) / HBM_BYTES_S,
                         err), flush=True)
                del state, y


if __name__ == '__main__':
    main()
