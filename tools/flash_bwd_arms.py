"""Interleaved in-process A/B of the flash backward arms: the backward
alone, ms a call.

    python tools/flash_bwd_arms.py [--T 8192] [--bh 16] [--rounds 3]
        [--arms default split kvmajor] [--blocks-q 0] [--blocks-k 0]

Every arm in ONE process, alternated across rounds, in-jit N/2N loops
differenced to cancel per-sync constants (the discipline of
tools/flash_autotune.py). What is timed is `_flash_bwd` on the residual
`_flash_fwd` keeps -- delta and the kernel, no forward -- so a line
reads beside `flash_attention_grad` a layer in a training cell's
breakdown. `default` forces nothing and prints the arm and blocks the
shape got (`_RESOLVED_ARM` / `_RESOLVED_BWD_BLOCKS`; counter
`pallas.flash.bwd.<arm>`, one a trace of `_bwd`). --blocks-q/--blocks-k
force a block config (0 = the tuned table).

On this chip (PERF.md section 6, PR 41; bf16, causal, d=128; parent ->
this kernel): BH=64, T=2048 (the training cells' shape, 4 sequences x
16 heads a chip) 2.07-2.13 -> 1.24 ms at its (2048, 2048) entry, a head
in one grid step; BH=16, T=8192 at its (512, 1024) entry 6.65 -> 4.60;
BH=64, T=512 0.138 -> 0.135. The arms `split` and `onepass` have not
been ranked on this chip.
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys

import numpy as np

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(_TOOLS), _TOOLS]

import jax
import jax.numpy as jnp


from flash_autotune import measure  # noqa: E402 — same harness


def timed_bwd(res, do):
    """A loop builder for flash_autotune.measure: iters calls of the
    backward on one residual, the gradients folded into the carry so
    that none of the three is dead code."""
    def timed(flash, q, k, v, iters):
        @jax.jit
        def loop(q, k, v):
            def body(c, _):
                dq, dk, dv = flash._flash_bwd(
                    True, 0.0884, False, tuple(c) + tuple(res[3:]), do)
                eps = jnp.bfloat16(1e-12)
                return tuple(x + g * eps
                             for x, g in zip(c, (dq, dk, dv))), None
            (q, k, v), _ = jax.lax.scan(body, (q, k, v), None,
                                        length=iters)
            return q[0, 0, 0] + k[0, 0, 0] + v[0, 0, 0]
        return loop
    return timed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--T', type=int, default=8192)
    ap.add_argument('--d', type=int, default=128)
    ap.add_argument('--bh', type=int, default=16)
    ap.add_argument('--rounds', type=int, default=3)
    ap.add_argument('--arms', nargs='+',
                    default=['default', 'split', 'kvmajor'])
    ap.add_argument('--blocks-q', type=int, default=0)
    ap.add_argument('--blocks-k', type=int, default=0)
    args = ap.parse_args()

    import paddle_tpu as fluid
    from paddle_tpu.pallas import flash_attention as flash

    known = ('default',) + flash._BWD_ARMS[1:]
    bad = [a for a in args.arms if a not in known]
    if bad:
        raise SystemExit('unknown arm(s) %s: expected %s'
                         % (bad, list(known)))

    if args.blocks_q or args.blocks_k:
        fluid.flags.set_flags({'FLAGS_flash_block_q': args.blocks_q,
                               'FLAGS_flash_block_k': args.blocks_k})

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(args.bh, args.T, args.d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(args.bh, args.T, args.d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(args.bh, args.T, args.d), jnp.bfloat16)
    do = jnp.asarray(rng.randn(args.bh, args.T, args.d), jnp.bfloat16)
    _, res = flash._flash_fwd(q, k, v, True, 0.0884, False)

    results = {a: [] for a in args.arms}
    failed = set()
    for rnd in range(args.rounds):
        for arm in args.arms:
            if arm in failed:
                continue
            # force every arm but `default` by NAME
            flash._FORCE_ARM = '' if arm == 'default' else arm
            # the arm binds at TRACE time — stale traces must go
            flash._bwd.clear_cache()
            try:
                ms = measure(flash, q, k, v, iters=8,
                             timed=timed_bwd(res, do))
            except Exception as e:   # noqa: BLE001 — e.g. VMEM OOM
                failed.add(arm)
                print('round %d  %-8s FAILED (%.80s)'
                      % (rnd, arm, str(e)), flush=True)
                continue
            if arm == 'default':
                print('default is %s at blocks %s'
                      % (flash._RESOLVED_ARM,
                         getattr(flash, '_RESOLVED_BWD_BLOCKS', '?')),
                      flush=True)
            elif flash._RESOLVED_ARM != arm:
                # a residency guard swapped the forced arm — ranking
                # the substitute under this label would corrupt the
                # table (e.g. onepass>12MB silently becomes split)
                failed.add(arm)
                print('round %d  %-8s SKIPPED (guard dispatched %r '
                      'for this shape)' % (rnd, arm,
                                           flash._RESOLVED_ARM),
                      flush=True)
                continue
            results[arm].append(ms)
            print('round %d  %-8s %.3f ms' % (rnd, arm, ms),
                  flush=True)
    flash._FORCE_ARM = ''
    arms = [a for a in args.arms if results[a] and a not in failed]
    if not arms:
        print('\nevery arm failed — nothing to rank')
        return
    ranked = sorted(arms, key=lambda a: statistics.median(results[a]))
    base = statistics.median(results[arms[0]])
    print('\n| arm | median ms | spread | vs %s |' % arms[0])
    print('|---|---|---|---|')
    for a in ranked:
        ms = results[a]
        print('| %s | %.3f | %.3f-%.3f | %+.1f%% |'
              % (a, statistics.median(ms), min(ms), max(ms),
                 (statistics.median(ms) / base - 1) * 100))


if __name__ == '__main__':
    main()
