"""Interleaved in-process A/B of the flash FORWARD arms.

Round-6 measurement for the stored-lse two-pass forward (ROADMAP item
4; PERF.md round 6): pass 1 sweeps K computing only row max + lse,
pass 2 recomputes p = exp(s - lse) with ONE exp per element and
accumulates p @ v rescale-free — the online arm's running-max/corr/
rescale VPU chain disappears in exchange for a second (streaming) K
read. This tool ranks online vs twopass with the same discipline as
tools/flash_bwd_arms.py: every arm in ONE process, alternated across
rounds, in-jit N/2N forward-only loops differenced to cancel per-sync
constants, and `_RESOLVED_FWD_ARM` cross-checked before any sample is
ranked so a guard-swapped arm can never pollute its label's column.

    python tools/flash_fwd_arms.py [--ladder 512 2048 4096 8192 16384]
        [--bh 16] [--rounds 3] [--arms online twopass]
        [--blocks-q 0] [--blocks-k 0] [--quick]

Each arm's line is ms a call beside its share of the chip's bf16 peak
(flash_autotune.peak_share: the benchmark's own count). The training
cells' shape is `--ladder 2048 --bh 64` (4 sequences x 16 heads a
chip, d=128, bf16, causal); `--arms default` times what the shape
gets with nothing forced.

--blocks-q/--blocks-k force one block config for every arm (0 = each
arm's own tuned table). --quick is the tier-1 smoke: one tiny shape,
one round, CPU-interpret safe — it validates the harness end to end
(forcing, cache-clearing, cross-check, ranking), not chip timings.
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from flash_autotune import measure, peak_share  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--ladder', type=int, nargs='+',
                    default=[512, 2048, 4096, 8192, 16384])
    ap.add_argument('--d', type=int, default=128)
    ap.add_argument('--bh', type=int, default=16)
    ap.add_argument('--rounds', type=int, default=3)
    ap.add_argument('--arms', nargs='+',
                    default=['online', 'twopass'])
    ap.add_argument('--blocks-q', type=int, default=0)
    ap.add_argument('--blocks-k', type=int, default=0)
    ap.add_argument('--quick', action='store_true')
    args = ap.parse_args(argv)

    import paddle_tpu as fluid
    from paddle_tpu.pallas import flash_attention as flash

    # 'default' forces nothing: the arm and blocks the shape gets
    known = list(flash._FWD_ARMS[1:]) + ['default']
    bad = [a for a in args.arms if a not in known]
    if bad:
        raise SystemExit('unknown arm(s) %s: expected %s' % (bad, known))

    interpret = jax.default_backend() != 'tpu'
    if args.quick:
        # tier-1 smoke: smallest supported shape, single round, tiny
        # iter count — exercises the full harness path in seconds
        # (interpret mode off-chip, so the numbers mean nothing; the
        # point is the forcing/cross-check/ranking plumbing)
        args.ladder, args.bh, args.rounds = [256], 2, 1
    elif interpret:
        raise SystemExit('full A/B ladder needs a TPU backend '
                         '(interpret-mode timings rank the emulator); '
                         'use --quick for the harness smoke')

    if args.blocks_q or args.blocks_k:
        fluid.flags.set_flags({'FLAGS_flash_block_q': args.blocks_q,
                               'FLAGS_flash_block_k': args.blocks_k})

    saved_force = flash._FORCE_FWD_ARM
    any_ranked = False
    try:
        for T in args.ladder:
            rng = np.random.RandomState(0)
            q = jnp.asarray(rng.randn(args.bh, T, args.d),
                            jnp.bfloat16)
            k = jnp.asarray(rng.randn(args.bh, T, args.d),
                            jnp.bfloat16)
            v = jnp.asarray(rng.randn(args.bh, T, args.d),
                            jnp.bfloat16)

            results = {a: [] for a in args.arms}
            failed = set()
            for rnd in range(args.rounds):
                for arm in args.arms:
                    if arm in failed:
                        continue
                    # force by NAME — '' means "default", which
                    # dispatches online, so a '' spelling would rank
                    # online against itself
                    flash._FORCE_FWD_ARM = '' if arm == 'default' \
                        else arm
                    # the arm binds at TRACE time — stale traces must
                    # go
                    flash._fwd.clear_cache()
                    try:
                        ms = measure(flash, q, k, v,
                                     iters=2 if args.quick else 6,
                                     fwd_only=True,
                                     interpret=interpret)
                    except Exception as e:  # noqa: BLE001 — VMEM OOM
                        failed.add(arm)
                        print('T=%-6d round %d  %-8s FAILED (%.80s)'
                              % (T, rnd, arm, str(e)), flush=True)
                        continue
                    if arm == 'default':
                        print('T=%-6d round %d  default is %s %s'
                              % (T, rnd, flash._RESOLVED_FWD_ARM,
                                 flash._RESOLVED_FWD_BLOCKS), flush=True)
                    elif flash._RESOLVED_FWD_ARM != arm:
                        # the residency guard swapped the forced arm —
                        # ranking the substitute under this label
                        # would corrupt the table (a guarded twopass
                        # silently becomes online)
                        failed.add(arm)
                        print('T=%-6d round %d  %-8s SKIPPED (guard '
                              'dispatched %r for this shape)'
                              % (T, rnd, arm,
                                 flash._RESOLVED_FWD_ARM), flush=True)
                        continue
                    results[arm].append(ms)
                    print('T=%-6d round %d  %-8s %.2f ms'
                          % (T, rnd, arm, ms), flush=True)
            arms = [a for a in args.arms
                    if results[a] and a not in failed]
            if not arms:
                print('\nT=%d: every arm failed — nothing to rank' % T)
                continue
            any_ranked = True
            ranked = sorted(
                arms, key=lambda a: statistics.median(results[a]))
            base = statistics.median(results[arms[0]])
            print('\nBH=%d T=%d d=%d bf16 causal, forward, ms a call\n'
                  '| arm | median ms | spread | vs %s | %% of peak |'
                  % (args.bh, T, args.d, arms[0]))
            print('|---|---|---|---|---|')
            for a in ranked:
                ms = results[a]
                med = statistics.median(ms)
                print('| %s | %.3f | %.3f-%.3f | %+.1f%% | %.1f |'
                      % (a, med, min(ms), max(ms), (med / base - 1) * 100,
                         peak_share(med, args.bh, T, args.d)))
            print()
    finally:
        flash._FORCE_FWD_ARM = saved_force
        flash._fwd.clear_cache()
        if args.blocks_q or args.blocks_k:
            fluid.flags.set_flags({'FLAGS_flash_block_q': 0,
                                   'FLAGS_flash_block_k': 0})
    if not any_ranked:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
