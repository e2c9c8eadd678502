"""The latent attention's shapes alone, on the chip, one process: the
decode kernel (pallas/paged_attention.paged_latent_attention) at a
serving step's lanes and contexts, and a prefill chunk's attention in
three forms of its sum (the kernel of pallas/latent_prefill.py, which
the program runs on a TPU; ops/latent_attention_ops.prefill_absorbed,
the same sum folded in plain XLA, its reference and the CPU's path; and
prefill_materialised here, which the program never had) behind 4 k and
12 k of context, 138 and 256 of the chunk's 256 rows live. Prints ms a
call and what that is of the chip's HBM or bf16 peak by the benchmark's
own count (benchmarks/harness/costs_axk1.py), and how many bytes the
chip gives a pool of 576-wide rows.

    python tools/mla_forms.py [--lanes 36] [--context 12288] [--tiles 8,16]
        [--prefill-blocks 32,64] [--quick]

--quick walks the same code here on the CPU at a tiny size (interpret
mode: the harness, not a time).
"""
import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def prefill_materialised(q, pool, table, positions, w_uk, w_uv, sm_scale,
                         block_tokens=1024):
    """The same sum with each block's keys and values made from its
    latent rows (through W_UK and W_UV) before the products, merged by
    log-sum-exp: fewer multiplies than the absorbed form (head size dn +
    dr and dv in place of dc + dr and dc), more traffic (a block's keys
    and values are H (dn + dv) / row times its latent)."""
    import jax
    import jax.numpy as jnp
    C, H = q.shape[:2]
    pt, row = pool.shape[1:]
    dc, dn = w_uk.shape[0], w_uk.shape[-1]
    dv = w_uv.shape[-1]
    bp = max(1, min(table.shape[0], block_tokens // pt))
    pages = -(-table.shape[0] // bp) * bp
    table = jnp.pad(table, (0, pages - table.shape[0]))
    qs = q * sm_scale
    q_c, q_r = qs[..., :dn], qs[..., dn:]
    dr = q_r.shape[-1]
    n_blocks = (positions[-1] // pt) // bp + 1

    def fold(i, carry):
        m, l, acc = carry                       # [H, C, 1], .., [H, C, dv]
        ids = jax.lax.dynamic_slice(table, (i * bp,), (bp,))
        blk = pool[ids].reshape(bp * pt, row)
        k_c = jnp.einsum('jc,chn->hjn', blk[:, :dc], w_uk)
        v = jnp.einsum('jc,chv->hjv', blk[:, :dc], w_uv)
        sc = jnp.einsum('thn,hjn->htj', q_c, k_c,
                        preferred_element_type=jnp.float32) \
            + jnp.einsum('thr,jr->htj', q_r, blk[:, dc:dc + dr],
                         preferred_element_type=jnp.float32)
        j = i * (bp * pt) + jnp.arange(bp * pt, dtype=jnp.int32)
        sc = jnp.where(j[None, None, :] <= positions[None, :, None], sc, -1e30)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        return (m_new, alpha * l + p.sum(axis=-1, keepdims=True),
                alpha * acc + jnp.einsum('htj,hjv->htv', p, v,
                                         preferred_element_type=jnp.float32))

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, fold,
        (jnp.full((H, C, 1), -1e30, jnp.float32),
         jnp.zeros((H, C, 1), jnp.float32),
         jnp.zeros((H, C, dv), jnp.float32)))
    return jnp.transpose(acc / l, (1, 0, 2)).astype(q.dtype)


def timed(fn, *args, n=20):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--lanes', type=int, default=36)
    ap.add_argument('--slots', type=int, default=48)
    ap.add_argument('--context', type=int, default=12288)
    ap.add_argument('--blocks', default='16,32,64')
    ap.add_argument('--tiles', default='',
                    help='chunk tokens a tile of the prefill kernel, '
                         'comma-separated (default: what the shapes give)')
    ap.add_argument('--prefill-blocks', default='',
                    help='pages a block of the prefill kernel')
    ap.add_argument('--quick', action='store_true')
    args = ap.parse_args(argv)
    if args.quick:
        os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import latent_attention_ops as lo
    from paddle_tpu.pallas import paged_attention as pa
    if not args.quick:
        from paddle_tpu.obs import perf
        perf.require_tpu()
    H, dn, dr, dv, dc, row, pt = 64, 128, 64, 128, 512, 640, 16
    S, lanes, ctx, chunk, reps = args.slots, args.lanes, args.context, 256, 20
    if args.quick:
        H, dn, dv, S, lanes, ctx, chunk, reps = 4, 32, 32, 4, 3, 200, 16, 1
        dc, row = 128, 256
    P = -(-(ctx + 1024) // pt)
    N = lanes * P + 2
    dev = jax.devices()[0]
    if not args.quick:
        before = dev.memory_stats()['bytes_in_use']
        probe = jax.block_until_ready(jnp.zeros((4096, pt, 576), jnp.float32))
        got = dev.memory_stats()['bytes_in_use'] - before
        print('a pool of 4096 pages x 16 x 576 float32 takes %d bytes: %.0f '
              'a row of 576 (2304 as needed)' % (got, got / 4096 / pt))
        del probe
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.standard_normal((N, pt, row), np.float32))
    table = np.zeros((S, P), np.int32)
    table[:lanes] = 1 + np.arange(lanes * P).reshape(lanes, P)
    positions = np.zeros((S,), np.int32)
    # contexts spread about the mean as the cell's documents are
    positions[:lanes] = np.linspace(0.7 * ctx, 1.3 * ctx, lanes).astype(int) \
        if lanes > 1 else ctx
    positions = np.minimum(positions, P * pt - 1)
    qa = jnp.asarray(rng.standard_normal((S, H, row), np.float32))
    table, positions = jnp.asarray(table), jnp.asarray(positions)
    rows = int(np.asarray(positions)[:lanes].sum()) + lanes
    need = rows * 4 * (dc + dr)
    for bp in (int(b) for b in args.blocks.split(',')):
        pa._LATENT_BLOCK_PAGES = bp
        fn = jax.jit(functools.partial(
            pa.paged_latent_attention.__wrapped__, sm_scale=0.13,
            value_dim=dc, interpret=args.quick))
        ms = timed(fn, qa, pool, table, positions, n=reps)
        print('decode kernel, %d lanes of %d, %d live rows, blocks of %d '
              'pages: %.3f ms a call, %.1f %% of the HBM peak for %d bytes'
              % (lanes, S, rows, bp, ms, 100 * need / 819e9 / (ms / 1e3),
                 need))
    ref = lo.decode_reference(qa, pool, table, positions, 0.13, dc) \
        if args.quick else None
    if ref is not None:
        got = fn(qa, pool, table, positions)
        print('kernel against the gathered window: %.2e'
              % float(jnp.abs(got - ref)[:lanes].max()))
    w_uk = jnp.asarray(rng.standard_normal((dc, H, dn), np.float32) / 20)
    w_uv = jnp.asarray(rng.standard_normal((dc, H, dv), np.float32) / 20)
    q = jnp.asarray(rng.standard_normal((chunk, H, dn + dr), np.float32))
    from paddle_tpu.pallas import latent_prefill as lp
    tiles = [int(t) for t in args.tiles.split(',')] if args.tiles \
        else [lp.tile_tokens(chunk, H)]
    pblocks = [int(b) for b in args.prefill_blocks.split(',')] \
        if args.prefill_blocks else [lp._BLOCK_PAGES]

    def kernel_form(tile, bp):
        def form(q, pool, t, p, n):
            u = lp.paged_latent_prefill.__wrapped__(
                lo.absorb_query(q, w_uk, row) * 0.13, pool, t, p[0], n,
                value_dim=dc, tile=tile, block_pages=bp,
                interpret=args.quick)
            return jnp.einsum('thc,chv->thv', u, w_uv)
        return form

    def whole(form):
        return lambda q, pool, t, p, n: form(q, pool, t, p, w_uk, w_uv, 0.13)

    forms = [('absorbed', whole(lo.prefill_absorbed)),
             ('materialised', whole(prefill_materialised))]
    forms += [('kernel, tiles of %d, blocks of %d pages' % (tile, bp),
               kernel_form(tile, bp)) for tile in tiles for bp in pblocks]
    fns = [(name, jax.jit(form)) for name, form in forms]
    lives = (138, chunk) if not args.quick else (5, chunk)
    for behind in ((ctx // 3, ctx) if not args.quick else (ctx,)):
        pos = behind + jnp.arange(chunk, dtype=jnp.int32)
        for live in lives:
            outs = {}
            for name, fn in fns:
                n = jnp.int32(live)
                ms = timed(fn, q, pool, table[0], pos, n, n=reps)
                outs[name] = fn(q, pool, table[0], pos, n)[:live]
                # the benchmark's count (costs_axk1.mla_prefill_flops):
                # the live rows against the context, absorbed
                flops = 2 * live * H * (behind + live) * (2 * dc + dr)
                print('prefill chunk of %d rows, %d live, behind %d, %s: '
                      '%.3f ms a call (%.1f %% of the bf16 peak for the '
                      'live rows by the absorbed count)'
                      % (chunk, live, behind, name, ms,
                         100 * flops / 197e12 / (ms / 1e3)))
            top = float(jnp.abs(outs['absorbed']).max())
            for name in list(outs)[1:]:
                print('  %s differs from absorbed by %.2e of %.2e'
                      % (name, float(jnp.abs(outs[name]
                                             - outs['absorbed']).max()), top))


if __name__ == '__main__':
    main(sys.argv[1:])
