"""Per-layer ResNet-50 conv roofline ladder (VERDICT round-4 #1b).

Times every distinct conv shape of ResNet-50/224 alone — fwd + input/
weight grads, bf16, bs=256, in-jit lax.scan so the per-dispatch host
cost is excluded — and compares
each against ITS OWN roofline:

    t_roofline = max(flops / MXU_peak, bytes / HBM_BW)

so the report answers per layer whether XLA's conv is compute-bound,
bandwidth-bound, or leaving real time on the table. Run on the chip:

    python tools/conv_ladder.py [--batch 256]

Prints a markdown table (pasted into PERF.md round-4 ResNet section).
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

MXU_PEAK = 155e12      # measured chained-matmul ceiling (PERF.md), not spec
HBM_BW = 819e9         # v5e spec sheet

# (name, hw_in, cin, cout, k, stride, count_in_resnet50)
SHAPES = [
    ('stem 7x7/2', 224, 3, 64, 7, 2, 1),
    ('s1 in 1x1', 56, 64, 64, 1, 1, 3),
    ('s1 3x3', 56, 64, 64, 3, 1, 3),
    ('s1 out 1x1', 56, 64, 256, 1, 1, 3),
    ('s1 back 1x1', 56, 256, 64, 1, 1, 2),
    ('s1 proj', 56, 64, 256, 1, 1, 1),
    ('s2 down 1x1/2', 56, 256, 128, 1, 2, 1),
    ('s2 proj/2', 56, 256, 512, 1, 2, 1),
    ('s2 3x3', 28, 128, 128, 3, 1, 4),
    ('s2 out 1x1', 28, 128, 512, 1, 1, 4),
    ('s2 back 1x1', 28, 512, 128, 1, 1, 3),
    ('s3 down 1x1/2', 28, 512, 256, 1, 2, 1),
    ('s3 proj/2', 28, 512, 1024, 1, 2, 1),
    ('s3 3x3', 14, 256, 256, 3, 1, 6),
    ('s3 out 1x1', 14, 256, 1024, 1, 1, 6),
    ('s3 back 1x1', 14, 1024, 256, 1, 1, 5),
    ('s4 down 1x1/2', 14, 1024, 512, 1, 2, 1),
    ('s4 proj/2', 14, 1024, 2048, 1, 2, 1),
    ('s4 3x3', 7, 512, 512, 3, 1, 3),
    ('s4 out 1x1', 7, 512, 2048, 1, 1, 3),
    ('s4 back 1x1', 7, 2048, 512, 1, 1, 2),
]


def measure(jax, jnp, lax, B, hw, cin, cout, k, stride, iters=15):
    pad = k // 2
    hw_out = (hw + 2 * pad - k) // stride + 1
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(B, hw, hw, cin).astype('f4')) \
        .astype(jnp.bfloat16)
    w = jnp.asarray((rng.rand(k, k, cin, cout) - 0.5).astype('f4')) \
        .astype(jnp.bfloat16)

    def conv(x, w):
        # pure-bf16 conv: the MXU accumulates fp32 internally, and the
        # vjp needs matching operand dtypes
        return lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'))

    def loss(x, w):
        return conv(x, w).astype(jnp.float32).sum()

    def mk_loop(n):
        @jax.jit
        def loop(x, w):
            def body(carry, _):
                xc, wc = carry
                _, (gx, gw) = jax.value_and_grad(
                    loss, argnums=(0, 1))(xc, wc)
                return (xc + gx.astype(xc.dtype) * jnp.bfloat16(1e-12),
                        wc + gw.astype(wc.dtype) * jnp.bfloat16(1e-12)), \
                    None
            (xf, wf), _ = lax.scan(body, (x, w), None, length=n)
            return xf.astype(jnp.float32).sum() \
                + wf.astype(jnp.float32).sum()
        return loop

    # difference an N and a 3N loop: every fetch-terminated wall time
    # carries one per-sync constant; differencing cancels it exactly
    l1, l3 = mk_loop(iters), mk_loop(3 * iters)
    float(l1(x, w))
    float(l3(x, w))
    t0 = time.perf_counter()
    float(l1(x, w))
    w1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(l3(x, w))
    w3 = time.perf_counter() - t0
    dt = max(w3 - w1, 1e-9) / (2 * iters)

    flops = 3 * 2 * B * hw_out * hw_out * cout * cin * k * k  # fwd+bwd
    xbytes = 2 * B * hw * hw * cin
    obytes = 2 * B * hw_out * hw_out * cout
    wbytes = 2 * k * k * cin * cout
    # fwd: read x,w write o; dx: read go,w write dx; dw: read x,go write dw
    bytes_total = (xbytes + wbytes + obytes) + (obytes + wbytes + xbytes) \
        + (xbytes + obytes + wbytes)
    t_mxu = flops / MXU_PEAK
    t_hbm = bytes_total / HBM_BW
    t_roof = max(t_mxu, t_hbm)
    return dict(hw=hw, hw_out=hw_out, dt=dt, flops=flops,
                tf=flops / dt / 1e12, roof_ms=t_roof * 1e3,
                frac=t_roof / dt,
                bound='MXU' if t_mxu >= t_hbm else 'HBM')


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--batch', type=int, default=256)
    ap.add_argument('--from-idx', type=int, default=0)
    ap.add_argument('--to-idx', type=int, default=len(SHAPES))
    args = ap.parse_args()
    shapes = SHAPES[args.from_idx:args.to_idx]
    import jax
    import jax.numpy as jnp
    from jax import lax

    rows = []
    total_dt = total_roof = 0.0
    for name, hw, cin, cout, k, stride, count in shapes:
        r = measure(jax, jnp, lax, args.batch, hw, cin, cout, k, stride)
        rows.append((name, cin, cout, k, stride, r, count))
        total_dt += r['dt'] * count
        total_roof += r['roof_ms'] / 1e3 * count
        print('| %-14s | %4d->%4d k%d s%d | %7.2f ms | %6.1f TF/s | '
              '%6.2f ms | %4.0f%% | %s |'
              % (name, cin, cout, k, stride, r['dt'] * 1e3, r['tf'],
                 r['roof_ms'], 100 * r['frac'], r['bound']), flush=True)
    print('| TOTAL (counts) | | %.1f ms | | %.1f ms | %.0f%% | |'
          % (total_dt * 1e3, total_roof * 1e3, 100 * total_roof / total_dt))


if __name__ == '__main__':
    sys.exit(main())
