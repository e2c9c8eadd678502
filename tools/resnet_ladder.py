"""In-model per-layer ResNet-50 ladder (VERDICT round-4 #1b).

Profiles the REAL bench training step (not isolated kernels — an
earlier standalone harness over-counted by ~2x from per-shape scan
overhead) and attributes device time to IR convs through the round-4
named_scope/HLO-metadata join (profiler.hlo_op_map). Each conv's
measured fwd+bwd time is compared against its own roofline
max(flops/MXU_peak, bytes/HBM_BW). Run on the chip:

    python tools/resnet_ladder.py [--batch 256]
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from collections import defaultdict

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), '..'))

MXU_PEAK = 155e12
HBM_BW = 819e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--batch', type=int, default=256)
    ap.add_argument('--space-to-depth', action='store_true')
    args = ap.parse_args()

    import jax
    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from paddle_tpu.models import resnet

    fluid.flags.set_flags({'FLAGS_amp_bf16_param_grads': True})
    batch, hw, class_dim = args.batch, 224, 1000
    main_prog, startup_prog = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup_prog):
        image = fluid.layers.data(name='image', shape=[3, hw, hw],
                                  dtype='float32')
        label = fluid.layers.data(name='label', shape=[1], dtype='int64')
        _, avg_cost, _ = resnet.train_network(
            image, label, class_dim=class_dim, depth=50,
            space_to_depth=args.space_to_depth)
        opt = fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9)
        opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(avg_cost)

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup_prog)
    pe = fluid.ParallelExecutor(use_cuda=True, loss_name=avg_cost.name,
                                main_program=main_prog)
    rng = np.random.RandomState(0)
    img = jax.device_put(rng.rand(batch, 3, hw, hw).astype('float32'))
    lbl = jax.device_put(rng.randint(0, class_dim, (batch, 1))
                         .astype('int64'))
    feed = {'image': img, 'label': lbl}
    for _ in range(3):
        wl = pe.run(fetch_list=[avg_cost.name], feed=feed,
                    return_numpy=False)
    float(np.asarray(wl[0]))

    def timed(n):
        t0 = time.perf_counter()
        for _ in range(n):
            l = pe.run(fetch_list=[avg_cost.name], feed=feed,
                       return_numpy=False)
        float(np.asarray(l[0]))
        return time.perf_counter() - t0

    # differencing cancels the per-fetch constant (bench._run_steps
    # uses the same pattern)
    w1 = timed(10)
    w2 = timed(20)
    step_ms = max(w2 - w1, 1e-9) / 10 * 1e3
    print('step: %.1f ms (%.0f img/s)' % (step_ms, batch / step_ms * 1e3))

    nsteps = 3
    with profiler.profiler('All', None, '/tmp/rn_ladder'):
        for _ in range(nsteps):
            l = pe.run(fetch_list=[avg_cost.name], feed=feed,
                       return_numpy=False)
        float(np.asarray(l[0]))

    # join device events to IR ops
    import glob
    texts = [open(f).read() for f in
             sorted(glob.glob('/tmp/rn_ladder.hlo/*.txt'))]
    op_map = profiler.hlo_op_map(texts)
    events = profiler.device_op_events('/tmp/rn_ladder.xplane', op_map)

    # op index -> conv descriptor from the program
    block = main_prog.global_block()
    conv_desc = {}
    for idx, op in enumerate(block.ops):
        if op.type in ('conv2d', 'conv2d_grad', 'depthwise_conv2d'):
            base = dict(op.attrs)
            x = block.var_recursive(op.single_input('Input'))
            w = block.var_recursive(op.single_input(
                'Filter' if op.input('Filter') else 'FilterParam'))
            conv_desc[idx] = (op.type, tuple(x.shape), tuple(w.shape),
                              base.get('strides', [1, 1])[0])

    per_layer = defaultdict(float)
    other = defaultdict(float)
    for label_, start, dur in events:
        parts = label_.rsplit('.', 1)
        if len(parts) == 2 and parts[1].isdigit() and \
                int(parts[1]) in conv_desc and 'conv' in parts[0]:
            idx = int(parts[1])
            typ, xs, ws, stride = conv_desc[idx]
            key = ('%dx%d %d->%d k%d s%d' % (
                xs[2], xs[3], ws[1], ws[0], ws[2], stride))
            per_layer[(key, typ)] += dur
        else:
            other[parts[0]] += dur

    total_dev = (sum(per_layer.values()) + sum(other.values())) / nsteps
    print('device total: %.1f ms/step' % (total_dev / 1e6))
    print('| shape | dir | ms/step | TF/s | roofline ms | % roof |')
    print('|---|---|---|---|---|---|')
    rows = sorted(per_layer.items(), key=lambda kv: -kv[1])
    for (key, typ), ns in rows:
        ms = ns / nsteps / 1e6
        hwp, ch, kk, ss = key.split(' ')
        hin = int(hwp.split('x')[0])
        cin, cout = (int(c) for c in ch.split('->'))
        k = int(kk[1:]); s = int(ss[1:])
        hout = hin // s
        mult = 1 if typ == 'conv2d' else 2      # grad op = dx + dw
        flops = mult * 2 * args.batch * hout * hout * cout * cin * k * k
        xb = 2 * args.batch * hin * hin * cin
        ob = 2 * args.batch * hout * hout * cout
        wb = 2 * k * k * cin * cout
        byts = mult * (xb + ob + wb)
        roof = max(flops / MXU_PEAK, byts / HBM_BW) * 1e3
        print('| %s | %s | %7.2f | %6.1f | %6.2f | %4.0f%% |'
              % (key, 'fwd' if typ == 'conv2d' else 'bwd', ms,
                 flops / (ms / 1e3) / 1e12, roof, 100 * roof / ms))
    print('--- non-conv classes (ms/step) ---')
    for k, v in sorted(other.items(), key=lambda kv: -kv[1])[:12]:
        print('  %-28s %8.2f' % (k, v / nsteps / 1e6))


if __name__ == '__main__':
    main()
