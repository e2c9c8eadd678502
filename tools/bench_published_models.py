"""Train-throughput of the reference's PUBLISHED benchmark models
(BASELINE.md tables: AlexNet / GoogleNet / VGG / ResNet-50) on the
real chip, through the full framework path — the direct
"reference's own headline benchmarks" comparison.

Feeds are pre-placed device arrays (a per-step 154 MB host feed would
measure the host-to-device copy, not the framework), timing is async
N/2N differenced.

    python tools/bench_published_models.py [--models alexnet googlenet]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# (batch, published img/s or ms/batch note) from BASELINE.md
CONFIGS = {
    'alexnet': dict(bs=128, published='334 ms/batch (383 img/s) K40m; '
                                      '627 img/s 2xXeon6148'),
    # benchmark/README.md:33-38 also publishes the bs=512 point
    'alexnet512': dict(bs=512, net='alexnet',
                       published='1629 ms/batch K40m (bs=512)'),
    'googlenet': dict(bs=128, published='1149 ms/batch (111 img/s) '
                                        'K40m; 270 img/s 2xXeon6148'),
    # 'vgg' is the depth-16 benchmark-suite model — NOT head-to-head
    # with the published number (which is VGG-19; see the vgg19 row)
    'vgg': dict(bs=64, published='(vgg16; published row is vgg19)'),
    'vgg19': dict(bs=64, published='30.44 img/s 2xXeon6148'),
    'resnet': dict(bs=256, published='84 img/s 2xXeon6148'),
    # benchmark/README.md:53-59 "SmallNet" (the caffe cifar10_quick
    # net, benchmark/paddle/image/smallnet_mnist_cifar.py): 32x32x3,
    # conv5/32 maxpool conv5/32 avgpool conv3/64 avgpool fc64 fc10
    'smallnet': dict(bs=256, published='33.1 ms/batch K40m (bs=256)'),
    # benchmark/README.md:113-120 "RNN / LSTM in Text Classification":
    # IMDB padded to T=100, dict 30000, 2 lstm layers + fc, peepholes,
    # hidden 512, bs 64 -> 184 ms/batch on the v0.9 K40m stack
    # (reference net: benchmark/paddle/rnn/rnn.py — emb 128,
    # lstm_num x simple_lstm, last_seq, fc softmax)
    'lstm': dict(bs=64, published='184 ms/batch K40m (h=512 bs=64)'),
}


def bench_model(model, bs, steps=12):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.models import alexnet, googlenet, vgg, resnet

    builders = {
        'alexnet': lambda i, l: alexnet.train_network(
            i, l, class_dim=1000),
        'googlenet': lambda i, l: googlenet.train_network(
            i, l, class_dim=1000),
        'vgg': lambda i, l: vgg.train_network(i, l, class_dim=1000),
        'vgg19': lambda i, l: vgg.train_network(i, l, class_dim=1000,
                                                depth=19),
        'resnet': lambda i, l: resnet.train_network(
            i, l, class_dim=1000, depth=50),
    }
    def lstm_text_class(words, lbl, hidden=512, lstm_num=2,
                        vocab=30000):
        """The published RNN row's net (reference
        benchmark/paddle/rnn/rnn.py): emb(128) -> lstm_num x
        [input proj + lstmemory(peepholes)] -> last_seq -> fc(2,
        softmax). simple_lstm's full-matrix input projection maps to
        the fluid-style fc(4*hidden) + dynamic_lstm pair."""
        net = fluid.layers.embedding(input=words, size=[vocab, 128])
        for _ in range(lstm_num):
            proj = fluid.layers.fc(input=net, size=4 * hidden)
            net, _ = fluid.layers.dynamic_lstm(
                input=proj, size=4 * hidden, use_peepholes=True)
        last = fluid.layers.sequence_pool(input=net, pool_type='last')
        predict = fluid.layers.fc(input=last, size=2, act='softmax')
        cost = fluid.layers.cross_entropy(input=predict, label=lbl)
        return None, fluid.layers.mean(cost), None

    def smallnet(img, lbl):
        """benchmark/paddle/image/smallnet_mnist_cifar.py (the caffe
        cifar10_quick shape)."""
        net = fluid.layers.conv2d(input=img, num_filters=32,
                                  filter_size=5, padding=2, act='relu')
        net = fluid.layers.pool2d(input=net, pool_size=3, pool_stride=2,
                                  pool_padding=1, pool_type='max')
        net = fluid.layers.conv2d(input=net, num_filters=32,
                                  filter_size=5, padding=2, act='relu')
        net = fluid.layers.pool2d(input=net, pool_size=3, pool_stride=2,
                                  pool_padding=1, pool_type='avg')
        net = fluid.layers.conv2d(input=net, num_filters=64,
                                  filter_size=3, padding=1, act='relu')
        net = fluid.layers.pool2d(input=net, pool_size=3, pool_stride=2,
                                  pool_padding=1, pool_type='avg')
        net = fluid.layers.fc(input=net, size=64, act='relu')
        predict = fluid.layers.fc(input=net, size=10, act='softmax')
        cost = fluid.layers.cross_entropy(input=predict, label=lbl)
        return None, fluid.layers.mean(cost), None

    with unique_name.guard():
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start):
            if model == 'lstm':
                img = fluid.layers.data(name='img', shape=[1],
                                        dtype='int64', lod_level=1)
            elif model == 'smallnet':
                img = fluid.layers.data(name='img', shape=[3, 32, 32],
                                        dtype='float32')
            else:
                img = fluid.layers.data(name='img', shape=[3, 224, 224],
                                        dtype='float32')
            lbl = fluid.layers.data(name='lbl', shape=[1],
                                    dtype='int64')
            builders['lstm'] = lstm_text_class
            builders['smallnet'] = smallnet
            _, loss, _ = builders[model](img, lbl)
            opt = fluid.optimizer.Momentum(learning_rate=1e-3,
                                           momentum=0.9)
            opt = fluid.contrib.mixed_precision.decorate(opt)
            opt.minimize(loss)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(start)
            pe = fluid.ParallelExecutor(use_cuda=True,
                                        loss_name=loss.name,
                                        main_program=main, scope=scope)
            rng = np.random.RandomState(0)
            if model == 'smallnet':
                feed = {
                    'img': jax.device_put(
                        rng.rand(bs, 3, 32, 32).astype('f4')),
                    'lbl': jax.device_put(
                        rng.randint(0, 10, (bs, 1)).astype('int64')),
                }
            elif model == 'lstm':
                # IMDB-shaped synthetic: padded T=100 (the published
                # row pads too), dict 30000. Tiny feed (~50 KB) — the
                # upload is negligible at this size.
                feed = {
                    'img': (rng.randint(0, 30000, (bs, 100, 1))
                            .astype('int64'),
                            np.full((bs,), 100, 'int32')),
                    'lbl': rng.randint(0, 2, (bs, 1)).astype('int64'),
                }
            else:
                feed = {
                    'img': jax.device_put(
                        rng.rand(bs, 3, 224, 224).astype('f4')),
                    'lbl': jax.device_put(
                        rng.randint(0, 1000, (bs, 1)).astype('int64')),
                }
            for _ in range(3):
                lv = pe.run(fetch_list=[loss.name], feed=feed,
                            return_numpy=False)
            float(np.asarray(lv[0]))

            def timed(n):
                t0 = time.perf_counter()
                for _ in range(n):
                    lv = pe.run(fetch_list=[loss.name], feed=feed,
                                return_numpy=False)
                float(np.asarray(lv[0]))
                return time.perf_counter() - t0

            w1, w2 = timed(steps), timed(2 * steps)
            step_s = max(w2 - w1, 1e-9) / steps
    return bs / step_s, step_s * 1e3


# the reference's published INFERENCE rows
# (benchmark/IntelOptimizedPaddle.md:72-87, bs=16, 2xXeon 6148)
INFER_CONFIGS = {
    'resnet': dict(bs=16, published='217.69 img/s'),
    'vgg19': dict(bs=16, published='96.75 img/s'),
}


def infer_model(model, bs, steps=16):
    """Serving-path device throughput (save_inference_model ->
    AnalysisPredictor BN fold -> bench.serving_throughput's async
    N/2N-differenced loop) — the SAME measurement as bench.py's
    infer_*_device_images_per_sec leg, shared so it cannot drift."""
    import tempfile
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    from paddle_tpu.models import resnet, vgg
    from bench import serving_throughput

    builders = {
        'resnet': lambda i: resnet.resnet_imagenet(
            i, class_dim=1000, depth=50, is_test=True),
        'vgg19': lambda i: vgg.vgg19(i, class_dim=1000, is_test=True),
    }
    with unique_name.guard():
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start):
            img = fluid.layers.data(name='img', shape=[3, 224, 224],
                                    dtype='float32')
            pred = builders[model](img)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.TPUPlace())
        with tempfile.TemporaryDirectory() as tmp:
            with fluid.scope_guard(scope):
                exe.run(start)
                fluid.io.save_inference_model(tmp, ['img'], [pred], exe,
                                              main_program=main)
            p = AnalysisPredictor(AnalysisConfig(tmp,
                                                 place=fluid.TPUPlace()))
        rng = np.random.RandomState(0)
        feed = {p.get_input_names()[0]: jax.device_put(
            rng.rand(bs, 3, 224, 224).astype('f4'))}
        per_sec, ms = serving_throughput(p, feed, bs, steps)
        if per_sec is None:
            return float('nan'), float('nan')
        return per_sec, ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--models', nargs='+', choices=sorted(CONFIGS),
                    default=['alexnet', 'googlenet'])
    ap.add_argument('--infer', nargs='*', choices=sorted(INFER_CONFIGS),
                    help='also run the published INFERENCE rows '
                         '(no args = all)')
    args = ap.parse_args()
    print('| model | bs | img/s (this chip) | ms/batch | published |')
    print('|---|---|---|---|---|')
    for m in args.models:
        cfg = CONFIGS[m]
        ips, ms = bench_model(cfg.get('net', m), cfg['bs'])
        print('| %s | %d | %.0f | %.1f | %s |'
              % (m, cfg['bs'], ips, ms, cfg['published']), flush=True)
    infer = args.infer if args.infer else (
        sorted(INFER_CONFIGS) if args.infer is not None else [])
    for m in infer:
        cfg = INFER_CONFIGS[m]
        ips, ms = infer_model(m, cfg['bs'])
        print('| %s INFER | %d | %.0f | %.2f | %s |'
              % (m, cfg['bs'], ips, ms, cfg['published']), flush=True)


if __name__ == '__main__':
    main()
