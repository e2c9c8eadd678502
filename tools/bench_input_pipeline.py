"""Input-pipeline-on-the-measured-path bench (round-5 VERDICT #4).

Reference analog: benchmark/fluid/fluid_benchmark.py trains through the
RecordIO reader stack (recordio_converter.py shards ->
open_files/double_buffer readers); this tool does the same for the
flagship ResNet-50 config and reports BOTH numbers:

  1. pre-placed feed (bench.py's MFU-isolation path: one device_put,
     provider re-serves the same batch)
  2. the REAL pipeline: u8 image shards on disk -> open_files
     (thread_num=N, native decode: C++ workers parse + normalize to
     f32) -> py_reader double buffer -> train step

plus the native prefetcher's standalone decode throughput at 1..N
threads (the thread-scaling evidence the round-4 verdict asked for).

    python tools/bench_input_pipeline.py            # full (TPU, bs256)
    python tools/bench_input_pipeline.py --smoke    # tiny CPU shapes
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def write_shards(dirname, n_files, recs_per_file, shape, seed=0):
    from paddle_tpu.recordio import RecordIOWriter
    rng = np.random.RandomState(seed)
    paths = []
    for f in range(n_files):
        p = os.path.join(dirname, 'imagenet-%03d.recordio' % f)
        with RecordIOWriter(p, max_num_records=64) as w:
            for i in range(recs_per_file):
                img = rng.randint(0, 256, shape, dtype='uint8')
                label = rng.randint(0, 1000, (1,)).astype('int64')
                w.append_sample([img, label])
        paths.append(p)
    return paths


def decode_throughput(paths, shape, n_threads, seconds=6.0):
    """Samples/sec drained from the native decode scanner."""
    from paddle_tpu.recordio import ParallelImageScanner
    n = 0
    t0 = time.perf_counter()
    with ParallelImageScanner(paths, shape, mean=[0.485, 0.456, 0.406],
                              std=[0.229, 0.224, 0.225],
                              n_threads=n_threads, capacity=8,
                              loop=True) as sc:
        for imgs, labels in sc:
            n += imgs.shape[0]
            if time.perf_counter() - t0 > seconds:
                break
    dt = time.perf_counter() - t0
    return n / dt


def build_train(image_source, batch, shape, class_dim, depth, on_tpu,
                paths=None, thread_num=4):
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        if image_source == 'pipeline':
            rdr = fluid.layers.open_files(
                paths, shapes=[(-1,) + shape, (-1, 1)],
                dtypes=['float32', 'int64'], thread_num=thread_num,
                pass_num=0,           # loop forever (steady state)
                image_norm=dict(mean=[0.485, 0.456, 0.406],
                                std=[0.229, 0.224, 0.225]))
            rdr = fluid.layers.batch(rdr, batch_size=batch)
            rdr = fluid.layers.double_buffer(rdr)
            image, label = fluid.layers.read_file(rdr)
        else:
            rdr = fluid.layers.py_reader(
                capacity=4, shapes=[(-1,) + shape, (-1, 1)],
                dtypes=['float32', 'int64'], name='pre_placed',
                use_double_buffer=True)
            image, label = fluid.layers.read_file(rdr)
        _, avg_cost, _ = resnet.train_network(
            image, label, class_dim=class_dim, depth=depth, nhwc=on_tpu)
        opt = fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9)
        opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(avg_cost)
    return main_prog, startup, avg_cost, rdr


def run_steps(pe, loss_name, warmup, iters):
    """N/2N differencing (bench._run_steps pattern)."""
    for _ in range(warmup):
        wl = pe.run(fetch_list=[loss_name], return_numpy=False)
    float(np.asarray(wl[0]))

    def timed(n):
        t0 = time.perf_counter()
        for _ in range(n):
            l = pe.run(fetch_list=[loss_name], return_numpy=False)
        float(np.asarray(l[0]))
        return time.perf_counter() - t0

    t1 = timed(iters)
    t2 = timed(2 * iters)
    return max(t2 - t1, 1e-9) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--smoke', action='store_true')
    ap.add_argument('--threads', type=int, default=4)
    ap.add_argument('--shard-dir', default=None,
                    help='reuse existing shards instead of writing')
    args = ap.parse_args()

    import jax
    if args.smoke:
        # the smoke is a CPU run; before any backend is touched
        jax.config.update('jax_platforms', 'cpu')
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    on_tpu = any(d.platform == 'tpu' for d in jax.devices()) \
        and not args.smoke
    if on_tpu:
        fluid.flags.set_flags({'FLAGS_amp_bf16_param_grads': True})
        shape, batch, class_dim, depth = (3, 224, 224), 256, 1000, 50
        n_files, recs = 8, 512
        warmup, iters = 3, 10
    else:
        shape, batch, class_dim, depth = (3, 32, 32), 16, 10, 18
        n_files, recs = 4, 64
        warmup, iters = 1, 3

    out = {'mode': 'input_pipeline', 'batch': batch,
           'image_shape': list(shape), 'threads': args.threads}

    tmp_ctx = tempfile.TemporaryDirectory() if not args.shard_dir \
        else None
    shard_dir = args.shard_dir or tmp_ctx.name
    t0 = time.perf_counter()
    if not args.shard_dir:
        paths = write_shards(shard_dir, n_files, recs, shape)
        out['shard_write_s'] = round(time.perf_counter() - t0, 1)
    else:
        import glob
        paths = sorted(glob.glob(os.path.join(shard_dir, '*.recordio')))
    out['n_shards'] = len(paths)
    out['shard_mb'] = round(sum(os.path.getsize(p) for p in paths)
                            / 1e6, 1)

    # ---- host memory-bandwidth probe ---------------------------------
    # Context for the scaling numbers: decode moves ~1 MB of memory
    # traffic per 224² sample (inflate read+write, normalize read+write,
    # queue hand-off); if one copy stream saturates the host, worker
    # threads CANNOT scale a memory-bound decode no matter the design.
    probe_src = np.random.randint(0, 255, 64 << 20, dtype=np.uint8)
    probe_dst = np.empty_like(probe_src)
    t0 = time.perf_counter()
    reps = 0
    while time.perf_counter() - t0 < 2.0:
        np.copyto(probe_dst, probe_src)
        reps += 1
    out['host_memcpy_gbps'] = round(
        reps * 64 / 1024 / (time.perf_counter() - t0), 2)

    # ---- native decode thread scaling (standalone) -------------------
    for nt in (1, 2, args.threads):
        rate = decode_throughput(paths, shape, nt,
                                 seconds=4.0 if on_tpu else 2.0)
        out['decode_samples_per_sec_t%d' % nt] = round(rate, 1)
    out['decode_scaling_1_to_%d' % args.threads] = round(
        out['decode_samples_per_sec_t%d' % args.threads]
        / out['decode_samples_per_sec_t1'], 2)

    # ---- A: pre-placed feed ------------------------------------------
    with unique_name.guard():
        prog, startup, cost, rdr = build_train(
            'preplaced', batch, shape, class_dim, depth, on_tpu)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace() if on_tpu
                             else fluid.CPUPlace())
        exe.run(startup)
        pe = fluid.ParallelExecutor(use_cuda=on_tpu,
                                    loss_name=cost.name,
                                    main_program=prog, scope=scope)
        rng = np.random.RandomState(0)
        img = jax.device_put(rng.rand(batch, *shape).astype('float32'))
        lbl = jax.device_put(
            rng.randint(0, class_dim, (batch, 1)).astype('int64'))

        def provider():
            while True:
                yield [img, lbl]

        rdr.decorate_tensor_provider(provider)
        rdr.start()
        dt_pre = run_steps(pe, cost.name, warmup, iters)
        rdr.reset()
    out['preplaced_step_ms'] = round(dt_pre * 1e3, 2)
    out['preplaced_images_per_sec'] = round(batch / dt_pre, 1)

    # ---- B: real pipeline (disk -> native decode -> double buffer) ---
    with unique_name.guard():
        prog, startup, cost, rdr = build_train(
            'pipeline', batch, shape, class_dim, depth, on_tpu,
            paths=paths, thread_num=args.threads)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace() if on_tpu
                             else fluid.CPUPlace())
        exe.run(startup)
        pe = fluid.ParallelExecutor(use_cuda=on_tpu,
                                    loss_name=cost.name,
                                    main_program=prog, scope=scope)
        rdr.start()
        dt_pipe = run_steps(pe, cost.name, warmup, iters)
        rdr.reset()
    out['pipeline_step_ms'] = round(dt_pipe * 1e3, 2)
    out['pipeline_images_per_sec'] = round(batch / dt_pipe, 1)
    out['pipeline_overhead_pct'] = round(
        100.0 * (dt_pipe - dt_pre) / dt_pre, 1)
    if tmp_ctx is not None:
        tmp_ctx.cleanup()
    print(json.dumps(out))


if __name__ == '__main__':
    main()
