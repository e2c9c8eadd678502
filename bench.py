"""Driver benchmark: ResNet-50 + Transformer-LM bf16 training on one chip.

Prints ONE JSON line. The primary metric keeps the r02 series
(ResNet-50 images/sec, bs=256, bf16) for trend continuity; the same line
carries the Transformer-LM tokens/sec + MFU as extra keys — the
MXU-dense config where the chip's ~79% matmul ceiling is approachable
(PERF.md gap analysis).

vs_baseline is computed against the reference repo's strongest published
single-machine ResNet-50 training number — 84.08 images/sec (bs=256,
MKL-DNN, 2x Xeon 6148; reference benchmark/IntelOptimizedPaddle.md:40-45;
the reference publishes no Fluid-GPU ResNet numbers).

Both configs run through the FULL framework path: Program IR -> autodiff
-> optimizer ops -> bf16 AMP -> whole-block XLA jit (ParallelExecutor),
fed by the framework's own async input pipeline
(fluid.layers.py_reader + double_buffer, reference
benchmark/fluid/fluid_benchmark.py:116 uses the same reader stack) — not
a hand-rolled loop.

MFU = achieved model FLOP/s over the chip's peak bf16 FLOP/s (the one
table, paddle_tpu/obs/perf.py), with model FLOPs = 3x forward
(fwd + bwd ~= 2x fwd) analytic matmul/conv FLOPs.

This program measures a TPU and fails without one: no shape, kernel or
peak is chosen by noticing that the chip is missing. Every series runs
on ONE chip (devices=jax.devices()[:1]) whatever the host holds, and the
row names the platform, device_kind and device count it ran on.
`python chip_smoke.py` is the quicker check that the system starts.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import memory  # noqa: E402
from paddle_tpu.models import resnet  # noqa: E402
from paddle_tpu.models import transformer as tfm  # noqa: E402
from paddle_tpu.obs import perf  # noqa: E402

BASELINE_IMG_PER_SEC = 84.08


def _resnet50_train_flops_per_image(image_hw, class_dim):
    """Analytic fwd FLOPs (2*MACs over convs+fc), x3 for fwd+bwd."""
    flops = 0

    def conv(hw_in, cin, cout, k, stride):
        hw_out = hw_in // stride
        flops_c = 2 * (hw_out ** 2) * cout * cin * k * k
        return hw_out, flops_c

    hw, f = conv(image_hw, 3, 64, 7, 2)
    flops += f
    hw //= 2  # maxpool
    stages = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]
    cin = 64
    for ch, count, stride in stages:
        for i in range(count):
            s = stride if i == 0 else 1
            # bottleneck: 1x1 (stride s), 3x3, 1x1 expand; + projection on i==0
            hw2, f1 = conv(hw, cin, ch, 1, s)
            _, f2 = conv(hw2, ch, ch, 3, 1)
            _, f3 = conv(hw2, ch, ch * 4, 1, 1)
            flops += f1 + f2 + f3
            if i == 0:
                _, fp = conv(hw, cin, ch * 4, 1, s)
                flops += fp
            hw = hw2
            cin = ch * 4
    flops += 2 * cin * class_dim  # fc
    return 3 * flops


def _transformer_train_flops_per_token(cfg, causal=False):
    """Analytic fwd FLOPs per token (2*MACs), x3 for fwd+bwd. With
    causal=True attention counts the useful T/2 per token."""
    d, f, t, v, n = cfg.dim, cfg.ffn, cfg.max_len, cfg.vocab, cfg.layers
    per_layer = 4 * d * d + 2 * d * f        # qkv+proj, ffn up+down (MACs)
    attn = (t if causal else 2 * t) * d      # q@k^T + probs@v per token
    head = d * v                             # logits projection
    return 3 * 2 * (n * (per_layer + attn) + head)


def _run_steps(pe, fetch_name, warmup, iters):
    """Timed async step loop, synced by a host fetch of the last loss.
    Timing both an `iters` and a `2*iters` loop and differencing cancels
    every per-sync constant. (Inherited from an earlier machine;
    ROADMAP S1 re-checks the discipline against a plain
    block_until_ready window on this one.)"""
    for _ in range(warmup):
        wl = pe.run(fetch_list=[fetch_name], return_numpy=False)
    float(np.asarray(wl[0]))

    def timed(n):
        t0 = time.perf_counter()
        for _ in range(n):
            loss = pe.run(fetch_list=[fetch_name], return_numpy=False)
        float(np.asarray(loss[0]))
        return time.perf_counter() - t0

    w1 = timed(iters)
    w2 = timed(2 * iters)
    return max(w2 - w1, 1e-9)


def bench_resnet():
    batch, image_hw, class_dim, depth = 256, 224, 1000, 50
    warmup, iters = 3, 30

    main_prog = fluid.Program()
    startup_prog = fluid.Program()
    with fluid.program_guard(main_prog, startup_prog):
        rdr = fluid.layers.py_reader(
            capacity=4,
            shapes=[(-1, 3, image_hw, image_hw), (-1, 1)],
            dtypes=['float32', 'int64'], name='resnet_reader',
            use_double_buffer=True)
        image, label = fluid.layers.read_file(rdr)
        # NHWC on TPU: channels-last is the lane-native layout (one tiny
        # stem transpose; numerics identical — layout parity test)
        _, avg_cost, _ = resnet.train_network(
            image, label, class_dim=class_dim, depth=depth, nhwc=True)
        opt = fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9)
        opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(avg_cost)

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup_prog)
    pe = fluid.ParallelExecutor(use_cuda=True, loss_name=avg_cost.name,
                                main_program=main_prog,
                                devices=jax.devices()[:1])

    rng = np.random.RandomState(0)
    img = jax.device_put(rng.rand(batch, 3, image_hw, image_hw)
                         .astype('float32'))
    lbl = jax.device_put(rng.randint(0, class_dim, size=(batch, 1))
                         .astype('int64'))

    def provider():
        while True:
            yield [img, lbl]

    rdr.decorate_tensor_provider(provider)
    rdr.start()
    dt = _run_steps(pe, avg_cost.name, warmup, iters)
    rdr.reset()

    img_per_sec = batch * iters / dt
    out = {
        'metric': 'resnet%d_train_images_per_sec_bs%d_%dpx_bf16' % (
            depth, batch, image_hw),
        'value': round(img_per_sec, 2),
        'unit': 'images/sec',
        'vs_baseline': round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
    }
    peak = perf.device_peak_flops(jax.devices()[0])
    model_flops = _resnet50_train_flops_per_image(image_hw, class_dim)
    out['model_tflops_per_sec'] = round(
        img_per_sec * model_flops / 1e12, 1)
    out['mfu'] = round(img_per_sec * model_flops / peak, 4)
    return out


def _bench_lm(cfg, batch, warmup, iters, prefix, causal_flops,
              reader_name, fused_head=False, head_chunk=4096):
    """Shared LM benchmark body: py_reader-fed AMP training step under
    the ParallelExecutor, async timing, tokens/s + MFU emission.
    fused_head routes the LM head through fused_softmax_cross_entropy
    (no [B*T, V] logits tensor in either pass)."""
    main_prog = fluid.Program()
    startup_prog = fluid.Program()
    with fluid.program_guard(main_prog, startup_prog):
        rdr = fluid.layers.py_reader(
            capacity=4,
            shapes=[(-1, cfg.max_len, 1), (-1, cfg.max_len, 1)],
            dtypes=['int64', 'int64'], name=reader_name,
            use_double_buffer=True)
        tokens, labels = fluid.layers.read_file(rdr)
        if fused_head:
            trunk = tfm.language_model_trunk(tokens, cfg)
            cost = fluid.layers.fused_softmax_cross_entropy(
                trunk, labels, cfg.vocab, chunk=head_chunk,
                name='lm_head')
        else:
            emb = tfm.language_model_logits(tokens, cfg)
            cost = fluid.layers.softmax_with_cross_entropy(emb, labels)
        avg_cost = fluid.layers.mean(cost)
        opt = fluid.optimizer.Momentum(learning_rate=0.001, momentum=0.9)
        opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(avg_cost)

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup_prog)
    pe = fluid.ParallelExecutor(use_cuda=True, loss_name=avg_cost.name,
                                main_program=main_prog,
                                devices=jax.devices()[:1])
    rng = np.random.RandomState(0)

    def provider():
        while True:
            toks = rng.randint(0, cfg.vocab,
                               size=(batch, cfg.max_len, 1)).astype('int64')
            yield [toks, np.roll(toks, -1, axis=1)]

    rdr.decorate_tensor_provider(provider)
    rdr.start()
    dt = _run_steps(pe, avg_cost.name, warmup, iters)
    rdr.reset()

    tokens_per_sec = batch * cfg.max_len * iters / dt
    out = {prefix + '_tokens_per_sec': round(tokens_per_sec, 1),
           prefix + '_config': 'L%d_D%d_F%d_T%d_V%d_bs%d_bf16' % (
               cfg.layers, cfg.dim, cfg.ffn, cfg.max_len, cfg.vocab,
               batch)}
    peak = perf.device_peak_flops(jax.devices()[0])
    fl = _transformer_train_flops_per_token(cfg, causal=causal_flops)
    out[prefix + '_tflops_per_sec'] = round(tokens_per_sec * fl / 1e12, 1)
    out[prefix + '_mfu'] = round(tokens_per_sec * fl / peak, 4)
    return out


def bench_transformer():
    # Pallas flash attention (no [B,H,T,T] HBM round-trips), fused
    # LM-head loss, bf16 param grads (PERF.md breakdown). chip_smoke.py
    # drives this same configuration.
    cfg = tfm.TransformerConfig(vocab=32768, dim=2048, heads=16,
                                layers=12, ffn=8192, max_len=512,
                                use_tp=False, use_sp=False,
                                flash_attention=True)
    batch, warmup, iters = 8, 3, 20
    # full (non-causal) attention FLOPs, as the series always counted
    return _bench_lm(cfg, batch, warmup, iters, 'transformer',
                     causal_flops=False, reader_name='tfm_reader',
                     fused_head=True)


def bench_long_context():
    """Long-context LM step via the Pallas flash-attention kernel
    (T=8192 — a length where the naive [T, T]-score path fails to
    compile on a 16 GB chip, PERF.md). Causal attention FLOPs counted
    at T/2 per token (the useful half)."""
    cfg = tfm.TransformerConfig(vocab=32768, dim=1024, heads=8,
                                layers=4, ffn=4096, max_len=8192,
                                use_tp=False, use_sp=False,
                                flash_attention=True)
    batch, warmup, iters = 2, 2, 10
    # head_chunk 8192: 2 scan chunks at N=16384 measured ~4% faster
    # than 4 (in-process differencing A/B); a single 16384 chunk loses
    # again (2 GB fp32 logits transient)
    return _bench_lm(cfg, batch, warmup, iters, 'longcontext',
                     causal_flops=True, reader_name='lc_reader',
                     fused_head=True, head_chunk=8192)


def _latency_stats(fn, iters):
    lats = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        lats.append(time.perf_counter() - t0)
    lats.sort()
    p50 = lats[len(lats) // 2]
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
    return p50 * 1e3, p99 * 1e3, sum(lats) / len(lats)


def serving_throughput(predictor, feed, batch, iters):
    """Device throughput of a predictor's (BN-folded) serving program:
    async predictor.run(return_numpy=False) on a device-resident feed,
    fetch once, N/2N differenced. Shared by bench_inference and
    tools/bench_published_models so the measurement cannot drift.
    Returns (per_sec, ms_per_batch), or (None, None) when no valid
    measurement was reached. Validity requires the differenced step
    work to DOMINATE the run (d > 0.5·w1): in the sync-constant-
    dominated regime, constant jitter can masquerade as step time, so
    instead of loosening acceptance the loop self-sizes — N doubles
    until step work out-weighs the constant (or a cap is hit)."""
    def _loop(n):
        t0 = time.perf_counter()
        r = None
        for _ in range(n):
            r = predictor.run(feed, return_numpy=False)
        np.asarray(r[0])
        return time.perf_counter() - t0
    _loop(3)
    for _ in range(4):
        w1, w2 = _loop(iters), _loop(2 * iters)
        d = w2 - w1
        if d > 0.5 * w1:
            return batch * iters / d, d / iters * 1e3
        iters *= 2
    return None, None


def bench_inference():
    """Inference perf series (round-5 VERDICT #6; reference publishes
    inference numbers in benchmark/IntelOptimizedPaddle.md:81-87 and
    ships per-model inference tests in inference/tests/book/).

    All legs go through the full serving path: save_inference_model ->
    AnalysisPredictor (offline BN fold) -> the predictor's program.
    Latencies are host wall time per synchronous predictor.run(), feed
    upload included; the resnet device-throughput leg drives the
    predictor's folded program async on a device-resident feed.
    """
    import tempfile
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    place = fluid.TPUPlace()
    out = {}
    iters = 20
    rng = np.random.RandomState(0)

    # --- ResNet-50 bs16 image classification ---
    bs, hw, classes, depth = 16, 224, 1000, 50
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        image = fluid.layers.data(name='image', shape=[3, hw, hw],
                                  dtype='float32')
        pred = resnet.resnet_imagenet(image, class_dim=classes,
                                      depth=depth, is_test=True,
                                      nhwc=True)
    exe = fluid.Executor(place)
    scope = fluid.Scope()
    with tempfile.TemporaryDirectory() as tmp:
        with fluid.scope_guard(scope):
            exe.run(startup)
            fluid.io.save_inference_model(tmp, ['image'], [pred], exe,
                                          main_program=main_prog)
        predictor = AnalysisPredictor(AnalysisConfig(tmp, place=place))
    img = rng.rand(bs, 3, hw, hw).astype('float32')
    predictor.run([img])                     # compile
    predictor.run([img])
    p50, p99, mean = _latency_stats(lambda: predictor.run([img]), iters)
    out.update({
        'infer_resnet%d_bs%d_images_per_sec' % (depth, bs):
            round(bs / mean, 1),
        'infer_resnet%d_bs%d_p50_ms' % (depth, bs): round(p50, 1),
        'infer_resnet%d_bs%d_p99_ms' % (depth, bs): round(p99, 1)})

    # Device-THROUGHPUT leg: the per-call numbers above include the
    # 9.6 MB feed upload; the reference's published 217.69 img/s
    # (IntelOptimizedPaddle.md:81-87) is a throughput number, so
    # measure ours the same way.
    thr, _ = serving_throughput(predictor,
                                {predictor.get_input_names()[0]:
                                 jax.device_put(img)}, bs, iters)
    out['infer_resnet%d_bs%d_device_images_per_sec' % (depth, bs)] = \
        None if thr is None else round(thr, 1)

    # --- Transformer decode step (next-token logits for a T-prefix) ---
    # L4/D1024 (the longcontext trunk at T=512). 16 heads of d=64 miss
    # the flash kernel's d % 128 tiling, so this leg runs the naive
    # contraction whatever the flag says (pallas.flash.naive counts it).
    cfg = tfm.TransformerConfig(vocab=32768, dim=1024, heads=16,
                                layers=4, ffn=4096, max_len=512,
                                use_tp=False, use_sp=False,
                                flash_attention=True)
    tbs = 4
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        tokens = fluid.layers.data(name='tokens',
                                   shape=[cfg.max_len, 1], dtype='int64')
        logits = tfm.language_model_logits(tokens, cfg)
        # fetch only the next-token distribution — the decode-step
        # contract (full [B,T,V] logits would move ~256 MB per call
        # to the host)
        last = fluid.layers.slice(logits, axes=[1],
                                  starts=[cfg.max_len - 1],
                                  ends=[cfg.max_len])
    exe = fluid.Executor(place)
    scope = fluid.Scope()
    with tempfile.TemporaryDirectory() as tmp:
        with fluid.scope_guard(scope):
            exe.run(startup)
            fluid.io.save_inference_model(tmp, ['tokens'], [last], exe,
                                          main_program=main_prog)
        predictor = AnalysisPredictor(AnalysisConfig(tmp, place=place))
    toks = rng.randint(0, cfg.vocab,
                       (tbs, cfg.max_len, 1)).astype('int64')
    predictor.run([toks])
    predictor.run([toks])
    p50, p99, mean = _latency_stats(lambda: predictor.run([toks]), iters)
    out.update({
        'infer_transformer_decode_config': 'L%d_D%d_T%d_bs%d' % (
            cfg.layers, cfg.dim, cfg.max_len, tbs),
        'infer_transformer_prefix_tokens_per_sec':
            round(tbs * cfg.max_len / mean, 1),
        'infer_transformer_decode_p50_ms': round(p50, 1),
        'infer_transformer_decode_p99_ms': round(p99, 1)})

    # --- cached vs recompute decode (same config, same weights) ---
    # The leg above recomputes the whole T-prefix for ONE next token:
    # that per-call mean IS the full-recompute tokens/s baseline
    # (tbs next-tokens per call). The KV-cached pair (serving/)
    # prefills once, then each decode step touches one token against
    # the page pool — O(1) per token vs O(T).
    out['infer_decode_config'] = 'L%d_D%d_T%d_bs%d' % (
        cfg.layers, cfg.dim, cfg.max_len, tbs)
    out['infer_decode_recompute_tokens_per_sec'] = round(tbs / mean, 2)
    # every stream one token short of its window, the step appending
    # the last; a page more a lane than the window, because that append
    # forks the tail page the prefix cache shares
    pages_per_slot = -(-cfg.max_len
                       // fluid.flags.get_flag('serving_page_tokens'))
    dec = predictor.prepare_decoding(
        slots=tbs, kv_pages=tbs * (pages_per_slot + 1) + 1,
        prefill_chunk=cfg.max_len)
    prompts = [toks[i, :-1, 0] for i in range(tbs)]
    t0 = time.perf_counter()
    for i in range(tbs):
        dec.prefill([prompts[i]], [i])
    out['infer_decode_prefill_ms'] = round(
        (time.perf_counter() - t0) * 1e3 / tbs, 1)
    step_toks = np.zeros((tbs,), 'int64')
    step_pos = np.full((tbs,), cfg.max_len - 1, 'int32')
    dec.decode_step(step_toks, step_pos)   # compile
    _, _, dmean = _latency_stats(
        lambda: dec.decode_step(step_toks, step_pos), iters)
    out['infer_decode_cached_tokens_per_sec'] = round(tbs / dmean, 2)
    out['infer_decode_speedup'] = round(mean / dmean, 2)
    return out


def _peak_hbm_gb():
    """The PJRT allocator's peak on the chip, in GiB."""
    return round(memory.memory_stats()['peak_bytes_in_use'] / 2 ** 30, 2)


def main():
    out = perf.require_tpu()
    # bf16 parameter gradients under AMP (flags.py): master weights
    # and optimizer state stay fp32; dW writes + update reads halve
    fluid.flags.set_flags({'FLAGS_amp_bf16_param_grads': True})
    # peak-HBM fields are the PJRT allocator's CUMULATIVE peak sampled
    # after each series (it has no reset), so each value bounds that
    # series' footprint from above; the long-context budget assertion
    # uses the final value. (VERDICT round-5 #7; reference analog:
    # FLAGS_benchmark per-op memory logs, framework/executor.cc:334-338)
    out.update(bench_resnet())
    out['resnet_peak_hbm_gb'] = _peak_hbm_gb()
    out.update(bench_transformer())
    out['transformer_peak_hbm_gb'] = _peak_hbm_gb()
    out.update(bench_long_context())
    out['longcontext_peak_hbm_gb'] = _peak_hbm_gb()
    # remat keeps the T=8192 config comfortably inside the 16 GB
    # chip; a 2x activation-memory regression would trip this
    out['longcontext_hbm_under_budget'] = bool(
        out['longcontext_peak_hbm_gb'] < 15.0)
    out.update(bench_inference())
    print(json.dumps(out))


if __name__ == '__main__':
    main()
