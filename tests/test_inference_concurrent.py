"""Concurrent inference serving: N threads run clone()d predictors
simultaneously against shared weights and must agree with the serial
results (reference multi-thread inference helper,
paddle/fluid/inference/tests/test_helper.h TestMultiThreadInference /
tests/book/ usage). clone() shares the weight Scope; programs and
compile caches are per-clone, so concurrent run() must be safe."""
import threading

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.framework import Program, program_guard


def _save_model(tmp_path):
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 3
    with unique_name.guard(), program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[8], dtype='float32')
        h = fluid.layers.fc(input=x, size=16, act='relu')
        out = fluid.layers.fc(input=h, size=4, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp_path), ['x'], [out], exe,
                                      main_program=prog)


def test_concurrent_cloned_predictors_agree_with_serial(tmp_path):
    _save_model(tmp_path)
    from paddle_tpu.inference import Config, create_predictor
    base = create_predictor(Config(str(tmp_path),
                                   place=fluid.CPUPlace()))
    rng = np.random.RandomState(0)
    batches = [rng.rand(5, 8).astype('f4') for _ in range(8)]

    # serial reference results from the base predictor
    serial = [base.run([b])[0] for b in batches]

    n_threads = 4
    clones = [base.clone() for _ in range(n_threads)]
    results = [[None] * len(batches) for _ in range(n_threads)]
    errors = []
    start = threading.Barrier(n_threads)

    def worker(t):
        try:
            start.wait(timeout=30)
            for rep in range(3):                 # sustained concurrency
                for i, b in enumerate(batches):
                    results[t][i] = clones[t].run([b])[0]
        except Exception as e:                   # surface, don't hang
            errors.append((t, repr(e)))

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
        assert not th.is_alive(), 'predictor thread hung (deadlock?)'
    assert not errors, errors
    for t in range(n_threads):
        for i in range(len(batches)):
            np.testing.assert_allclose(
                results[t][i], serial[i], rtol=1e-5, atol=1e-6,
                err_msg='thread %d batch %d diverged from serial'
                        % (t, i))
    # weights are genuinely shared, not copied: the clones' scope IS
    # the base predictor's scope object
    assert all(c._scope is base._scope for c in clones)


def test_concurrent_cloned_decode_predictors_agree_with_serial(tmp_path):
    """The serving extension of the clone contract: PagedDecodePredictor
    clones share the weight scope but carry PRIVATE K/V cache scopes,
    so concurrent generation streams must equal their serial runs
    (deeper checks live in tests/test_serving.py)."""
    from paddle_tpu.models.transformer import (TransformerConfig,
                                               language_model_logits)
    cfg = TransformerConfig(vocab=32, dim=16, heads=2, layers=1,
                            ffn=32, max_len=8, use_tp=False,
                            use_sp=False)
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 5
    with unique_name.guard(), program_guard(prog, startup):
        toks = fluid.layers.data(name='tokens',
                                 shape=[1, cfg.max_len, 1],
                                 dtype='int64', append_batch_size=False)
        logits = language_model_logits(toks, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp_path), ['tokens'],
                                      [logits], exe, main_program=prog)
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    pred = AnalysisPredictor(AnalysisConfig(str(tmp_path),
                                            place=fluid.CPUPlace()))
    base = pred.prepare_decoding(slots=1, page_tokens=4, kv_pages=8)
    workers = [base] + [base.clone() for _ in range(2)]
    prompts = [[3, 1, 4], [7, 7], [2, 9, 6, 1]]
    serial = [w.generate(p, 5) for w, p in zip(workers, prompts)]
    for w in workers:
        w.reset()

    results, errors = [None] * 3, []
    start = threading.Barrier(3)

    def worker(i):
        try:
            start.wait(timeout=30)
            results[i] = workers[i].generate(prompts[i], 5)
        except Exception as e:                   # surface, don't hang
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
        assert not th.is_alive(), 'decode thread hung (deadlock?)'
    assert not errors, errors
    assert results == serial
    assert all(w._weight_scope is base._weight_scope for w in workers)
