"""What a one-device ParallelExecutor's training step traces to, as a
digest: a tiny language model under bf16 AMP and Adam (the training
cells' program at toy widths), the step's one device segment, traced
with the jit options the executor gives it. A jaxpr's text holds no file
name and no line number, so the digest moves only when what is computed
moves.

    PYTHONPATH=. JAX_PLATFORMS=cpu python tests/train_step_jaxpr.py > FILE

writes the record; tests/test_parallel_axes.py holds the tree to the one
recorded from the parent of the PR that made a dp mesh shard its
optimizer's update (PR 47; tests/train_step_jaxpr_pr46.json): with one
device nothing of that is reached. Uses nothing that commit lacks.
"""
import hashlib
import json

import jax
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.executor import PreparedProgram, _DeviceSegment
from paddle_tpu.framework import Program, program_guard

T, BATCH = 16, 4


def build():
    """(main, startup, loss) of the toy training step."""
    from paddle_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab=64, dim=32, heads=2, layers=2, ffn=64,
                                max_len=T, use_tp=False, use_sp=False)
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 1
    with program_guard(main, startup), unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[T, 1], dtype='int64')
        labels = fluid.layers.data('labels', shape=[T, 1], dtype='int64')
        trunk = tfm.language_model_trunk(tokens, cfg)
        cost = fluid.layers.fused_softmax_cross_entropy(
            trunk, labels, cfg.vocab, chunk=BATCH * T, name='lm_head')
        loss = fluid.layers.mean(cost)
        opt = fluid.optimizer.Adam(learning_rate=1e-3)
        fluid.contrib.mixed_precision.decorate(opt).minimize(loss)
    return main, startup, loss


def step_digest():
    main, _, loss = build()
    pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                main_program=main,
                                devices=jax.devices()[:1])
    feeds = ('tokens', 'labels')
    prepared = PreparedProgram(main, 0, feeds, [loss.name])
    segment, = [s for s in prepared.steps if isinstance(s, _DeviceSegment)]
    block = prepared.block

    def struct(name):
        var = block.vars[name]
        shape = tuple(BATCH if d in (-1, None) else int(d)
                      for d in (var.shape or ()))
        return jax.ShapeDtypeStruct(
            shape, jax.dtypes.canonicalize_dtype(np.dtype(var.dtype)))

    out = set(segment.out_names)
    donated = {n: struct(n) for n in segment.in_names
               if n in out and n not in feeds}
    const = {n: struct(n) for n in segment.in_names if n not in donated}
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    jitted = pe._compile_segment(segment, block, main, feed_names=feeds)
    text = str(jitted.trace(donated, const, key).jaxpr)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


if __name__ == '__main__':
    print(json.dumps({'one_device_train_step': step_digest()}))
