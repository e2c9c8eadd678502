"""Worker for the multi-host DP test (subprocess-localhost pattern,
reference tests/unittests/test_dist_base.py:13-100). Launched by
test_dist_multihost.py with the PADDLE_* env contract set. Trains an MLP
on a deterministic stream, feeding only this trainer's LOCAL half-batch,
and prints per-step losses as JSON on the last line."""
import json
import os
import sys

os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '')
                           + ' --xla_force_host_platform_device_count=4')

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.framework import Program, program_guard  # noqa: E402

GLOBAL_BATCH = 32
STEPS = 5


def build(mode):
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 11
    with program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[8], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        if mode == 'tp':
            # Megatron pair: the psum completing the row-parallel matmul
            # rides the tp axis ACROSS the trainer boundary
            from paddle_tpu.parallel.layers import (column_parallel_fc,
                                                    row_parallel_fc)
            h = column_parallel_fc(x, 16, act='relu')
            pred = row_parallel_fc(h, 1)
        elif mode == 'sp':
            # ring attention with the sp axis spanning processes: the
            # K/V ppermute ring crosses the trainer boundary every step
            from paddle_tpu.parallel.layers import ring_attention
            h = fluid.layers.fc(input=x, size=16, act='relu')
            q = fluid.layers.reshape(h, shape=[-1, 1, 8, 2])  # [B,1,T=8,2]
            att = ring_attention(q, q, q, causal=True)
            flat = fluid.layers.reshape(att, shape=[-1, 16])
            pred = fluid.layers.fc(input=flat, size=1)
        else:
            h = fluid.layers.fc(input=x, size=16, act='relu')
            pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        # Adam: ZeRO-1 shards its moments; SGD has no state to shard
        fluid.optimizer.Adam(learning_rate=0.1).minimize(loss)
    return prog, startup, loss


def batches():
    rng = np.random.RandomState(7)
    for _ in range(STEPS):
        xv = rng.rand(GLOBAL_BATCH, 8).astype('float32')
        yv = xv.sum(1, keepdims=True).astype('float32')
        yield xv, yv


def main():
    num_trainers = int(os.environ.get('PADDLE_TRAINERS_NUM', 1))
    trainer_id = int(os.environ.get('PADDLE_TRAINER_ID', 0))
    mode = os.environ.get('DIST_TEST_MODE', 'dp')

    prog, startup, loss = build(mode)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())

    kwargs = {}
    if mode == 'zero1':
        bs = fluid.BuildStrategy()
        bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
        kwargs['build_strategy'] = bs
    elif mode == 'tp':
        from paddle_tpu.parallel import DistributedStrategy
        n_dev = 4 * max(num_trainers, 1)   # 4 forced local devices each
        kwargs['strategy'] = DistributedStrategy(dp=n_dev // 2, tp=2)
    elif mode == 'sp':
        from paddle_tpu.parallel import DistributedStrategy
        n_dev = 4 * max(num_trainers, 1)
        sp = min(n_dev, 8)                 # T=8 must divide by sp
        kwargs['strategy'] = DistributedStrategy(dp=n_dev // sp, sp=sp)

    pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                main_program=prog, scope=scope,
                                num_trainers=num_trainers,
                                trainer_id=trainer_id, **kwargs)
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)

    losses = []
    from paddle_tpu.parallel import distributed as dist
    for xv, yv in batches():
        if num_trainers > 1 and 'dp' in pe.mesh.axis_names:
            # this process's rows of the global batch, derived from the
            # mesh's device->process mapping along 'dp' (NOT trainer_id
            # arithmetic: under dp x sp meshes several trainers share a
            # dp row and must feed identical rows)
            xl = dist.shard_rows_for_process(xv, pe.mesh, 'dp')
            yl = dist.shard_rows_for_process(yv, pe.mesh, 'dp')
        else:
            # dp==1 (dropped from the mesh): batch fully replicated,
            # every trainer feeds the whole global batch
            xl, yl = xv, yv
        l, = pe.run(fetch_list=[loss.name], feed={'x': xl, 'y': yl})
        losses.append(float(np.asarray(l)))
    # a save on every trainer writes whole arrays, whatever the mesh
    # holds as shards across the trainers (a collective: all call it)
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        with fluid.scope_guard(scope):
            fluid.io.save_persistables(exe, tmp, main_program=prog)
        fresh = fluid.Scope()
        with fluid.scope_guard(fresh):
            fluid.io.load_persistables(exe, tmp, main_program=prog)
        saved = sorted(
            (v.name, float(np.abs(np.asarray(fresh.find_var(v.name))).sum()))
            for v in prog.global_block().vars.values()
            if isinstance(v, fluid.framework.Parameter))
    print('SAVED ' + json.dumps(saved), flush=True)
    print('LOSSES ' + json.dumps(losses), flush=True)


if __name__ == '__main__':
    main()
