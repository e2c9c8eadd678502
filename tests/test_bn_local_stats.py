"""Local-statistics batch_norm under data parallelism (VERDICT round-5 #2).

Reference semantics: the multi-device engine replicates batch_norm per
device, so statistics are per-device local and never synchronized
(multi_devices_graph_pass.cc replicates compute ops; batch_norm_op.cc
computes stats over its own batch). The default here is SyncBN (GSPMD
reduces over the sharded batch — numerically stronger); FLAGS_bn_local_stats
or BuildStrategy.bn_local_stats selects the reference behavior, removing
every per-step BN-stat all-reduce from the compiled HLO.
"""
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.profiler import collective_audit


def _n_collectives(hlo_texts):
    """The step's sums: its all-reduce instructions. (A dp mesh holds
    what the optimizer updates as dp shards, so a step also gathers
    weights; those are no synchronization of statistics.)"""
    return len(collective_audit(hlo_texts).get('all-reduce', ()))


def _build(nhwc=False, seed=7):
    fmt = 'NHWC' if nhwc else 'NCHW'
    prog, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[3, 8, 8], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='int64')
        if nhwc:
            x = fluid.layers.transpose(x, perm=[0, 2, 3, 1])
        c = fluid.layers.conv2d(x, 8, 3, padding=1, bias_attr=False,
                                data_format=fmt)
        b = fluid.layers.batch_norm(c, act='relu', data_layout=fmt)
        c2 = fluid.layers.conv2d(b, 8, 3, padding=1, bias_attr=False,
                                 data_format=fmt)
        b2 = fluid.layers.batch_norm(c2, act='relu', data_layout=fmt)
        p = fluid.layers.pool2d(b2, pool_type='avg', global_pooling=True,
                                data_format=fmt)
        pred = fluid.layers.fc(p, size=10, act='softmax')
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return prog, startup, loss


def _train(local, n_devices=None, steps=5, nhwc=False, audit=False):
    import jax
    devices = jax.devices()[:n_devices] if n_devices else jax.devices()
    fluid.flags.set_flags({'FLAGS_bn_local_stats': local})
    try:
        with unique_name.guard():
            prog, startup, loss = _build(nhwc=nhwc)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                        main_program=prog, scope=scope,
                                        devices=devices)
            rng = np.random.RandomState(0)
            xb = rng.rand(16, 3, 8, 8).astype('f4')
            yb = rng.randint(0, 10, (16, 1)).astype('int64')
            losses = [float(pe.run(fetch_list=[loss.name],
                                   feed={'x': xb, 'y': yb})[0])
                      for _ in range(steps)]
            n_coll = _n_collectives(
                pe.compiled_hlo_texts()) if audit else None
        return losses, n_coll
    finally:
        fluid.flags.set_flags({'FLAGS_bn_local_stats': False})


def test_local_equals_sync_on_one_device():
    """With dp=1 the local shard IS the global batch: bit-equal paths."""
    sync, _ = _train(False, n_devices=1)
    local, _ = _train(True, n_devices=1)
    np.testing.assert_allclose(sync, local, rtol=1e-6)


def test_local_mode_trains_and_tracks_sync():
    """8-way local-stats training converges and stays near the SyncBN
    trajectory (stats over bs/8 shards differ, so tolerance is loose —
    this is the reference's numerics, not an approximation of ours)."""
    sync, _ = _train(False)
    local, _ = _train(True)
    assert local[-1] < local[0]
    np.testing.assert_allclose(sync, local, rtol=0.05, atol=0.02)


def test_collective_audit_local_vs_sync():
    """The done-criterion from the round-4 verdict: local mode's n=8
    compiled HLO carries exactly ONE collective (the coalesced gradient
    all-reduce, BN scale/bias grad psums folded in); sync mode carries a
    BN-stat all-reduce per BN per direction on the critical path."""
    _, n_sync = _train(False, steps=1, audit=True)
    _, n_local = _train(True, steps=1, audit=True)
    assert n_sync >= 5          # 2 BNs x (fwd + bwd stats) + grad AR
    assert n_local == 1


def test_local_mode_nhwc():
    """Local stats compose with the channels-last layout."""
    losses, n_local = _train(True, steps=3, nhwc=True, audit=True)
    assert losses[-1] < losses[0]
    assert n_local == 1


def test_build_strategy_knob():
    """BuildStrategy.bn_local_stats is a PER-EXECUTOR override (the
    reference's build-strategy surface, details/build_strategy.h): it
    must not mutate process-global state — a sibling PE with a default
    strategy in the same process keeps SyncBN."""
    bs = fluid.BuildStrategy()
    assert hasattr(bs, 'bn_local_stats') and bs.bn_local_stats is False
    bs.bn_local_stats = True
    feed_rng = np.random.RandomState(0)
    feed = {'x': feed_rng.rand(16, 3, 8, 8).astype('f4'),
            'y': feed_rng.randint(0, 10, (16, 1)).astype('int64')}

    def audit(build_strategy):
        with unique_name.guard():
            prog, startup, loss = _build()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            pe = fluid.ParallelExecutor(use_cuda=False,
                                        loss_name=loss.name,
                                        main_program=prog, scope=scope,
                                        build_strategy=build_strategy)
            pe.run(fetch_list=[loss.name], feed=feed)
            return _n_collectives(pe.compiled_hlo_texts())

    assert audit(bs) == 1                      # local for THIS executor
    assert not fluid.flags.get_flag('bn_local_stats')   # no global leak
    assert audit(None) > 1                     # sibling PE keeps SyncBN
