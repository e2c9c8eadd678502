"""Multi-axis parallelism: tp/sp/ep shardings on the 8-device virtual CPU
mesh -- numeric parity against single-device execution (the analog of the
reference's parallel_executor_test_base.py compare-losses pattern, run
with dp x tp instead of pure dp)."""
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.models import transformer
from paddle_tpu.parallel import DistributedStrategy
from paddle_tpu.parallel.layers import (column_parallel_fc,
                                        row_parallel_fc, moe_layer)

import jax


def _transformer_progs(cfg, seed=11):
    prog, startup = Program(), Program()
    prog.random_seed = seed
    startup.random_seed = seed
    with program_guard(prog, startup):
        tokens = fluid.layers.data(name='tokens', shape=[cfg.max_len, 1],
                                   dtype='int64')
        labels = fluid.layers.data(name='labels', shape=[cfg.max_len, 1],
                                   dtype='int64')
        probs, avg_cost = transformer.train_network(tokens, labels, cfg)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
    return prog, startup, avg_cost


def _batch(cfg, B=8):
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab, (B, cfg.max_len, 1)).astype('int64')
    labs = np.roll(toks, -1, axis=1)
    return {'tokens': toks, 'labels': labs}


def test_transformer_tp_sp_matches_serial():
    cfg_serial = transformer.TransformerConfig(
        vocab=64, dim=16, heads=2, layers=2, ffn=32, max_len=8,
        use_tp=False, use_sp=False)
    cfg_par = transformer.TransformerConfig(
        vocab=64, dim=16, heads=2, layers=2, ffn=32, max_len=8,
        use_tp=True, use_sp=True)

    feed = _batch(cfg_serial)

    losses = {}
    for key, cfg, strategy in [
            ('serial', cfg_serial, None),
            ('tp_sp', cfg_par, DistributedStrategy(dp=2, tp=2, sp=2))]:
        prog, startup, avg_cost = _transformer_progs(cfg)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        pe = fluid.ParallelExecutor(
            use_cuda=True, loss_name=avg_cost.name, main_program=prog,
            scope=scope,
            devices=jax.devices()[:1] if strategy is None
            else jax.devices()[:8],
            strategy=strategy)
        vals = []
        for _ in range(3):
            l, = pe.run(fetch_list=[avg_cost.name], feed=feed)
            vals.append(float(np.asarray(l).reshape(-1)[0]))
        losses[key] = vals

    # identical init (same seed) => same loss trajectory modulo float
    # reduction order
    np.testing.assert_allclose(losses['serial'], losses['tp_sp'],
                               rtol=2e-3)


def test_column_row_parallel_fc_pair_matches_fc():
    """Megatron pair == one serial two-layer MLP numerically."""
    prog, startup = Program(), Program()
    prog.random_seed = 3
    startup.random_seed = 3
    with program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[8, 16], dtype='float32',
                              append_batch_size=False)
        x3 = fluid.layers.reshape(x, shape=[2, 4, 16])
        h = column_parallel_fc(x3, 32, act='relu')
        y = row_parallel_fc(h, 16)
        out = fluid.layers.reduce_sum(y)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    pe = fluid.ParallelExecutor(use_cuda=True, main_program=prog,
                                scope=scope, devices=jax.devices()[:8],
                                strategy=DistributedStrategy(dp=2, tp=4))
    xv = np.random.RandomState(0).rand(8, 16).astype('float32')
    r_par, = pe.run(fetch_list=[out.name], feed={'x': xv})

    # serial: same program, single device (annotations become no-ops)
    scope2 = fluid.Scope()
    exe2 = fluid.Executor(fluid.CPUPlace())
    prog2, startup2 = Program(), Program()
    prog2.random_seed = 3
    startup2.random_seed = 3
    with program_guard(prog2, startup2):
        x = fluid.layers.data(name='x', shape=[8, 16], dtype='float32',
                              append_batch_size=False)
        x3 = fluid.layers.reshape(x, shape=[2, 4, 16])
        h = column_parallel_fc(x3, 32, act='relu')
        y = row_parallel_fc(h, 16)
        out2 = fluid.layers.reduce_sum(y)
    exe2.run(startup2, scope=scope2)
    with fluid.scope_guard(scope2):
        r_ser, = exe2.run(prog2, feed={'x': xv}, fetch_list=[out2])
    np.testing.assert_allclose(np.asarray(r_par), np.asarray(r_ser),
                               rtol=1e-4)


def test_moe_expert_parallel_runs():
    prog, startup = Program(), Program()
    prog.random_seed = 5
    startup.random_seed = 5
    with program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[4, 16], dtype='float32',
                              append_batch_size=False)
        y = moe_layer(x, num_experts=4, hidden_size=32)
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    pe = fluid.ParallelExecutor(use_cuda=True, main_program=prog,
                                scope=scope, devices=jax.devices()[:8],
                                strategy=DistributedStrategy(dp=2, ep=4))
    xv = np.random.RandomState(1).rand(4, 16).astype('float32')
    l1, = pe.run(fetch_list=[loss.name], feed={'x': xv})
    l2, = pe.run(fetch_list=[loss.name], feed={'x': xv})
    assert np.isfinite(np.asarray(l1)).all()
    assert not np.allclose(np.asarray(l1), np.asarray(l2))  # sgd stepped


def test_transformer_moe_trains():
    cfg = transformer.TransformerConfig(
        vocab=64, dim=16, heads=2, layers=1, ffn=32, max_len=8,
        moe_experts=2, use_tp=False, use_sp=False)
    prog, startup, avg_cost = _transformer_progs(cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = _batch(cfg, B=4)
    first = last = None
    for _ in range(15):
        l, = exe.run(prog, feed=feed, fetch_list=[avg_cost])
        if first is None:
            first = float(l)
        last = float(l)
    assert np.isfinite(last) and last < first


def test_pipeline_parallel_matches_serial_and_trains():
    """GPipe schedule over 'pp': exact parity with serial stage stack and
    nonzero gradients."""
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.parallel.pipeline import (pipeline_apply,
                                              stack_stage_params)
    S, M, mb, D = 4, 8, 2, 16
    mesh = Mesh(np.array(jax.devices()[:S]), ('pp',))
    rng = np.random.RandomState(0)
    per_stage = [{'w': jnp.asarray(rng.randn(D, D).astype('f4') * 0.1),
                  'b': jnp.asarray(rng.randn(D).astype('f4') * 0.1)}
                 for _ in range(S)]
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(rng.randn(M, mb, D).astype('f4'))

    def stage_fn(p, v):
        return jnp.tanh(v @ p['w'] + p['b'])

    out = pipeline_apply(stage_fn, mesh, M, stacked, x)
    ref = x
    for p in per_stage:
        ref = jnp.tanh(ref @ p['w'] + p['b'])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def loss_fn(params, x):
        return jnp.mean(pipeline_apply(stage_fn, mesh, M, params, x) ** 2)

    g = jax.jit(jax.grad(loss_fn))(stacked, x)
    assert float(jnp.linalg.norm(g['w'])) > 0


def test_zero1_sharded_optimizer_state():
    """sharded_optimizer=True: Adam moments sharded over dp, loss matches
    replicated run."""
    results = {}
    for key, sharded in [('replicated', False), ('zero1', True)]:
        prog, startup = Program(), Program()
        prog.random_seed = startup.random_seed = 9
        with program_guard(prog, startup):
            x = fluid.layers.data(name='x', shape=[16], dtype='float32')
            y = fluid.layers.data(name='y', shape=[1], dtype='float32')
            h = fluid.layers.fc(input=x, size=32, act='relu')
            pred = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        pe = fluid.ParallelExecutor(
            use_cuda=True, loss_name=loss.name, main_program=prog,
            scope=scope, devices=jax.devices()[:8],
            strategy=DistributedStrategy(dp=8, sharded_optimizer=sharded))
        rng = np.random.RandomState(0)
        xv = rng.rand(16, 16).astype('f4')
        yv = xv.sum(1, keepdims=True).astype('f4')
        vals = [float(np.asarray(
            pe.run(fetch_list=[loss.name], feed={'x': xv, 'y': yv})[0]))
            for _ in range(4)]
        results[key] = vals
        if sharded:
            # a moment accumulator really is dp-sharded
            moment_names = [n for n in scope.local_var_names()
                            if 'moment' in n.lower() or 'velocity' in n]
            sharded_any = False
            for n in moment_names:
                v = scope.find_var(n)
                if v is not None and hasattr(v, 'sharding') and \
                        'dp' in str(v.sharding):
                    sharded_any = True
            assert sharded_any, moment_names
    np.testing.assert_allclose(results['replicated'], results['zero1'],
                               rtol=2e-3)


def test_reduce_strategy_knob_drives_zero1():
    """Setting only the reference-API BuildStrategy.ReduceStrategy.Reduce
    (no DistributedStrategy) must shard optimizer state -- the knob used
    to be accepted-and-ignored (reference details/build_strategy.h,
    multi_devices_graph_pass.cc:413-422)."""
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 9
    with program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    bs = fluid.BuildStrategy()
    bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    pe = fluid.ParallelExecutor(
        use_cuda=True, loss_name=loss.name, main_program=prog,
        scope=scope, devices=jax.devices()[:8], build_strategy=bs)
    rng = np.random.RandomState(0)
    xv = rng.rand(16, 16).astype('f4')
    yv = xv.sum(1, keepdims=True).astype('f4')
    val = pe.run(fetch_list=[loss.name], feed={'x': xv, 'y': yv})[0]
    assert np.isfinite(np.asarray(val)).all()
    sharded_any = False
    for n in scope.local_var_names():
        if 'moment' in n.lower():
            v = scope.find_var(n)
            if v is not None and 'dp' in str(getattr(v, 'sharding', '')):
                sharded_any = True
    assert sharded_any


def test_zero3_sharded_params():
    """sharded_params=True (ZeRO-3-style, beyond-reference): the
    Parameters themselves shard over dp — per-device shards really are
    1/dp of the parameter, and the training trajectory matches the
    replicated run."""
    results = {}
    for key, z3 in [('replicated', False), ('zero3', True)]:
        prog, startup = Program(), Program()
        prog.random_seed = startup.random_seed = 9
        with program_guard(prog, startup):
            # feature dim 10: the first fc weight is [10, 32] — dim 0
            # does NOT divide dp=8, so the first-divisible-dim rule
            # must shard axis 1 (and the moments with it)
            x = fluid.layers.data(name='x', shape=[10], dtype='float32')
            y = fluid.layers.data(name='y', shape=[1], dtype='float32')
            h = fluid.layers.fc(input=x, size=32, act='relu',
                                param_attr=fluid.ParamAttr(name='z3w'))
            pred = fluid.layers.fc(
                input=h, size=1,
                param_attr=fluid.ParamAttr(name='z3w2'))
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        pe = fluid.ParallelExecutor(
            use_cuda=True, loss_name=loss.name, main_program=prog,
            scope=scope, devices=jax.devices()[:8],
            strategy=DistributedStrategy(dp=8, sharded_params=z3))
        rng = np.random.RandomState(0)
        xv = rng.rand(16, 10).astype('f4')
        yv = xv.sum(1, keepdims=True).astype('f4')
        vals = [float(np.asarray(
            pe.run(fetch_list=[loss.name], feed={'x': xv, 'y': yv})[0]))
            for _ in range(4)]
        results[key] = vals
        if z3:
            w = scope.find_var('z3w')          # [10, 32] → axis-1 shard
            assert w is not None and 'dp' in str(w.sharding), w.sharding
            assert w.addressable_shards[0].data.shape == (10, 4), \
                w.addressable_shards[0].data.shape
            w2 = scope.find_var('z3w2')        # [32, 1] → axis-0 shard
            assert w2.addressable_shards[0].data.shape == (4, 1)
            # the moments follow the SAME first-divisible-dim rule:
            # an axis-1-sharded weight has axis-1-sharded moments
            moment_shapes = {
                tuple(np.asarray(v.addressable_shards[0].data).shape)
                for v in (scope.find_var(n)
                          for n in scope.local_var_names()
                          if 'moment' in n.lower())
                if v is not None and hasattr(v, 'addressable_shards')
                and v.ndim == 2}
            assert (10, 4) in moment_shapes, moment_shapes
    np.testing.assert_allclose(results['replicated'], results['zero3'],
                               rtol=2e-3)


# -- the rule of a dp mesh (PR 47): what an optimizer op updates is a dp
# shard by itself, no strategy, no knob ---------------------------------------

import json  # noqa: E402
import os  # noqa: E402

import pytest  # noqa: E402

from paddle_tpu.obs import telemetry  # noqa: E402

_OPTIMIZERS = {
    'adam': lambda: fluid.optimizer.Adam(learning_rate=0.01),
    'momentum': lambda: fluid.optimizer.Momentum(learning_rate=0.01,
                                                 momentum=0.9),
    'sgd': lambda: fluid.optimizer.SGD(learning_rate=0.01),
}


def _mlp(optimizer):
    """x[16] -> fc 32 -> fc 1: fc_0.w [16, 32] and fc_1.w [32, 1] divide
    by 8 on dimension 0, fc_0.b [32] too, fc_1.b [1] on none. Returns
    (main, startup, test program, loss, pred)."""
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 9
    with program_guard(prog, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(input=x, size=32, act='relu')
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        test_prog = prog.clone(for_test=True)
        _OPTIMIZERS[optimizer]().minimize(loss)
    return prog, startup, test_prog, loss, pred


def _feed():
    rng = np.random.RandomState(0)
    xv = rng.rand(16, 16).astype('f4')
    return {'x': xv, 'y': xv.sum(1, keepdims=True).astype('f4')}


def _params(prog):
    return [v.name for v in prog.global_block().vars.values()
            if isinstance(v, fluid.framework.Parameter)]


def _train(optimizer, devices, steps=3):
    """(losses, {parameter: whole array}, scope, executor, programs)
    after `steps` steps of a ParallelExecutor built with no strategy."""
    progs = _mlp(optimizer)
    prog, startup, _, loss, _ = progs
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    pe = fluid.ParallelExecutor(use_cuda=True, loss_name=loss.name,
                                main_program=prog, scope=scope,
                                devices=devices)
    losses = [float(np.asarray(pe.run(fetch_list=[loss.name],
                                      feed=_feed())[0]))
              for _ in range(steps)]
    names = _params(prog)
    # fetched through the executor with one more step's update: read the
    # scope instead, whole, as a fetch hands a parameter back
    values = {n: pe._to_numpy(scope.find_var(n)) for n in names}
    return losses, values, scope, pe, progs


@pytest.fixture
def gauges():
    was = telemetry.enabled()
    telemetry.enable()
    telemetry.reset()
    yield lambda: telemetry.snapshot()['gauges']
    if not was:
        telemetry.disable()


@pytest.mark.parametrize('optimizer', sorted(_OPTIMIZERS))
def test_default_dp_mesh_shards_the_update(optimizer, gauges):
    """A ParallelExecutor over dp = 8 with no strategy holds masters and
    accumulators as dp shards; loss and every parameter after three
    steps are those of the run that holds every variable whole (one
    device: the placement is not reached); what no dimension of divides
    stays a replica; the gauges read the bytes."""
    losses, values, scope, pe, progs = _train(optimizer, jax.devices()[:8])
    read = gauges()
    want_losses, want, _, _, _ = _train(optimizer, jax.devices()[:1])
    np.testing.assert_allclose(losses, want_losses, rtol=2e-3)
    assert sorted(values) == sorted(want) and len(values) == 4
    for name in values:
        np.testing.assert_allclose(values[name], want[name], rtol=2e-3,
                                   atol=1e-6, err_msg=name)
    updated = pe._updated_state()
    assert set(values) <= updated
    sharded = replicated = 0
    for name in updated:
        var = progs[0].global_block().vars[name]
        value = scope.find_var(name)
        divides = any(d % 8 == 0 for d in var.shape)
        assert (not value.is_fully_replicated) == divides, (name, var.shape)
        if divides:
            assert 'dp' in value.sharding.spec, (name, value.sharding)
            assert value.addressable_shards[0].data.size * 8 == value.size
            sharded += value.nbytes
        else:
            replicated += value.nbytes
    assert scope.find_var('fc_1.w_1').is_fully_replicated      # [1]
    assert not scope.find_var('fc_0.w_0').is_fully_replicated  # [16, 32]
    if optimizer != 'sgd':
        assert len(updated) > 4     # accumulators beside the masters
    assert read['parallel.update_sharded_bytes'] == sharded > 0
    assert read['parallel.update_replicated_bytes'] == replicated > 0


@pytest.mark.parametrize('knob', ['reduce_strategy', 'sharded_optimizer',
                                  'sharded_params'])
def test_accepted_knobs_change_nothing_the_rule_already_shards(knob):
    """BuildStrategy.ReduceStrategy.Reduce and the two DistributedStrategy
    fields are accepted; masters and accumulators lie where the rule
    puts them with or without."""
    prog, startup, _, loss, _ = _mlp('adam')
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    kwargs = {}
    if knob == 'reduce_strategy':
        bs = fluid.BuildStrategy()
        bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
        kwargs['build_strategy'] = bs
    else:
        kwargs['strategy'] = DistributedStrategy(dp=8, **{knob: True})
    asked = fluid.ParallelExecutor(use_cuda=True, loss_name=loss.name,
                                   main_program=prog, scope=scope,
                                   devices=jax.devices()[:8], **kwargs)
    plain = fluid.ParallelExecutor(use_cuda=True, loss_name=loss.name,
                                   main_program=prog, scope=scope,
                                   devices=jax.devices()[:8])
    for name in plain._updated_state():
        assert asked.state_sharding(name).spec == \
            plain.state_sharding(name).spec, name
    out = asked.run(fetch_list=[loss.name], feed=_feed())
    assert np.isfinite(np.asarray(out[0])).all()


def test_evaluation_on_shared_vars_leaves_the_state_where_it_lives(gauges):
    """Train, then evaluate with a ParallelExecutor built from the test
    program with share_vars_from (no optimizer op there): the shared
    state stays the training executor's dp shards, the gauges keep what
    the training executor placed, the prediction is the trained
    weights', and training goes on."""
    _, values, scope, pe, progs = _train('adam', jax.devices()[:8], steps=2)
    prog, _, test_prog, loss, pred = progs
    placed = gauges()
    before = {n: scope.find_var(n).sharding for n in pe._updated_state()}
    test_pe = fluid.ParallelExecutor(use_cuda=True, main_program=test_prog,
                                     share_vars_from=pe,
                                     devices=jax.devices()[:8])
    assert not test_pe._updated_state()
    for _ in range(2):      # the second call is the compiled step again
        got, = test_pe.run(fetch_list=[pred.name], feed=_feed())
    x = _feed()['x']
    hidden = np.maximum(x @ values['fc_0.w_0'] + values['fc_0.w_1'], 0)
    np.testing.assert_allclose(
        got, hidden @ values['fc_1.w_0'] + values['fc_1.w_1'],
        rtol=1e-5, atol=1e-6)
    for name, sharding in before.items():
        value = scope.find_var(name)
        assert value.sharding.is_equivalent_to(sharding, value.ndim), name
        assert test_pe.state_sharding(name) is pe.state_sharding(name)
    assert not scope.find_var('fc_0.w_0').is_fully_replicated
    assert gauges() == placed
    assert placed['parallel.update_sharded_bytes'] > 0
    out = pe.run(fetch_list=[loss.name], feed=_feed())
    assert np.isfinite(out[0]).all()
    for name, sharding in before.items():
        value = scope.find_var(name)
        assert value.sharding.is_equivalent_to(sharding, value.ndim), name


def test_evaluation_first_places_the_state_as_its_owner_would():
    """The sharing executor run BEFORE its owner's first step places the
    shared state where the owner's rule puts it, not as replicas."""
    prog, startup, test_prog, loss, pred = _mlp('adam')
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    pe = fluid.ParallelExecutor(use_cuda=True, loss_name=loss.name,
                                main_program=prog, scope=scope,
                                devices=jax.devices()[:8])
    test_pe = fluid.ParallelExecutor(use_cuda=True, main_program=test_prog,
                                     share_vars_from=pe,
                                     devices=jax.devices()[:8])
    test_pe.run(fetch_list=[pred.name], feed=_feed())
    assert not scope.find_var('fc_0.w_0').is_fully_replicated
    out = pe.run(fetch_list=[loss.name], feed=_feed())
    assert np.isfinite(out[0]).all()
    for name in pe._updated_state():
        value = scope.find_var(name)
        assert value.sharding.is_equivalent_to(pe.state_sharding(name),
                                               value.ndim), name


def test_fetched_parameter_is_whole():
    """A fetch of a variable held as shards hands back the whole array."""
    _, values, scope, pe, progs = _train('adam', jax.devices()[:8], steps=1)
    prog, loss = progs[0], progs[3]
    out = pe.run(fetch_list=[loss.name, 'fc_0.w_0'], feed=_feed())
    assert out[1].shape == (16, 32)
    np.testing.assert_array_equal(
        out[1], pe._to_numpy(scope.find_var('fc_0.w_0')))
    assert not np.array_equal(out[1], values['fc_0.w_0'])   # it trained


def test_one_device_places_nothing_and_traces_the_parents_step(gauges):
    """With one device the placement is not reached: every variable
    stays whole on the device, the gauges read no shard, and the step of
    a language model under AMP and Adam traces to what it traced to on
    the parent commit (tests/train_step_jaxpr.py recorded it there)."""
    _, _, scope, pe, _ = _train('adam', jax.devices()[:1], steps=1)
    for name in pe._updated_state():
        assert scope.find_var(name).is_fully_replicated, name
        assert pe.state_sharding(name) is pe._replicated
    assert gauges()['parallel.update_sharded_bytes'] == 0
    import train_step_jaxpr
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           'train_step_jaxpr_pr46.json')) as f:
        recorded = json.load(f)
    assert train_step_jaxpr.step_digest() == \
        recorded['one_device_train_step']


def test_trained_scope_saves_loads_and_serves_a_test_program(tmp_path):
    """save_persistables of a scope a dp mesh trained writes whole
    arrays; load_persistables into a fresh scope and an Executor run of
    the test program there give the trained weights' own prediction."""
    _, values, scope, pe, progs = _train('adam', jax.devices()[:8])
    prog, _, test_prog, _, pred = progs
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        fluid.io.save_persistables(exe, str(tmp_path), main_program=prog)
    fresh = fluid.Scope()
    with fluid.scope_guard(fresh):
        fluid.io.load_persistables(exe, str(tmp_path), main_program=prog)
        got, = exe.run(test_prog, feed=_feed(), fetch_list=[pred.name])
    for name in pe._updated_state():
        np.testing.assert_array_equal(
            np.asarray(fresh.find_var(name)),
            pe._to_numpy(scope.find_var(name)), err_msg=name)
    x = _feed()['x']
    hidden = np.maximum(x @ values['fc_0.w_0'] + values['fc_0.w_1'], 0)
    np.testing.assert_allclose(
        got, hidden @ values['fc_1.w_0'] + values['fc_1.w_1'],
        rtol=1e-5, atol=1e-6)


def test_fresh_values_put_in_the_scope_are_laid_out_by_the_step():
    """A script that puts new weights in a trained scope (plain,
    uncommitted arrays: the benchmark's check does) and runs a step with
    another fetch list: the step takes them, and hands the state back
    where state_sharding holds it."""
    import jax.numpy as jnp
    _, values, scope, pe, progs = _train('adam', jax.devices()[:8], steps=2)
    prog, loss = progs[0], progs[3]
    for name, value in values.items():
        scope.set_var(name, jnp.asarray(value) * 0.5)
    out = pe.run(fetch_list=[loss.name, 'fc_0.w_0@GRAD'], feed=_feed())
    assert np.isfinite(out[0]).all() and out[1].shape == (16, 32)
    for name in pe._updated_state():
        value = scope.find_var(name)
        assert value.sharding.is_equivalent_to(pe.state_sharding(name),
                                               value.ndim), name
