"""MoE top-k capacity dispatch (ops/moe_ops.py): parity with the dense
reference at ample capacity, FLOPs independence of the expert count (the
property that makes expert parallelism scale), capacity dropping, and
the load-balance aux loss."""
import math

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.parallel.layers import moe_layer

import jax
import jax.numpy as jnp


def _moe_prog(E, k, dispatch, capacity_factor=2.0, S=8, D=16, H=32,
              seed=5, aux_loss=False):
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = seed
    with program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[S, D], dtype='float32',
                              append_batch_size=False)
        out = moe_layer(x, num_experts=E, hidden_size=H, k=k,
                        dispatch=dispatch, capacity_factor=capacity_factor,
                        aux_loss=aux_loss)
        if aux_loss:
            out, aux = out
        loss = fluid.layers.mean(out)
    fetch = [out, loss] + ([aux] if aux_loss else [])
    return prog, startup, fetch


def test_topk_matches_dense_at_ample_capacity():
    """With capacity >= S (no token can be dropped), topk dispatch must
    reproduce the dense top-k-masked combine exactly."""
    S, E, k = 8, 4, 2
    xv = np.random.RandomState(3).rand(S, 16).astype('float32')
    outs = {}
    for mode in ('dense', 'topk'):
        prog, startup, fetch = _moe_prog(
            E, k, mode, capacity_factor=float(E * S), S=S)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(startup)
            o, l = exe.run(prog, feed={'x': xv},
                           fetch_list=fetch)
        outs[mode] = np.asarray(o)
    np.testing.assert_allclose(outs['topk'], outs['dense'],
                               rtol=1e-5, atol=1e-6)


def test_topk_flops_independent_of_expert_count():
    """Expert compute is E*C*(D*H) with E*C = k*S*cf: doubling E at fixed
    k must NOT double FLOPs (the dense path does exactly that)."""
    S, D, H, k, cf = 32, 64, 128, 2, 1.0

    def flops_for(E, mode):
        def f(x, gate, w_up, w_down):
            from paddle_tpu.ops.moe_ops import (_topk_route,
                                                _dispatch_combine)
            route = _topk_route(gate, k)
            if mode == 'dense':
                h = jax.nn.relu(jnp.einsum('sd,edh->seh', x, w_up))
                return jnp.einsum('seh,ehd,se->sd', h, w_down, route)
            C = max(1, int(math.ceil(S * k * cf / E)))
            disp, comb = _dispatch_combine(route, k, C)
            ein = jnp.einsum('sec,sd->ecd', disp, x)
            h = jax.nn.relu(jnp.einsum('ecd,edh->ech', ein, w_up))
            y = jnp.einsum('ech,ehd->ecd', h, w_down)
            return jnp.einsum('sec,ecd->sd', comb, y)
        args = (jnp.zeros((S, D)), jnp.zeros((S, E)),
                jnp.zeros((E, D, H)), jnp.zeros((E, H, D)))
        comp = jax.jit(f).lower(*args).compile()
        (an,) = comp.cost_analysis() if isinstance(comp.cost_analysis(),
                                                   list) \
            else (comp.cost_analysis(),)
        return an['flops']

    f4, f16 = flops_for(4, 'topk'), flops_for(16, 'topk')
    d4, d16 = flops_for(4, 'dense'), flops_for(16, 'dense')
    assert d16 > 2.5 * d4          # dense scales ~linearly in E
    assert f16 < 1.5 * f4, (f4, f16)   # topk stays ~flat


def test_capacity_dropping_zeroes_overflow_tokens():
    """With capacity 1 and all tokens routed to one expert, only the
    first token (slot-major priority) gets expert output; the rest
    combine to zero."""
    from paddle_tpu.ops.moe_ops import _dispatch_combine
    S, E = 4, 2
    route = np.zeros((S, E), 'float32')
    route[:, 0] = 1.0                     # everyone wants expert 0
    disp, comb = _dispatch_combine(jnp.asarray(route), 1, 1)
    disp = np.asarray(disp)
    assert disp[0, 0, 0] == 1.0
    assert disp[1:].sum() == 0.0          # overflow dropped
    assert np.asarray(comb)[1:].sum() == 0.0


def test_moe_topk_trains_and_drops_loss():
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 5
    with program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[8, 16], dtype='float32',
                              append_batch_size=False)
        y = fluid.layers.data(name='y', shape=[8, 16], dtype='float32',
                              append_batch_size=False)
        out, aux = moe_layer(x, num_experts=4, hidden_size=32, k=2,
                             aux_loss=True)
        mse = fluid.layers.mean(
            fluid.layers.square_error_cost(out, y))
        loss = fluid.layers.elementwise_add(
            mse, fluid.layers.scale(aux, scale=0.01))
        fluid.optimizer.Adam(0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    xv = rng.rand(8, 16).astype('float32')
    yv = np.tanh(xv)
    first = last = None
    for _ in range(60):
        l, a = exe.run(prog, feed={'x': xv, 'y': yv},
                       fetch_list=[loss, aux])
        if first is None:
            first = float(np.asarray(l))
        last = float(np.asarray(l))
    assert np.isfinite(last) and last < 0.5 * first, (first, last)
    # aux = E * sum(f*P): ~1 near balance (f is the hard top-1 count, P
    # the soft mean, so it can sit slightly either side of 1)
    assert 0.5 < float(np.asarray(a)) < 4.0


def test_moe_topk_on_ep_mesh():
    """topk dispatch compiles and runs under the ep axis on the 8-device
    mesh (GSPMD turns the dispatch einsum into collectives)."""
    from paddle_tpu.parallel import DistributedStrategy
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 5
    with program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[8, 16], dtype='float32',
                              append_batch_size=False)
        out = moe_layer(x, num_experts=4, hidden_size=32, k=2)
        loss = fluid.layers.mean(out)
        fluid.optimizer.SGD(0.1).minimize(loss)
    if len(jax.devices()) < 8:
        pytest.skip('needs 8 virtual devices')
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    pe = fluid.ParallelExecutor(use_cuda=True, main_program=prog,
                                scope=scope, devices=jax.devices()[:8],
                                strategy=DistributedStrategy(dp=2, ep=4))
    xv = np.random.RandomState(1).rand(8, 16).astype('float32')
    l1, = pe.run(fetch_list=[loss.name], feed={'x': xv})
    l2, = pe.run(fetch_list=[loss.name], feed={'x': xv})
    assert np.isfinite(np.asarray(l1)).all()
    assert not np.allclose(np.asarray(l1), np.asarray(l2))


# -- the served expert layer (op moe_experts) ---------------------------------

def _served_case(rows, experts, held, offset, k, seed=0, d=24, lat=16, f=20):
    rng = np.random.default_rng(seed)
    return dict(
        x=rng.normal(size=(rows, d)).astype('f4'),
        lat=rng.normal(size=(rows, lat)).astype('f4'),
        router=(rng.normal(size=(d, experts)) / math.sqrt(d)).astype('f4'),
        bias=np.zeros(experts, 'f4'),
        w1=rng.normal(size=(held, lat, f)).astype('f4'),
        w2=rng.normal(size=(held, f, lat)).astype('f4'),
        k=k, offset=offset)


def _served_op(case, live=None, length=None):
    """moe_experts through the executor -> (out [rows, lat], stats [4])."""
    prog, startup = Program(), Program()
    feed = {n: case[n] for n in ('x', 'lat', 'router', 'bias', 'w1', 'w2')}
    with program_guard(prog, startup):
        v = {n: fluid.layers.data(n, list(a.shape), dtype='float32',
                                  append_batch_size=False)
             for n, a in feed.items()}
        ins = {'X': [v['x']], 'Lat': [v['lat']], 'RouterW': [v['router']],
               'Bias': [v['bias']], 'W1': [v['w1']], 'W2': [v['w2']]}
        for slot, name, value in (('Live', 'live', live),
                                  ('Len', 'len', length)):
            if value is not None:
                feed[name] = np.asarray(value, 'i4')
                ins[slot] = [fluid.layers.data(
                    name, list(feed[name].shape), dtype='int32',
                    append_batch_size=False)]
        block = prog.global_block()
        out = block.create_var(name='out', dtype='float32')
        stats = block.create_var(name='stats', dtype='int32')
        block.append_op(type='moe_experts', inputs=ins,
                        outputs={'Out': [out], 'Stats': [stats]},
                        attrs={'top_k': case['k'], 'scale': 2.5,
                               'expert_offset': case['offset']})
    return fluid.Executor(fluid.CPUPlace()).run(prog, feed=feed,
                                                fetch_list=[out, stats])


def _served_numpy(case, rows_live=None):
    """The layer's definition, a loop over rows and their experts, in
    float64: sigmoid scores, the k largest, renormalised and scaled."""
    x, lat = case['x'].astype(np.float64), case['lat'].astype(np.float64)
    s = 1 / (1 + np.exp(-(x @ case['router'].astype(np.float64))))
    out = np.zeros_like(lat)
    pairs, touched = 0, set()
    for r in range(x.shape[0]):
        if rows_live is not None and not rows_live[r]:
            continue
        top = np.argsort(-s[r])[:case['k']]
        for e in top:
            j = e - case['offset']
            if 0 <= j < case['w1'].shape[0]:
                h = np.maximum(lat[r] @ case['w1'][j], 0) ** 2
                out[r] += 2.5 * s[r, e] / s[r, top].sum() \
                    * (h @ case['w2'][j])
                pairs += 1
                touched.add(j)
    return out, pairs, len(touched)


@pytest.mark.parametrize('rows,experts,held,offset,k', [
    (37, 512, 64, 128, 22),     # the published router, a chip's share
    (5, 32, 32, 0, 22),         # every expert held: the uncut layer
    (64, 32, 4, 28, 22),        # the last share; most rows choose it all
])
def test_served_experts_drop_no_pair(rows, experts, held, offset, k):
    """Top-22 with no capacity: every pair of a row and a held expert
    has its product in the sum, whatever the rows chose, and the count
    of pairs not computed is 0."""
    case = _served_case(rows, experts, held, offset, k, seed=rows)
    want, pairs, touched = _served_numpy(case)
    out, stats = _served_op(case)
    assert np.abs(out - want).max() <= 1e-4 * np.abs(want).max()
    assert list(stats) == [pairs, touched, 0, 1]
    assert pairs > 0


def test_served_experts_dead_rows_choose_nothing():
    case = _served_case(12, 32, 8, 8, 22, seed=3)
    live = np.array([1, 0, 1, 1, 0, 0, 1, 1, 1, 0, 1, 1])
    want, pairs, touched = _served_numpy(case, live)
    out, stats = _served_op(case, live=live)
    assert np.abs(out - want).max() <= 1e-4 * np.abs(want).max()
    assert not out[live == 0].any()
    assert list(stats) == [pairs, touched, 0, 1]
    head = np.arange(12) < 7                     # a chunk's padded tail
    want, pairs, touched = _served_numpy(case, head)
    out, stats = _served_op(case, length=[7])
    assert np.abs(out - want).max() <= 1e-4 * np.abs(want).max()
    assert list(stats) == [pairs, touched, 0, 1]


def test_moe_ffn_is_refused_by_the_decode_transpiler_by_its_drops():
    """The training op stays refused, and the message says why and names
    the served op."""
    from paddle_tpu.transpiler.decode_transpiler import (
        DecodeTranspileError, extract_decode_spec)
    prog, _, _ = _moe_prog(4, 1, 'topk')
    with pytest.raises(DecodeTranspileError, match='drops.*moe_experts'):
        extract_decode_spec(prog)


def test_served_experts_have_no_backward_and_say_so():
    case = _served_case(4, 8, 8, 0, 2)
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        v = {n: fluid.layers.data(n, list(case[n].shape), dtype='float32',
                                  append_batch_size=False)
             for n in ('x', 'lat', 'router', 'bias', 'w1', 'w2')}
        v['lat'].stop_gradient = False
        out = prog.global_block().create_var(name='out', dtype='float32')
        out.stop_gradient = False
        prog.global_block().append_op(
            type='moe_experts',
            inputs={'X': [v['x']], 'Lat': [v['lat']],
                    'RouterW': [v['router']], 'Bias': [v['bias']],
                    'W1': [v['w1']], 'W2': [v['w2']]},
            outputs={'Out': [out]}, attrs={'top_k': 2})
        loss = fluid.layers.mean(out)
        with pytest.raises(NotImplementedError, match='moe_experts'):
            fluid.backward.append_backward(loss)


# -- group-limited choice and gated experts (the A.X-K1 form) -----------------

def _group_limited_numpy(s, n_group, topk_group, k):
    """A plain sort-based choice, float64: a group scores the sum of its
    two largest entries, the best groups, the k largest inside them;
    ties go to the lower index (a stable sort of the negated scores)."""
    rows, experts = s.shape
    chosen = np.zeros((rows, experts), bool)
    for r in range(rows):
        g = s[r].reshape(n_group, -1)
        score = np.sort(g, axis=1)[:, -2:].sum(1)
        kept = np.argsort(-score, kind='stable')[:topk_group]
        masked = np.full(experts, -np.inf)
        for j in kept:
            width = experts // n_group
            masked[j * width:(j + 1) * width] = g[j]
        chosen[r, np.argsort(-masked, kind='stable')[:k]] = True
    return chosen


@pytest.mark.parametrize('rows,experts,n_group,topk_group,k', [
    (41, 192, 8, 4, 8),         # the published gate
    (7, 32, 4, 2, 4),
    (16, 24, 3, 1, 5),          # one group kept
    (9, 16, 4, 4, 6),           # every group kept: no limit at all
])
def test_group_limited_choice_is_the_sort_based_one(rows, experts, n_group,
                                                    topk_group, k):
    import jax
    from paddle_tpu.ops import moe_ops
    rng = np.random.default_rng(rows)
    x = rng.normal(size=(rows, 24)).astype('f4')
    router = (rng.normal(size=(24, experts)) / math.sqrt(24)).astype('f4')
    bias = np.zeros(experts, 'f4')
    w = np.asarray(moe_ops.served_weights(x, router, bias, k, 2.5, n_group,
                                          topk_group))
    s = np.asarray(jax.nn.sigmoid(jax.numpy.matmul(
        x, router, precision='highest'))).astype(np.float64)
    chosen = _group_limited_numpy(s, n_group, topk_group, k)
    assert ((w != 0) == chosen).all()
    want = np.where(chosen, s, 0)
    want = 2.5 * want / want.sum(-1, keepdims=True)
    assert np.abs(w - want).max() < 1e-5
    if topk_group == n_group:
        free = np.asarray(moe_ops.served_weights(x, router, bias, k, 2.5))
        assert (free == w).all()


def test_one_group_is_the_choice_over_all_experts_to_the_letter():
    """n_group 1 and topk_group 1 are today's selection: the same
    jaxpr as a call that names neither."""
    import jax
    from paddle_tpu.ops import moe_ops
    x, router, bias = (np.zeros((5, 24), 'f4'), np.zeros((24, 32), 'f4'),
                       np.zeros(32, 'f4'))
    assert str(jax.make_jaxpr(
        lambda *a: moe_ops.served_weights(*a, 22, 5.0))(x, router, bias)) \
        == str(jax.make_jaxpr(
            lambda *a: moe_ops.served_weights(*a, 22, 5.0, 1, 1))(
                x, router, bias))


def test_gated_experts_of_three_matrices_through_the_op():
    """With W3 beside W1 the held experts are W2 (silu(W1 x) * W3 x),
    routed group-limited, on x itself: against a loop in float64."""
    rng = np.random.default_rng(11)
    rows, d, f, experts, held, offset, k = 23, 24, 20, 32, 8, 8, 4
    feed = {'x': rng.normal(size=(rows, d)).astype('f4'),
            'router': (rng.normal(size=(d, experts))
                       / math.sqrt(d)).astype('f4'),
            'bias': np.zeros(experts, 'f4'),
            'w1': rng.normal(size=(held, d, f)).astype('f4'),
            'w3': rng.normal(size=(held, d, f)).astype('f4'),
            'w2': rng.normal(size=(held, f, d)).astype('f4')}
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        v = {n: fluid.layers.data(n, list(a.shape), dtype='float32',
                                  append_batch_size=False)
             for n, a in feed.items()}
        block = prog.global_block()
        out = block.create_var(name='out', dtype='float32')
        stats = block.create_var(name='stats', dtype='int32')
        block.append_op(
            type='moe_experts',
            inputs={'X': [v['x']], 'Lat': [v['x']], 'RouterW': [v['router']],
                    'Bias': [v['bias']], 'W1': [v['w1']], 'W3': [v['w3']],
                    'W2': [v['w2']]},
            outputs={'Out': [out], 'Stats': [stats]},
            attrs={'top_k': k, 'scale': 2.5, 'expert_offset': offset,
                   'n_group': 4, 'topk_group': 2})
    got, counted = fluid.Executor(fluid.CPUPlace()).run(
        prog, feed=feed, fetch_list=[out, stats])
    x = feed['x'].astype(np.float64)
    s = 1 / (1 + np.exp(-(x @ feed['router'].astype(np.float64))))
    chosen = _group_limited_numpy(s, 4, 2, k)
    want = np.zeros_like(x)
    pairs, touched = 0, set()
    for r in range(rows):
        for e in np.flatnonzero(chosen[r]):
            j = e - offset
            if 0 <= j < held:
                g = x[r] @ feed['w1'][j]
                h = g / (1 + np.exp(-g)) * (x[r] @ feed['w3'][j])
                want[r] += 2.5 * s[r, e] / s[r, chosen[r]].sum() \
                    * (h @ feed['w2'][j])
                pairs += 1
                touched.add(j)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    assert list(counted) == [pairs, len(touched), 0, 1] and pairs > 0
