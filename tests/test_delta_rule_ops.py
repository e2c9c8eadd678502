"""The gated delta rule's ops (ops/delta_rule_ops.py) at tiny widths:
the chunked rule against the token-by-token recurrence, the step forms
against the chunk forms, state that a padded tail or an idle lane must
leave alone, and the Pallas step kernel in interpret mode."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.ops import delta_rule_ops as dr

H, DK, DV, K = 2, 8, 16, 4
C = H * (2 * DK + DV)
SLOTS = 3


def _inputs(rng, t, ba_scale=1.0, ba_shift=(0.0, 0.0)):
    qkv = rng.normal(size=(1, t, C)).astype('f4')
    ba = ba_scale * rng.normal(size=(1, t, 2 * H)).astype('f4')
    ba[..., :H] += ba_shift[0]
    ba[..., H:] += ba_shift[1]
    return qkv, ba, (0.3 * rng.normal(size=H)).astype('f4'), \
        rng.normal(size=H).astype('f4')


def _recurrence(qkv, ba, a_log, dt_bias, s0=None):
    """Token by token, in float64 numpy: the rule as its docstring has it."""
    q, k, v, beta, g = (np.asarray(a, np.float64) for a in dr.delta_inputs(
        jnp.asarray(qkv[0]), jnp.asarray(ba[0]), jnp.asarray(a_log),
        jnp.asarray(dt_bias), H, DK, DV, 2.0))
    s = np.zeros((H, DK, DV)) if s0 is None else np.asarray(s0, np.float64)
    out = []
    for t in range(q.shape[0]):
        s = s * np.exp(g[t])[:, None, None]
        u = beta[t][:, None] * (v[t] - np.einsum('hkv,hk->hv', s, k[t]))
        s = s + k[t][:, :, None] * u[:, None, :]
        out.append(np.einsum('hkv,hk->hv', s, q[t]))
    return np.stack(out).reshape(len(out), H * DV), s


def _run_ops(build, feed, fetch_names, scope_vars=None):
    """One program of raw ops through the executor."""
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        fetch = build(prog.global_block())
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        for name, value in (scope_vars or {}).items():
            scope.set_var(name, value)
        out = exe.run(prog, feed=feed, fetch_list=fetch)
        return out, {n: np.asarray(scope.find_var(n)) for n in fetch_names}


def _var(block, name, shape, dtype='float32', persistable=False):
    return block.create_var(name=name, shape=shape, dtype=dtype,
                            persistable=persistable, stop_gradient=True)


def _delta_op(block, op_type, t, lanes, extra_in=None, extra_out=None,
              block_size=None):
    qkv = _var(block, 'qkv', [lanes, t, C])
    ba = _var(block, 'ba', [lanes, t, 2 * H])
    a_log, dt_bias = _var(block, 'a_log', [H]), _var(block, 'dt_bias', [H])
    out = _var(block, 'out', None)
    attrs = {'heads': H, 'key_dim': DK, 'value_dim': DV, 'beta_scale': 2.0}
    if block_size:
        attrs['block'] = block_size
    block.append_op(type=op_type,
                    inputs=dict({'QKV': [qkv], 'BA': [ba], 'ALog': [a_log],
                                 'DtBias': [dt_bias]}, **(extra_in or {})),
                    outputs=dict({'Out': [out]}, **(extra_out or {})),
                    attrs=attrs)
    return [out]


def _chunk(qkv, ba, a_log, dt_bias, block_size, state=None, slot=0, n=None,
           reset=0):
    """gated_delta_chunk through the executor; with `state` the stateful
    form. Returns (out [T, H*DV], state after or None)."""
    t = qkv.shape[1]
    feed = {'qkv': qkv, 'ba': ba, 'a_log': a_log, 'dt_bias': dt_bias}
    if state is None:
        (out,), _ = _run_ops(
            lambda b: _delta_op(b, 'gated_delta_chunk', t, 1,
                                block_size=block_size), feed, [])
        return out[0], None

    def build(b):
        s = _var(b, 'state', list(state.shape), persistable=True)
        at = {k: [_var(b, k.lower(), [1], 'int32')]
              for k in ('Slot', 'Len', 'Reset')}
        return _delta_op(b, 'gated_delta_chunk', t, 1,
                         dict(at, State=[s]), {'StateOut': [s]}, block_size)
    feed.update(slot=np.array([slot], 'i4'),
                len=np.array([t if n is None else n], 'i4'),
                reset=np.array([reset], 'i4'))
    (out,), after = _run_ops(build, feed, ['state'], {'state': state})
    return out[0], after['state']


@pytest.mark.parametrize('t,block_size', [(64, 16), (50, 16), (16, 16),
                                          (5, 16), (33, 8)])
def test_chunked_rule_is_the_token_recurrence(t, block_size):
    rng = np.random.default_rng(t)
    qkv, ba, a_log, dt_bias = _inputs(rng, t)
    want, _ = _recurrence(qkv, ba, a_log, dt_bias)
    got, _ = _chunk(qkv, ba, a_log, dt_bias, block_size)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_chunk_starts_from_the_slots_state_and_leaves_it_there():
    rng = np.random.default_rng(1)
    qkv, ba, a_log, dt_bias = _inputs(rng, 40)
    state = rng.normal(size=(SLOTS, H, DK, DV)).astype('f4')
    want, s_want = _recurrence(qkv, ba, a_log, dt_bias, state[1])
    got, after = _chunk(qkv, ba, a_log, dt_bias, 16, state, slot=1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(after[1], s_want, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(after[[0, 2]], state[[0, 2]])


def test_reset_starts_from_zero_whatever_the_slot_held():
    rng = np.random.default_rng(2)
    qkv, ba, a_log, dt_bias = _inputs(rng, 24)
    state = rng.normal(size=(SLOTS, H, DK, DV)).astype('f4')
    want, s_want = _recurrence(qkv, ba, a_log, dt_bias)
    got, after = _chunk(qkv, ba, a_log, dt_bias, 16, state, slot=2, reset=1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(after[2], s_want, rtol=2e-4, atol=2e-5)


def test_padded_tail_leaves_the_state_untouched():
    rng = np.random.default_rng(3)
    qkv, ba, a_log, dt_bias = _inputs(rng, 32)
    state = rng.normal(size=(SLOTS, H, DK, DV)).astype('f4')
    n = 21
    want, s_want = _recurrence(qkv[:, :n], ba[:, :n], a_log, dt_bias, state[0])
    got, after = _chunk(qkv, ba, a_log, dt_bias, 16, state, slot=0, n=n)
    np.testing.assert_allclose(got[:n], want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(after[0], s_want, rtol=2e-4, atol=2e-5)


def test_beta_near_two_and_alpha_near_one():
    """The edge of stability: I - beta k k^T has an eigenvalue near -1
    and almost nothing decays, over several blocks."""
    rng = np.random.default_rng(4)
    qkv, ba, a_log, dt_bias = _inputs(rng, 96, ba_scale=0.1,
                                      ba_shift=(6.0, -9.0))
    q, k, v, beta, g = dr.delta_inputs(
        jnp.asarray(qkv[0]), jnp.asarray(ba[0]), jnp.asarray(a_log),
        jnp.asarray(dt_bias), H, DK, DV, 2.0)
    assert float(beta.min()) > 1.98 and float(jnp.exp(g).min()) > 0.999
    want, _ = _recurrence(qkv, ba, a_log, dt_bias)
    got, _ = _chunk(qkv, ba, a_log, dt_bias, 16)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def _step(qkv, ba, a_log, dt_bias, state, live):
    """gated_delta_step through the executor: qkv [S, 1, C]."""
    def build(b):
        s = _var(b, 'state', list(state.shape), persistable=True)
        lv = _var(b, 'live', [state.shape[0]], 'int32')
        return _delta_op(b, 'gated_delta_step', 1, state.shape[0],
                         {'State': [s], 'Live': [lv]}, {'StateOut': [s]})
    (out,), after = _run_ops(
        build, {'qkv': qkv, 'ba': ba, 'a_log': a_log, 'dt_bias': dt_bias,
                'live': np.asarray(live, 'i4')}, ['state'], {'state': state})
    return out[:, 0], after['state']


def _step_case(seed):
    rng = np.random.default_rng(seed)
    qkv = rng.normal(size=(SLOTS, 1, C)).astype('f4')
    ba = rng.normal(size=(SLOTS, 1, 2 * H)).astype('f4')
    a_log = (0.3 * rng.normal(size=H)).astype('f4')
    dt_bias = rng.normal(size=H).astype('f4')
    state = rng.normal(size=(SLOTS, H, DK, DV)).astype('f4')
    return qkv, ba, a_log, dt_bias, state


def test_step_form_is_the_chunk_form_one_token_a_lane():
    qkv, ba, a_log, dt_bias, state = _step_case(5)
    got, after = _step(qkv, ba, a_log, dt_bias, state, [1, 1, 1])
    for lane in range(SLOTS):
        want, s_want = _chunk(qkv[lane:lane + 1], ba[lane:lane + 1], a_log,
                              dt_bias, 16, state, slot=lane)
        np.testing.assert_allclose(got[lane], want[0], rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(after[lane], s_want[lane], rtol=2e-5,
                                   atol=2e-6)


def test_step_leaves_idle_lanes_untouched():
    qkv, ba, a_log, dt_bias, state = _step_case(6)
    _, after = _step(qkv, ba, a_log, dt_bias, state, [0, 1, 0])
    np.testing.assert_array_equal(after[[0, 2]], state[[0, 2]])
    assert np.abs(after[1] - state[1]).max() > 1e-3


@pytest.mark.parametrize('live', [[1, 0, 1], [0, 0, 0], [1, 1, 1],
                                  [0, 0, 1]])
def test_step_kernel_in_interpret_mode_is_the_composition(live):
    qkv, ba, a_log, dt_bias, state = _step_case(7)
    want, s_want = _step(qkv, ba, a_log, dt_bias, state, live)
    fluid.set_flags({'pallas_interpret': True})
    try:
        got, s_got = _step(qkv, ba, a_log, dt_bias, state, live)
    finally:
        fluid.set_flags({'pallas_interpret': False})
    lanes = np.asarray(live, bool)
    np.testing.assert_allclose(got[lanes], want[lanes], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s_got, s_want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(s_got[~lanes], state[~lanes])


# -- short_conv ---------------------------------------------------------------

def _conv(x, w, state=None, **at):
    """short_conv through the executor: whole sequence without `state`,
    chunk form with slot/len/reset, step form with live."""
    def build(b):
        xv = _var(b, 'x', list(x.shape))
        wv = _var(b, 'w', list(w.shape))
        out = _var(b, 'out', None)
        ins, outs = {'X': [xv], 'W': [wv]}, {'Out': [out]}
        if state is not None:
            s = _var(b, 'state', list(state.shape), persistable=True)
            ins['State'], outs['StateOut'] = [s], [s]
            for k in at:
                ins[k.capitalize()] = [_var(b, k, [len(at[k])], 'int32')]
        b.append_op(type='short_conv', inputs=ins, outputs=outs)
        return [out]
    feed = dict({'x': x, 'w': w},
                **{k: np.asarray(v, 'i4') for k, v in at.items()})
    (out,), after = _run_ops(build, feed,
                             ['state'] if state is not None else [],
                             {'state': state} if state is not None else None)
    return out, after.get('state')


def _conv_numpy(x, w):
    t = x.shape[0]
    xx = np.concatenate([np.zeros((K - 1, x.shape[1])), x])
    acc = sum(xx[j:j + t] * w[j] for j in range(K))
    return acc / (1.0 + np.exp(-acc))


def test_short_conv_whole_sequence_is_causal_depthwise_then_silu():
    rng = np.random.default_rng(8)
    x = rng.normal(size=(2, 11, 6)).astype('f4')
    w = rng.normal(size=(K, 6)).astype('f4')
    out, _ = _conv(x, w)
    for b in range(2):
        np.testing.assert_allclose(out[b], _conv_numpy(x[b], w), rtol=1e-5,
                                   atol=1e-6)


def test_short_conv_by_chunks_is_the_whole_sequence():
    rng = np.random.default_rng(9)
    x = rng.normal(size=(1, 16, 6)).astype('f4')
    w = rng.normal(size=(K, 6)).astype('f4')
    want, _ = _conv(x, w)
    state = rng.normal(size=(SLOTS, K - 1, 6)).astype('f4')
    # 8 rows from a reset slot, then 8 of which 5 are real, then 3 more
    a, state1 = _conv(x[:, :8], w, state, slot=[1], len=[8], reset=[1])
    b, state2 = _conv(x[:, 8:16], w, state1, slot=[1], len=[5], reset=[0])
    np.testing.assert_array_equal(state2[1], x[0, 10:13])  # rows before 13
    c, state3 = _conv(np.concatenate([x[:, 13:16], x[:, :5]], 1), w, state2,
                      slot=[1], len=[3], reset=[0])
    got = np.concatenate([a[0], b[0, :5], c[0, :3]])
    np.testing.assert_allclose(got, want[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(state3[[0, 2]], state[[0, 2]])


def test_short_conv_step_is_its_chunk_form_and_spares_idle_lanes():
    rng = np.random.default_rng(10)
    x = rng.normal(size=(SLOTS, 1, 6)).astype('f4')
    w = rng.normal(size=(K, 6)).astype('f4')
    state = rng.normal(size=(SLOTS, K - 1, 6)).astype('f4')
    got, after = _conv(x, w, state, live=[1, 0, 1])
    np.testing.assert_array_equal(after[1], state[1])
    for lane in (0, 2):
        want, s_want = _conv(x[lane:lane + 1], w, state, slot=[lane],
                             len=[1], reset=[0])
        np.testing.assert_allclose(got[lane], want[0], rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(after[lane], s_want[lane])


# -- norms and the missing backward ---------------------------------------------

def test_rms_norm_forward_and_gradient():
    rng = np.random.default_rng(11)
    xv = rng.normal(size=(3, 5, 8)).astype('f4')
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        x = fluid.layers.data('x', [3, 5, 8], append_batch_size=False)
        x.stop_gradient = False
        y = fluid.layers.rms_norm(x, begin_norm_axis=2, epsilon=1e-6)
        loss = fluid.layers.mean(fluid.layers.square(y))
        fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        got, gx = exe.run(prog, feed={'x': xv},
                          fetch_list=[y, 'x@GRAD'])
    want = xv / np.sqrt((xv ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def f(a):
        return jnp.mean((a * jax.lax.rsqrt(
            jnp.mean(a * a, -1, keepdims=True) + 1e-6)) ** 2)
    np.testing.assert_allclose(gx, jax.grad(f)(jnp.asarray(xv)), rtol=1e-4,
                               atol=1e-6)


@pytest.mark.parametrize('op_type', ['gated_delta_chunk', 'short_conv'])
def test_no_backward_and_the_error_names_the_op(op_type):
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        x = fluid.layers.data('x', [1, 8, C], append_batch_size=False)
        x.stop_gradient = False
        if op_type == 'short_conv':
            y = fluid.layers.short_conv(x, kernel=K)
        else:
            ba = fluid.layers.fc(x, 2 * H, num_flatten_dims=2)
            y = fluid.layers.gated_delta_rule(x, ba, H, DK, DV)
        loss = fluid.layers.mean(y)
        with pytest.raises(NotImplementedError, match=op_type):
            fluid.backward.append_backward(loss)
