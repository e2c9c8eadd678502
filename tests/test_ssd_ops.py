"""The state-space mixer's ops (ops/ssd_ops.py) at tiny widths: the
chunked recurrence against the step against the token-by-token scan,
state that a padded tail or an idle lane must leave alone, the Pallas
step kernel in interpret mode, the grouped gated norm and the
convolution's bias."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.ops import ssd_ops

H, P, G, N, K = 4, 8, 2, 128, 4          # N a whole lane tile: the kernel's
C = H * P + 2 * G * N
SLOTS = 3
# float32 sums of the same products in another order (the chunked form
# against the scan), relative to the largest value; the state kept in
# bfloat16 misses it by more than 20 times (test below)
TOL = 2e-5


def _raw(rng, lanes, t):
    xbc = rng.normal(size=(lanes, t, C)).astype('f4')
    dt = (rng.normal(size=(lanes, t, H)) - 2.0).astype('f4')
    return (xbc, dt, np.log(rng.uniform(1, 16, size=H)).astype('f4'),
            rng.normal(size=H).astype('f4'), rng.normal(size=H).astype('f4'))


def _scan(xbc, dt, a_log, dt_bias, d, h0=None):
    """Token by token in float64 numpy: the recurrence as the module's
    docstring has it, one stream."""
    x, b, c, dt, log_a = (np.asarray(a, np.float64) for a in
                          ssd_ops.ssd_inputs(jnp.asarray(xbc),
                                             jnp.asarray(dt),
                                             jnp.asarray(a_log),
                                             jnp.asarray(dt_bias),
                                             H, P, G, N))
    h = np.zeros((H, P, N)) if h0 is None else np.asarray(h0, np.float64)
    out = []
    for t in range(x.shape[0]):
        bt, ct = (np.repeat(v[t], H // G, axis=0) for v in (b, c))
        h = h * np.exp(log_a[t])[:, None, None] \
            + (dt[t][:, None] * x[t])[:, :, None] * bt[:, None, :]
        out.append(np.einsum('hpn,hn->hp', h, ct) + d[:, None] * x[t])
    return np.stack(out).reshape(len(out), H * P), h


def _var(block, name, shape, dtype='float32', persistable=False):
    return block.create_var(name=name, shape=shape, dtype=dtype,
                            persistable=persistable, stop_gradient=True)


def _run(op_type, xbc, dt, a_log, dt_bias, d, state=None, block_size=None,
         **at):
    """ssd_chunk / ssd_step through the executor; with `state` the
    stateful form (slot/len/reset for a chunk, live for a step). Returns
    (out, state after or None)."""
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        b = prog.global_block()
        ins = {'XBC': [_var(b, 'xbc', list(xbc.shape))],
               'DT': [_var(b, 'dt', list(dt.shape))],
               'ALog': [_var(b, 'a_log', [H])],
               'DtBias': [_var(b, 'dt_bias', [H])], 'D': [_var(b, 'd', [H])]}
        out = _var(b, 'out', None)
        outs = {'Out': [out]}
        if state is not None:
            s = _var(b, 'state', list(state.shape), persistable=True)
            ins['State'], outs['StateOut'] = [s], [s]
            for k, v in at.items():
                ins[k.capitalize()] = [_var(b, k, [len(v)], 'int32')]
        attrs = {'heads': H, 'head_dim': P, 'groups': G, 'state': N}
        if block_size:
            attrs['block'] = block_size
        b.append_op(type=op_type, inputs=ins, outputs=outs, attrs=attrs)
    feed = dict({'xbc': xbc, 'dt': dt, 'a_log': a_log, 'dt_bias': dt_bias,
                 'd': d}, **{k: np.asarray(v, 'i4') for k, v in at.items()})
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        if state is not None:
            scope.set_var('state', state)
        got, = exe.run(prog, feed=feed, fetch_list=[out])
        after = np.asarray(scope.find_var('state')) \
            if state is not None else None
    return np.asarray(got), after


def _close(got, want):
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


@pytest.mark.parametrize('t,block_size', [(64, 16), (50, 16), (16, 16),
                                          (7, 16), (37, 128)])
def test_chunked_form_is_the_token_scan(t, block_size):
    raw = _raw(np.random.default_rng(t), 1, t)
    want, _ = _scan(raw[0][0], raw[1][0], *raw[2:])
    got, _ = _run('ssd_chunk', *raw, block_size=block_size)
    _close(got[0], want)


def test_chunk_starts_from_the_slots_state_and_leaves_it_there():
    rng = np.random.default_rng(1)
    raw = _raw(rng, 1, 40)
    state = rng.normal(size=(SLOTS, H, P, N)).astype('f4')
    want, h_want = _scan(raw[0][0], raw[1][0], *raw[2:], h0=state[1])
    got, after = _run('ssd_chunk', *raw, state=state, block_size=16,
                      slot=[1], len=[40], reset=[0])
    _close(got[0], want)
    _close(after[1], h_want)
    np.testing.assert_array_equal(after[[0, 2]], state[[0, 2]])
    # and by two chunks as by one
    first = tuple(a[:, :24] if a.ndim == 3 else a for a in raw)
    second = tuple(a[:, 24:] if a.ndim == 3 else a for a in raw)
    _, mid = _run('ssd_chunk', *first, state=state, block_size=16,
                  slot=[1], len=[24], reset=[0])
    got2, after2 = _run('ssd_chunk', *second, state=mid, block_size=16,
                        slot=[1], len=[16], reset=[0])
    _close(got2[0], want[24:])
    _close(after2[1], h_want)


def test_reset_starts_from_zero_whatever_the_slot_held():
    rng = np.random.default_rng(2)
    raw = _raw(rng, 1, 20)
    state = rng.normal(size=(SLOTS, H, P, N)).astype('f4')
    want, h_want = _scan(raw[0][0], raw[1][0], *raw[2:])
    got, after = _run('ssd_chunk', *raw, state=state, block_size=16,
                      slot=[2], len=[20], reset=[1])
    _close(got[0], want)
    _close(after[2], h_want)


def test_padded_tail_leaves_the_state_untouched():
    rng = np.random.default_rng(3)
    raw = _raw(rng, 1, 32)
    state = rng.normal(size=(SLOTS, H, P, N)).astype('f4')
    want, h_want = _scan(raw[0][0, :11], raw[1][0, :11], *raw[2:],
                         h0=state[0])
    got, after = _run('ssd_chunk', *raw, state=state, block_size=16,
                      slot=[0], len=[11], reset=[0])
    _close(got[0, :11], want)
    _close(after[0], h_want)


def _step_case(seed):
    rng = np.random.default_rng(seed)
    return _raw(rng, SLOTS, 1), \
        rng.normal(size=(SLOTS, H, P, N)).astype('f4')


def test_step_form_is_the_chunk_form_and_the_scan_one_token_a_lane():
    raw, state = _step_case(4)
    got, after = _run('ssd_step', *raw, state=state, live=[1, 1, 1])
    for lane in range(SLOTS):
        one = tuple(a[lane:lane + 1] if a.ndim == 3 else a for a in raw)
        want, h_want = _scan(one[0][0], one[1][0], *one[2:], h0=state[lane])
        via_chunk, h_chunk = _run('ssd_chunk', *one, state=state,
                                  slot=[lane], len=[1], reset=[0])
        _close(got[lane], want)
        _close(after[lane], h_want)
        _close(via_chunk[0], want)
        _close(h_chunk[lane], h_want)


def test_step_leaves_idle_lanes_untouched():
    raw, state = _step_case(5)
    _, after = _run('ssd_step', *raw, state=state, live=[0, 1, 0])
    np.testing.assert_array_equal(after[[0, 2]], state[[0, 2]])
    assert np.abs(after[1] - state[1]).max() > 0


@pytest.mark.parametrize('live', [[1, 0, 1], [0, 0, 0], [1, 1, 1],
                                  [0, 0, 1]])
def test_step_kernel_in_interpret_mode_is_the_composition(live):
    raw, state = _step_case(6)
    want, s_want = _run('ssd_step', *raw, state=state, live=live)
    fluid.set_flags({'pallas_interpret': True})
    try:
        got, s_got = _run('ssd_step', *raw, state=state, live=live)
    finally:
        fluid.set_flags({'pallas_interpret': False})
    lanes = np.asarray(live, bool)
    np.testing.assert_allclose(got[lanes], want[lanes], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_got, s_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(s_got[~lanes], state[~lanes])


def test_state_kept_in_bfloat16_misses_the_tolerance():
    rng = np.random.default_rng(7)
    raw = _raw(rng, 1, 48)
    want, _ = _scan(raw[0][0], raw[1][0], *raw[2:])
    x, b, c, dt, log_a = ssd_ops.ssd_inputs(
        jnp.asarray(raw[0][0]), jnp.asarray(raw[1][0]),
        jnp.asarray(raw[2]), jnp.asarray(raw[3]), H, P, G, N)
    h, out = jnp.zeros((H, P, N), jnp.bfloat16), []
    for t in range(48):
        y, new = ssd_ops.ssd_step(h.astype(jnp.float32), x[t], b[t], c[t],
                                  dt[t], log_a[t], jnp.asarray(raw[4]))
        h = new.astype(jnp.bfloat16)
        out.append(np.asarray(y).reshape(-1))
    assert np.abs(np.stack(out) - want).max() > 20 * TOL * np.abs(want).max()


def test_gated_group_norm_is_an_rms_norm_a_group():
    rng = np.random.default_rng(8)
    x = rng.normal(size=(2, 5, 32)).astype('f4')
    z = rng.normal(size=(2, 5, 32)).astype('f4')
    scale = (1 + 0.1 * rng.normal(size=32)).astype('f4')
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        b = prog.global_block()
        out = _var(b, 'y', None)
        b.append_op(type='gated_group_norm',
                    inputs={'X': [_var(b, 'x', [2, 5, 32])],
                            'Z': [_var(b, 'z', [2, 5, 32])],
                            'Scale': [_var(b, 'scale', [32])]},
                    outputs={'Y': [out]},
                    attrs={'groups': 4, 'epsilon': 1e-5})
    got, = fluid.Executor(fluid.CPUPlace()).run(
        prog, feed={'x': x, 'z': z, 'scale': scale}, fetch_list=[out])
    y = (x * z / (1 + np.exp(-z))).astype(np.float64).reshape(2, 5, 4, 8)
    want = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want.reshape(2, 5, 32) * scale,
                               rtol=1e-5, atol=1e-6)


def test_short_conv_adds_its_bias_before_the_silu():
    rng = np.random.default_rng(9)
    x = rng.normal(size=(1, 9, 6)).astype('f4')
    w = rng.normal(size=(K, 6)).astype('f4')
    bias = rng.normal(size=6).astype('f4')
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        b = prog.global_block()
        out = _var(b, 'out', None)
        b.append_op(type='short_conv',
                    inputs={'X': [_var(b, 'x', [1, 9, 6])],
                            'W': [_var(b, 'w', [K, 6])],
                            'Bias': [_var(b, 'bias', [6])]},
                    outputs={'Out': [out]})
    got, = fluid.Executor(fluid.CPUPlace()).run(
        prog, feed={'x': x, 'w': w, 'bias': bias}, fetch_list=[out])
    xx = np.concatenate([np.zeros((K - 1, 6)), x[0]])
    acc = sum(xx[j:j + 9] * w[j] for j in range(K)) + bias
    np.testing.assert_allclose(got[0], acc / (1 + np.exp(-acc)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('op_type', ['ssd_chunk', 'gated_group_norm'])
def test_no_backward_and_the_error_names_the_op(op_type):
    x = fluid.layers.data('x', [1, 4, C], append_batch_size=False)
    x.stop_gradient = False
    block = fluid.default_main_program().global_block()
    out = _var(block, 'out.' + op_type, None)
    out.stop_gradient = False
    if op_type == 'ssd_chunk':
        block.append_op(
            type=op_type,
            inputs={'XBC': [x], 'DT': [_var(block, 'dt', [1, 4, H])],
                    'ALog': [_var(block, 'a_log', [H])],
                    'DtBias': [_var(block, 'dt_bias', [H])],
                    'D': [_var(block, 'd', [H])]},
            outputs={'Out': [out]},
            attrs={'heads': H, 'head_dim': P, 'groups': G, 'state': N})
    else:
        block.append_op(type=op_type,
                        inputs={'X': [x], 'Z': [x],
                                'Scale': [_var(block, 'scale', [C])]},
                        outputs={'Y': [out]}, attrs={'groups': 2})
    loss = fluid.layers.mean(out)
    with pytest.raises(NotImplementedError, match=op_type):
        fluid.backward.append_backward(loss)
