"""The delta rule with a decay a key channel (ops kda_chunk / kda_step,
ops/delta_rule_ops.py; the Pallas step kernel pallas/gated_delta.kda_step):
the step form, the chunk form and a token-by-token loop agree on random
inputs whose decays differ between the channels of one head, across
block and sub-block boundaries, with a padded tail, from a non-zero
state; a decay held equal over a head's channels reproduces the
per-head ops; the kernel in interpret mode against the plain
composition; and the ops inside a program, in their three forms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.ops import delta_rule_ops as dr
from paddle_tpu.pallas import gated_delta

H, DK, DV = 2, 16, 8


def _inputs(t, seed=0, fast=3):
    """q, k normalised, v, beta in (0, 2), g = log alpha with alpha over
    0.3-0.999 and `fast` channels a head that forget within a token or
    two (exp(-8)): a sum of their logs passes float32's exp after a few
    dozen tokens, so a form that took exp(-c) alone would overflow."""
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(t, H, DK)).astype('f4') for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * DK ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(t, H, DV)).astype('f4')
    beta = rng.uniform(0, 2, size=(t, H)).astype('f4')
    g = np.log(rng.uniform(0.3, 0.999, size=(t, H, DK))).astype('f4')
    g[:, :, :fast] = -8.0
    s0 = rng.normal(size=(H, DK, DV)).astype('f4')
    return q, k, v, beta, g, s0


def _loop(s0, q, k, v, beta, g):
    s, out = jnp.asarray(s0), []
    for t in range(q.shape[0]):
        o, s = dr.kda_step(s, q[t], k[t], v[t], beta[t], g[t])
        out.append(o)
    return jnp.stack(out), s


@pytest.mark.parametrize('t, block, sub', [
    (96, 32, 8),        # three blocks of four sub-blocks
    (96, 96, 16),       # one block, six sub-blocks
    (64, 64, 16),       # the served shape
    (48, 16, 16),       # a block that is one sub-block
    (128, 64, 32)])
def test_chunk_form_is_the_token_loop(t, block, sub):
    q, k, v, beta, g, s0 = _inputs(t, seed=t)
    want_o, want_s = _loop(s0, q, k, v, beta, g)
    o, s = dr.kda_chunk(jnp.asarray(s0), q, k, v, beta, g, block, sub)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s, want_s, rtol=2e-5, atol=2e-5)


def test_no_exponent_is_positive_however_fast_a_channel_forgets():
    """512 tokens of channels at exp(-8) a token: the running sum
    reaches -4096 and exp(+4096) is inf in float32; the chunk form's
    output stays finite and right."""
    q, k, v, beta, g, s0 = _inputs(512, seed=5, fast=8)
    want_o, want_s = _loop(s0, q, k, v, beta, g)
    o, s = dr.kda_chunk(jnp.asarray(s0), q, k, v, beta, g, 512, 16)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-4)


def test_a_decay_equal_over_the_channels_is_the_per_head_rule():
    q, k, v, beta, _, s0 = _inputs(96, seed=3)
    g1 = np.log(np.random.default_rng(4).uniform(
        0.3, 0.999, size=(96, H))).astype('f4')
    same = np.repeat(g1[..., None], DK, axis=-1)
    o_h, s_h = dr.delta_chunk(jnp.asarray(s0), q, k, v, beta, g1, 32)
    o_c, s_c = dr.kda_chunk(jnp.asarray(s0), q, k, v, beta, same, 32, 16)
    np.testing.assert_allclose(o_c, o_h, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_c, s_h, rtol=1e-5, atol=1e-5)
    o1, s1 = dr.delta_step(jnp.asarray(s0), q[0], k[0], v[0], beta[0], g1[0])
    o2, s2 = dr.kda_step(jnp.asarray(s0), q[0], k[0], v[0], beta[0], same[0])
    np.testing.assert_allclose(o2, o1, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(s2, s1, rtol=1e-6, atol=1e-6)


def test_kda_inputs_normalises_and_gates():
    rng = np.random.default_rng(7)
    qkv = rng.normal(size=(5, H * (2 * DK + DV))).astype('f4')
    gate = rng.normal(size=(5, H * DK)).astype('f4')
    b = rng.normal(size=(5, H)).astype('f4')
    a_log = rng.normal(size=(H,)).astype('f4') * 0.2
    dt_bias = rng.normal(size=(H * DK,)).astype('f4')
    q, k, v, beta, g = dr.kda_inputs(qkv, gate, b, a_log, dt_bias, H, DK,
                                     DV, 2.0)
    np.testing.assert_allclose(np.linalg.norm(k, axis=-1), 1.0, rtol=1e-4)
    np.testing.assert_allclose(np.linalg.norm(q, axis=-1), DK ** -0.5,
                               rtol=1e-4)
    assert g.shape == (5, H, DK) and (np.asarray(g) < 0).all()
    want = -np.exp(a_log)[:, None] * np.log1p(
        np.exp((gate + dt_bias).reshape(5, H, DK)))
    np.testing.assert_allclose(g, want, rtol=1e-5)
    np.testing.assert_allclose(beta, 2.0 / (1.0 + np.exp(-b)), rtol=1e-5)
    # the channels of one head differ
    assert np.ptp(np.asarray(g), axis=-1).min() > 0


@pytest.mark.parametrize('live', [[1, 0, 1, 1], [0, 0, 0, 0], [1, 1, 1, 1]])
def test_the_step_kernel_is_the_plain_composition(live):
    rng = np.random.default_rng(11)
    S, h, dk, dv = 4, 2, 128, 128
    state = rng.normal(size=(S, h, dk, dv)).astype('f4')
    q, k, g = (rng.normal(size=(S, h, dk)).astype('f4') for _ in range(3))
    g = -np.abs(g) * 0.2
    v = rng.normal(size=(S, h, dv)).astype('f4')
    beta = rng.uniform(0, 2, size=(S, h)).astype('f4')
    live = jnp.asarray(live, bool)
    want_o, want_s = dr.kda_step_reference(jnp.asarray(state), q, k, v,
                                           beta, g, live)
    o, s = gated_delta.kda_step(jnp.asarray(state), q, k, v, beta,
                                jnp.exp(g), live, interpret=True)
    np.testing.assert_allclose(
        o, jnp.where(live[:, None, None], want_o, 0.0), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-5)


# -- the ops inside a program --------------------------------------------------

def _run_op(op_type, feeds, outs, attrs):
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        block = main.global_block()
        ins = {}
        for slot, value in feeds.items():
            var = fluid.layers.data(
                slot.lower(), list(value.shape), append_batch_size=False,
                dtype=str(value.dtype))
            ins[slot] = [var]
        out_vars = {slot: [block.create_var(name='out_' + slot.lower(),
                                            dtype='float32')]
                    for slot in outs}
        block.append_op(type=op_type, inputs=ins, outputs=out_vars,
                        attrs=attrs)
    exe = fluid.Executor(fluid.CPUPlace())
    return exe.run(main, feed={s.lower(): v for s, v in feeds.items()},
                   fetch_list=[out_vars[s][0] for s in outs])


def _raw(t, seed, batch=1):
    rng = np.random.default_rng(seed)
    return {
        'QKV': rng.normal(size=(batch, t, H * (2 * DK + DV))).astype('f4'),
        'G': rng.normal(size=(batch, t, H * DK)).astype('f4') - 2.0,
        'B': rng.normal(size=(batch, t, H)).astype('f4'),
        'ALog': (0.2 * rng.normal(size=(H,))).astype('f4'),
        'DtBias': rng.normal(size=(H * DK,)).astype('f4')}


ATTRS = {'heads': H, 'key_dim': DK, 'value_dim': DV, 'beta_scale': 2.0}
T = dr.KDA_BLOCK + 24                       # over a block's edge, padded


def _want(raw, s0, n=None):
    q, k, v, beta, g = dr.kda_inputs(raw['QKV'][0], raw['G'][0], raw['B'][0],
                                     raw['ALog'], raw['DtBias'], H, DK, DV,
                                     2.0)
    n = q.shape[0] if n is None else n
    return _loop(s0, q[:n], k[:n], v[:n], beta[:n], g[:n])


def test_chunk_op_whole_sequence_from_zero_state():
    raw = _raw(T, seed=1)                   # not a whole block: padded
    out, = _run_op('kda_chunk', raw, ['Out'], ATTRS)
    want, _ = _want(raw, np.zeros((H, DK, DV), 'f4'))
    np.testing.assert_allclose(out[0], np.asarray(want).reshape(T, -1),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('n, reset', [(T, 0), (71, 0), (71, 1), (1, 0)])
def test_chunk_op_on_a_slot_leaves_a_padded_tail_untouched(n, reset):
    raw = _raw(T, seed=2)
    state = np.random.default_rng(3).normal(
        size=(3, H, DK, DV)).astype('f4')
    feeds = dict(raw, State=state, Slot=np.array([1], 'i4'),
                 Len=np.array([n], 'i4'), Reset=np.array([reset], 'i4'))
    out, new = _run_op('kda_chunk', feeds, ['Out', 'StateOut'], ATTRS)
    s0 = np.zeros_like(state[1]) if reset else state[1]
    want_o, want_s = _want(raw, s0, n)
    np.testing.assert_allclose(out[0, :n], np.asarray(want_o).reshape(n, -1),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(new[1], want_s, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(new[[0, 2]], state[[0, 2]])


@pytest.mark.parametrize('interpret', [False, True])
def test_step_op_updates_the_live_lanes_only(interpret):
    rng = np.random.default_rng(5)
    h, dk, dv = (2, 128, 128) if interpret else (H, DK, DV)
    raw = {'QKV': rng.normal(size=(3, 1, h * (2 * dk + dv))).astype('f4'),
           'G': rng.normal(size=(3, 1, h * dk)).astype('f4') - 2.0,
           'B': rng.normal(size=(3, 1, h)).astype('f4'),
           'ALog': (0.2 * rng.normal(size=(h,))).astype('f4'),
           'DtBias': rng.normal(size=(h * dk,)).astype('f4')}
    state = rng.normal(size=(3, h, dk, dv)).astype('f4')
    feeds = dict(raw, State=state, Live=np.array([1, 0, 1], 'i4'))
    attrs = dict(ATTRS, heads=h, key_dim=dk, value_dim=dv)
    fluid.set_flags({'pallas_interpret': interpret})
    try:
        out, new = _run_op('kda_step', feeds, ['Out', 'StateOut'], attrs)
    finally:
        fluid.set_flags({'pallas_interpret': False})
    q, k, v, beta, g = dr.kda_inputs(raw['QKV'][:, 0], raw['G'][:, 0],
                                     raw['B'][:, 0], raw['ALog'],
                                     raw['DtBias'], h, dk, dv, 2.0)
    want_o, want_s = dr.kda_step(jnp.asarray(state), q, k, v, beta, g)
    for lane in (0, 2):
        np.testing.assert_allclose(out[lane, 0],
                                   np.asarray(want_o[lane]).reshape(-1),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(new[lane], want_s[lane], rtol=1e-5,
                                   atol=1e-5)
    np.testing.assert_array_equal(new[1], state[1])


def test_neither_op_has_a_backward():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data('x', [1, 8, H * (2 * DK + DV)],
                              append_batch_size=False)
        x.stop_gradient = False
        block = main.global_block()
        gate = fluid.layers.fc(x, H * DK, num_flatten_dims=2)
        b = fluid.layers.fc(x, H, num_flatten_dims=2)
        out = block.create_var(name='o', dtype='float32')
        block.append_op(
            type='kda_chunk',
            inputs={'QKV': [x], 'G': [gate], 'B': [b],
                    'ALog': [fluid.layers.create_parameter([H], 'float32')],
                    'DtBias': [fluid.layers.create_parameter(
                        [H * DK], 'float32')]},
            outputs={'Out': [out]}, attrs=ATTRS)
        with pytest.raises(NotImplementedError, match='kda_chunk'):
            fluid.backward.append_backward(fluid.layers.mean(out))
