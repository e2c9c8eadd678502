"""The state-space mixer's ops (ops/ssd_ops.py) at tiny widths: the
chunked recurrence against the step against the token-by-token scan,
state that a padded tail or an idle lane must leave alone, the Pallas
step kernel in interpret mode (and what of it the chip's compiler is
handed), the grouped gated norm and the convolution's bias."""
import base64
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import ssd_ops
from paddle_tpu.pallas import ssd

H, P, G, N, K = 4, 8, 2, 128, 4          # N a whole lane tile: the kernel's
C = H * P + 2 * G * N
SLOTS = 3
# float32 sums of the same products in another order (the chunked form
# against the scan), relative to the largest value; the state kept in
# bfloat16 misses it by more than 20 times (test below)
TOL = 2e-5


def _raw(rng, lanes, t, dims=(H, P, G, N)):
    h, p, g, n = dims
    xbc = rng.normal(size=(lanes, t, h * p + 2 * g * n)).astype('f4')
    dt = (rng.normal(size=(lanes, t, h)) - 2.0).astype('f4')
    return (xbc, dt, np.log(rng.uniform(1, 16, size=h)).astype('f4'),
            rng.normal(size=h).astype('f4'), rng.normal(size=h).astype('f4'))


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
         dims=(H, P, G, N), **at):
    """ssd_chunk / ssd_step through the executor; with `state` the
    stateful form (slot/len/reset for a chunk, live for a step). Returns
    (out, state after or None)."""
    heads, head_dim, groups, n_state = dims
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        b = prog.global_block()
        ins = {'XBC': [_var(b, 'xbc', list(xbc.shape))],
               'DT': [_var(b, 'dt', list(dt.shape))],
               'ALog': [_var(b, 'a_log', [heads])],
               'DtBias': [_var(b, 'dt_bias', [heads])],
               'D': [_var(b, 'd', [heads])]}
        out = _var(b, 'out', None)
        outs = {'Out': [out]}
        if state is not None:
            s = _var(b, 'state', list(state.shape), persistable=True)
            ins['State'], outs['StateOut'] = [s], [s]
            for k, v in at.items():
                ins[k.capitalize()] = [_var(b, k, [len(v)], 'int32')]
        attrs = {'heads': heads, 'head_dim': head_dim, 'groups': groups,
                 'state': n_state}
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


@pytest.fixture
def interpret_kernel():
    fluid.set_flags({'pallas_interpret': True})
    yield
    fluid.set_flags({'pallas_interpret': False})


@pytest.fixture
def budget(monkeypatch):
    """Sets the VMEM the kernel may give the state's blocks, for the shapes
    traced while the test runs."""
    def give(nbytes):
        monkeypatch.setattr(ssd, '_STATE_VMEM_BYTES', nbytes)
        ssd.ssd_step.clear_cache()
    yield give
    ssd.ssd_step.clear_cache()


# name: ((H, P, G, N), bytes of VMEM for the state's blocks or None for the
# file's own, heads a block that gives)
KERNEL_SHAPES = {
    'whole_lane_two_groups': ((H, P, G, N), None, 4),
    'whole_lane_two_trips': ((16, 8, 2, N), None, 16),
    'one_group': ((4, 8, 1, N), None, 4),
    'two_lane_rows_of_state': ((4, 16, 2, 2 * N), None, 4),
    'three_tiles_a_head': ((6, 24, 2, N), None, 6),
    'two_blocks_a_group_each': ((8, 8, 2, N), 4 * 4 * 8 * N * 4, 4),
    'two_blocks_in_one_group': ((8, 16, 1, N), 4 * 4 * 16 * N * 4, 4),
}


def _kernel_case(shape, seed, budget):
    dims, nbytes, hb = KERNEL_SHAPES[shape]
    if nbytes:
        budget(nbytes)
    assert ssd.heads_per_block(dims[0], dims[1], dims[3], dims[2]) == hb
    rng = np.random.default_rng(seed)
    return dims, _raw(rng, SLOTS, 1, dims), \
        rng.normal(size=(SLOTS, dims[0], dims[1], dims[3])).astype('f4')


@pytest.mark.parametrize('live', [[1, 0, 1], [0, 0, 0], [1, 1, 1],
                                  [0, 0, 1]])
@pytest.mark.parametrize('shape', list(KERNEL_SHAPES))
def test_step_kernel_in_interpret_mode_is_the_composition(shape, live,
                                                          budget):
    """A lane in one block of one or two trips, in two blocks that are a
    group each, in two blocks inside the one group: the kernel's y and
    state are the composition's, and an idle lane's state is as it was."""
    dims, raw, state = _kernel_case(shape, 6, budget)
    want, s_want = _run('ssd_step', *raw, state=state, dims=dims, live=live)
    fluid.set_flags({'pallas_interpret': True})
    try:
        got, s_got = _run('ssd_step', *raw, state=state, dims=dims,
                          live=live)
    finally:
        fluid.set_flags({'pallas_interpret': False})
    lanes = np.asarray(live, bool)
    np.testing.assert_allclose(got[lanes], want[lanes], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_got, s_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(s_got[~lanes], state[~lanes])


@pytest.mark.parametrize('shape', ['whole_lane_two_groups',
                                   'two_blocks_a_group_each'])
def test_kernel_leaves_an_idle_lanes_state_bit_for_bit(shape, budget,
                                                       interpret_kernel):
    """Idle lanes are neither read nor written: what they hold comes back
    bit for bit, be it a NaN, an infinity, a negative zero or a
    denormal, and no live lane catches any of it."""
    dims, raw, state = _kernel_case(shape, 10, budget)
    odd = np.array([np.nan, np.inf, -np.inf, -0.0, 1e-42], 'f4')
    state[0].reshape(-1)[:5] = odd
    state[2].reshape(-1)[-5:] = odd
    out, after = _run('ssd_step', *raw, state=state, dims=dims,
                      live=[0, 1, 0])
    np.testing.assert_array_equal(after[[0, 2]].view('u4'),
                                  state[[0, 2]].view('u4'))
    assert np.isfinite(after[1]).all() and np.isfinite(out[1]).all()
    assert np.abs(after[1] - state[1]).max() > 0


@pytest.mark.parametrize('m', [1, 3, 8, 64])
def test_a_trips_tiles_are_summed_each_into_its_lane(m):
    """`_lane_sums` in a kernel of its own (interpret mode): lane i of
    row r holds tile i's row r summed along its 128 lanes, for 1, 3, 8
    and 64 tiles (a trip of either cell's is 64)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tiles = np.random.default_rng(m).normal(size=(m, 8, 128)).astype('f4')

    def body(t_ref, o_ref):
        o_ref[...] = ssd._lane_sums([t_ref[i] for i in range(m)])

    got = pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        interpret=pltpu.InterpretParams())(jnp.asarray(tiles))
    want = tiles.astype(np.float64).sum(axis=2).T             # [8, m]
    np.testing.assert_allclose(np.asarray(got)[:, :m], want, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize('dims,takes', [
    ((128, 64, 8, 128), True), ((128, 64, 1, 128), True),
    ((4, 8, 2, 256), True),
    ((4, 24, 2, 128), True),
    ((4, 520, 2, 128), False),      # 65 tiles down a head's rows
    ((4, 8, 2, 64), False),         # half a lane row
    ((4, 4, 2, 128), False),        # half a tile
    ((4, 8, 3, 128), False)])       # heads that are no whole groups
def test_the_kernel_takes_what_it_tiles_and_nothing_else(dims, takes):
    assert ssd.supported(*dims) is takes


# -- what the chip's compiler is handed ---------------------------------------

def _step_args(slots, heads, p, groups, n):
    f32 = jnp.float32
    return (jax.ShapeDtypeStruct((slots, heads, p, n), f32),
            jax.ShapeDtypeStruct((slots, heads, p), f32),
            jax.ShapeDtypeStruct((slots, groups, n), f32),
            jax.ShapeDtypeStruct((slots, groups, n), f32),
            jax.ShapeDtypeStruct((slots, heads), f32),
            jax.ShapeDtypeStruct((slots, heads), f32),
            jax.ShapeDtypeStruct((heads,), f32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_))


@pytest.mark.parametrize('slots,groups', [(64, 8), (32, 1)],
                         ids=['nemotron-3-super-120b-serve',
                              'granite-4.0-h-small-serve'])
def test_a_lane_of_either_cell_is_one_step_of_the_grid(slots, groups):
    """128 heads of [64, 128] float32 in 8 groups or in 1: the whole lane
    is one block, so the walk is `slots` steps, one a lane, and the state
    goes in as it lies and comes out in its place."""
    assert ssd.heads_per_block(128, 64, 128, groups) == 128
    jaxpr = jax.make_jaxpr(ssd.ssd_step.__wrapped__)(
        *_step_args(slots, 128, 64, groups, 128))
    call, = [e for e in jaxpr.eqns if e.primitive.name == 'pallas_call']
    assert tuple(call.params['grid_mapping'].grid) == (1, slots)
    assert dict(call.params['input_output_aliases']) == {6: 1}
    state_in = call.invars[6].aval
    assert state_in.shape == (slots, 128, 64, 128)
    assert state_in.dtype == jnp.float32


def test_a_lane_too_large_for_the_budget_is_walked_in_divisors(monkeypatch):
    """The same code, a smaller budget: the largest divisor of the heads
    that fits, made of whole groups or lying inside one; one head where
    nothing fits."""
    lane = 128 * 64 * 128 * 4
    monkeypatch.setattr(ssd, '_STATE_VMEM_BYTES', 4 * lane - 1)
    assert ssd.heads_per_block(128, 64, 128, 8) == 64
    assert ssd.heads_per_block(128, 64, 128, 1) == 64
    monkeypatch.setattr(ssd, '_STATE_VMEM_BYTES', lane // 2)
    assert ssd.heads_per_block(128, 64, 128, 8) == 16
    # 12 heads in 3 groups: 6 heads would straddle a group's end
    monkeypatch.setattr(ssd, '_STATE_VMEM_BYTES', 4 * 6 * 64 * 128 * 4)
    assert ssd.heads_per_block(12, 64, 128, 3) == 4
    monkeypatch.setattr(ssd, '_STATE_VMEM_BYTES', 1)
    assert ssd.heads_per_block(128, 64, 128, 8) == 1


def _kernel_body(call):
    """The serialized Mosaic body in the module `call` lowers to for a
    TPU (base64, as the custom call's configuration holds it)."""
    text = jax.jit(call).trace(*_step_args(SLOTS, 16, 8, 2, N)).lower(
        lowering_platforms=('tpu',)).as_text()
    assert text.count('tpu_custom_call') == 1
    return re.search(r'\\22body\\22: \\22(.*?)\\22', text, re.S).group(1)


def test_the_ssd_step_kernels_body_holds_no_callers_lines():
    """ROADMAP S17, for this kernel: its serialized body, part of the
    decode executable's cache key, is the same from two call sites and
    names no file of the checkout, so no line of ops/ssd_ops.py, the
    executor or serving/paged.py."""
    def one(*args):
        return ssd.ssd_step.__wrapped__(*args)

    def other(*args):
        moved = [a for a in args]
        return ssd.ssd_step.__wrapped__(*moved)

    body = _kernel_body(one)
    assert body == _kernel_body(other)
    raw = base64.b64decode(body)
    assert b'ssd_step' in raw
    assert os.path.dirname(os.path.abspath(ssd.__file__)).encode() not in raw
    assert b'.py' not in raw


def test_a_trace_of_the_op_counts_the_path_it_took():
    """ops.ssd_step.kernel under FLAGS_pallas_interpret, .fallback
    without it (off a TPU), once an emission."""
    was = telemetry.enabled()
    telemetry.enable()
    took = {k: telemetry.counter('ops.ssd_step.' + k)
            for k in ('kernel', 'fallback')}
    before = {k: c.value for k, c in took.items()}

    def since():
        return {k: c.value - before[k] for k, c in took.items()}

    raw, state = _step_case(11)
    try:
        _run('ssd_step', *raw, state=state, live=[1, 1, 0])
        assert since() == {'kernel': 0, 'fallback': 1}
        fluid.set_flags({'pallas_interpret': True})
        _run('ssd_step', *raw, state=state, live=[1, 1, 0])
        assert since() == {'kernel': 1, 'fallback': 1}
    finally:
        fluid.set_flags({'pallas_interpret': False})
        if not was:
            telemetry.disable()


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
