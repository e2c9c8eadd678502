"""The kernel a step's rows take through the held experts
(pallas/moe_experts.py) against the batched product over the whole
stack (ops/moe_ops.held_experts / held_gated_experts), in interpret mode
on the CPU: the same sum whatever was touched, nothing read past the
touched, and which rows the op `moe_experts` sends where. A step's rows
are a decode step's slots or a block step's slots x 4 (128 here)."""
import base64
import math
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import moe_ops
from paddle_tpu.pallas import moe_experts as me

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'tools'))
import moe_experts_arms  # noqa: E402

# (matrices an expert, act): Nemotron's relu^2 of one product, SwiGLU, ReGLU
FORMS = {'relu2': (2, 'relu2'), 'silu': (3, 'silu'), 'relu': (3, 'relu')}
ROWS, L, F, HELD = 16, 128, 256, 6
BLOCK_ROWS = 128        # a block step's rows: 32 slots x 4
TOUCHED = {'none': [], 'one': [3], 'some': [0, 2, 5],
           'all': list(range(HELD))}


@pytest.fixture
def interpret_kernel():
    fluid.set_flags({'pallas_interpret': True})
    yield
    fluid.set_flags({'pallas_interpret': False})


def _stack(rng, matrices, held=HELD, lat=L, f=F):
    w1 = rng.normal(size=(held, lat, f)) / math.sqrt(lat)
    w3 = rng.normal(size=(held, lat, f)) / math.sqrt(lat)
    w2 = rng.normal(size=(held, f, lat)) / math.sqrt(f)
    return (jnp.asarray(w1, jnp.float32),
            jnp.asarray(w3, jnp.float32) if matrices == 3 else None,
            jnp.asarray(w2, jnp.float32))


def _choices(rng, rows, held, touched):
    w = np.zeros((rows, held), 'f4')
    for e in touched:
        mine = rng.choice(rows, 3, replace=False)
        w[mine, e] = rng.uniform(0.1, 1.0, 3)
    return jnp.asarray(w)


def _product(lat, w, w1, w3, w2, act):
    if w3 is None:
        return moe_ops.held_experts(lat, w, w1, w2)
    return moe_ops.held_gated_experts(lat, w, w1, w3, w2, act)


@pytest.mark.parametrize('rows', [ROWS, BLOCK_ROWS])
@pytest.mark.parametrize('touched', list(TOUCHED))
@pytest.mark.parametrize('form', list(FORMS))
def test_the_kernel_is_the_batched_product(form, touched, rows):
    """Both forms and the three activations, with 0, 1, some and all of
    the held experts touched, one tile an expert and two, at a decode
    step's rows and at a block step's."""
    matrices, act = FORMS[form]
    rng = np.random.default_rng(len(touched) + matrices)
    lat = jnp.asarray(rng.normal(size=(rows, L)), jnp.float32)
    w1, w3, w2 = _stack(rng, matrices)
    w = _choices(rng, rows, HELD, TOUCHED[touched])
    ids, n = me.touched_ids(jnp.any(w != 0, axis=0))
    assert int(n[0]) == len(TOUCHED[touched])
    want = np.asarray(_product(lat, w, w1, w3, w2, act))
    for tile in (None, 128):
        got = me.moe_experts(lat, w, ids, n, w1, w3, w2, act=act, tile=tile,
                             interpret=True)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)
    if not TOUCHED[touched]:
        assert not np.asarray(got).any()


@pytest.mark.parametrize('touched,ids,n', [
    ([0, 0, 0, 0, 0], [0, 0, 0, 0, 0], 0),
    ([0, 0, 1, 0, 0], [2, 2, 2, 2, 2], 1),
    ([1, 0, 1, 0, 1], [0, 2, 4, 4, 4], 3),
    ([0, 1, 1, 1, 0], [1, 2, 3, 3, 3], 3),
    ([1, 1, 1, 1, 1], [0, 1, 2, 3, 4], 5),
])
def test_the_touched_come_first_in_rising_order(touched, ids, n):
    got_ids, got_n = me.touched_ids(jnp.asarray(touched, bool))
    assert np.asarray(got_ids).tolist() == ids
    assert np.asarray(got_n).tolist() == [n]
    assert got_ids.dtype == jnp.int32 and got_n.dtype == jnp.int32


@pytest.mark.parametrize('rows', [ROWS, BLOCK_ROWS])
def test_an_untouched_expert_is_never_read(interpret_kernel, rows):
    """NaNs in the experts no row chose do not reach the result: their
    tiles are skipped, not multiplied by zero."""
    rng = np.random.default_rng(5)
    lat = jnp.asarray(rng.normal(size=(rows, L)), jnp.float32)
    w1, w3, w2 = _stack(rng, 3)
    w = _choices(rng, rows, HELD, [1, 4])
    ids, n = me.touched_ids(jnp.any(w != 0, axis=0))
    want = np.asarray(moe_ops.held_gated_experts(lat, w, w1, w3, w2))
    dead = jnp.asarray([0, 2, 3, 5])
    w1, w3, w2 = (m.at[dead].set(jnp.nan) for m in (w1, w3, w2))
    got = me.moe_experts(lat, w, ids, n, w1, w3, w2, tile=128,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('L_,F_,matrices,tile', [
    (1024, 2688, 2, 2688),      # Nemotron 3 Super: an expert is one step
    (7168, 2048, 3, 256),       # A.X-K1
    (4096, 1280, 3, 256),       # Solar Open2
    (4096, 768, 3, 384),        # Granite 4.0-H Small
    (2560, 768, 3, 768),        # SmallThinker
    (128, 200, 3, 0),           # no whole number of lane rows
    (65536, 128, 3, 128),       # nothing fits: one lane row
])
def test_tiles_follow_from_the_widths(L_, F_, matrices, tile):
    assert me.tile_width(L_, F_, matrices) == tile


def test_only_a_steps_rows_are_supported():
    assert me.step_supported(48, 4096, 1280, 10, 3)
    assert me.step_supported(64, 1024, 2688, 64, 2)
    assert me.step_supported(me.STEP_ROWS, 1024, 2688, 64, 2)
    assert not me.step_supported(256, 4096, 1280, 10, 3)    # a chunk's rows
    assert not me.step_supported(me.STEP_ROWS + 8, 1024, 2688, 64, 2)
    assert not me.step_supported(12, 4096, 1280, 10, 3)     # no whole sublanes
    assert not me.step_supported(48, 16, 20, 6, 3)          # a tiny model


@pytest.mark.parametrize('cell', sorted(moe_experts_arms.CELLS))
def test_every_cells_step_takes_the_kernel(cell):
    """The five decode steps (32-64 rows) and SDAR's block step (32 x 4)
    at float32, what the cells hold: a "no" here is a cell back on the
    product."""
    rows, L_, F_, held, matrices, _, _ = moe_experts_arms.CELLS[cell]
    assert me.step_supported(rows, L_, F_, held, matrices)


@pytest.mark.parametrize('rows,L_,F_,held,matrices,takes', [
    (128, 2048, 768, 16, 3, True),      # SDAR's block step: 32 slots x 4
    (136, 2048, 768, 16, 3, False),     # past a step's rows
    (128, 7168, 2048, 8, 3, True),      # A.X-K1's widths would still fit
    (128, 1024, 2688, 64, 2, True),
    (64, 16384, 128, 8, 3, True),       # one lane row a tile, and it fits
    (128, 16384, 128, 8, 3, False),     # the rows' blocks no longer do
    (128, 32768, 128, 8, 2, False),
])
def test_a_steps_rows_are_bounded_by_the_kernels_memory(rows, L_, F_, held,
                                                        matrices, takes):
    """Up to `STEP_ROWS` the bound is what the walk keeps in VMEM at the
    tile the widths give, against the limit the call sets: no bare
    constant, and the same count `moe_experts` hands the compiler."""
    assert me.step_supported(rows, L_, F_, held, matrices) is takes
    need = me._vmem_bytes(rows, L_, me.tile_width(L_, F_, matrices), held,
                          matrices, 4)
    if rows <= me.STEP_ROWS:
        assert (need + me._VMEM_ROOM <= me._VMEM_CAP) is takes


# -- the op: which rows go where, and what it counts --------------------------

def _op_case(rows, experts=16, held=HELD, offset=4, k=5, seed=0, d=24,
             matrices=3):
    rng = np.random.default_rng(seed)
    case = dict(
        x=rng.normal(size=(rows, d)).astype('f4'),
        lat=rng.normal(size=(rows, L)).astype('f4'),
        router=(rng.normal(size=(d, experts)) / math.sqrt(d)).astype('f4'),
        bias=np.zeros(experts, 'f4'),
        w1=(rng.normal(size=(held, L, F)) / math.sqrt(L)).astype('f4'),
        w2=(rng.normal(size=(held, F, L)) / math.sqrt(F)).astype('f4'),
        k=k, offset=offset)
    if matrices == 3:
        case['w3'] = (rng.normal(size=(held, L, F)) / math.sqrt(L)) \
            .astype('f4')
    return case


def _run_op(case, live=None, act='silu'):
    """moe_experts through the executor -> (out [rows, L], stats [4])."""
    prog, startup = Program(), Program()
    names = [n for n in ('x', 'lat', 'router', 'bias', 'w1', 'w3', 'w2')
             if n in case]
    feed = {n: case[n] for n in names}
    with program_guard(prog, startup):
        v = {n: fluid.layers.data(n, list(a.shape), dtype='float32',
                                  append_batch_size=False)
             for n, a in feed.items()}
        ins = {'X': [v['x']], 'Lat': [v['lat']], 'RouterW': [v['router']],
               'Bias': [v['bias']], 'W1': [v['w1']], 'W2': [v['w2']]}
        if 'w3' in v:
            ins['W3'] = [v['w3']]
        if live is not None:
            feed['live'] = np.asarray(live, 'i4')
            ins['Live'] = [fluid.layers.data(
                'live', list(feed['live'].shape), dtype='int32',
                append_batch_size=False)]
        block = prog.global_block()
        out = block.create_var(name='out', dtype='float32')
        stats = block.create_var(name='stats', dtype='int32')
        block.append_op(type='moe_experts', inputs=ins,
                        outputs={'Out': [out], 'Stats': [stats]},
                        attrs={'top_k': case['k'], 'scale': 2.5, 'act': act,
                               'expert_offset': case['offset']})
    return fluid.Executor(fluid.CPUPlace()).run(prog, feed=feed,
                                                fetch_list=[out, stats])


@pytest.fixture
def took():
    """{'kernel': n, 'fallback': n} emissions since the fixture began."""
    was = telemetry.enabled()
    telemetry.enable()
    counters = {k: telemetry.counter('ops.moe_experts.' + k)
                for k in ('kernel', 'fallback')}
    before = {k: c.value for k, c in counters.items()}
    yield lambda: {k: c.value - before[k] for k, c in counters.items()}
    if not was:
        telemetry.disable()


@pytest.mark.parametrize('form', list(FORMS))
def test_the_op_counts_and_sums_alike_on_both_paths(form, took,
                                                    interpret_kernel,
                                                    monkeypatch):
    """48 rows of which some are dead by Live, experts 4..9 of 16 held:
    the kernel's result is the product's, Stats are equal, and
    Stats[1], the experts touched, is the n the kernel was handed."""
    matrices, act = FORMS[form]
    case = _op_case(48, matrices=matrices, seed=matrices)
    live = (np.arange(48) % 5 != 0).astype('i4')
    handed = []
    kernel = me.moe_experts

    def spy(lat, w, ids, n, *rest, **kw):
        jax.debug.callback(lambda v: handed.append(int(v[0])), n)
        return kernel(lat, w, ids, n, *rest, **kw)

    monkeypatch.setattr(me, 'moe_experts', spy)
    got, got_stats = _run_op(case, live=live, act=act)
    assert took() == {'kernel': 1, 'fallback': 0}
    fluid.set_flags({'pallas_interpret': False})
    want, want_stats = _run_op(case, live=live, act=act)
    assert took() == {'kernel': 1, 'fallback': 1}
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert got_stats.tolist() == want_stats.tolist()
    assert 0 < got_stats[1] <= HELD and handed == [got_stats[1]]
    assert not got[live == 0].any()


def test_a_chunks_rows_take_the_product_and_a_steps_the_kernel(
        took, interpret_kernel):
    """The op's static row count alone decides: 256 rows (a prefill
    chunk) take the batched product under the flag too, 48 the kernel;
    and without the flag, off a TPU, every row takes the product."""
    _run_op(_op_case(256))
    assert took() == {'kernel': 0, 'fallback': 1}
    _run_op(_op_case(48))
    assert took() == {'kernel': 1, 'fallback': 1}
    fluid.set_flags({'pallas_interpret': False})
    _run_op(_op_case(48))
    assert took() == {'kernel': 1, 'fallback': 2}


@pytest.mark.parametrize('form', ['relu2', 'silu'])
def test_a_block_steps_rows_take_the_kernel_and_sum_alike(form, took,
                                                          interpret_kernel):
    """[lanes, 4] rows with Live [lanes], a lane's flag for its four
    rows: 32 lanes (128 rows) take the kernel and 64 lanes (256, a
    chunk's count) the product; at 128 the kernel's sum and Stats are
    the product's, and a dead lane's rows are zeros."""
    matrices, act = FORMS[form]
    case = _op_case(BLOCK_ROWS, matrices=matrices, seed=7 + matrices)
    for name in ('x', 'lat'):
        case[name] = case[name].reshape(32, 4, -1)
    live = (np.arange(32) % 3 != 0).astype('i4')
    got, got_stats = _run_op(case, live=live, act=act)
    assert took() == {'kernel': 1, 'fallback': 0}
    wide = _op_case(256, matrices=matrices)
    for name in ('x', 'lat'):
        wide[name] = wide[name].reshape(64, 4, -1)
    _run_op(wide, live=np.ones(64, 'i4'), act=act)
    assert took() == {'kernel': 1, 'fallback': 1}
    fluid.set_flags({'pallas_interpret': False})
    want, want_stats = _run_op(case, live=live, act=act)
    assert took() == {'kernel': 1, 'fallback': 2}
    assert got.shape == (32, 4, L)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert got_stats.tolist() == want_stats.tolist()
    assert 0 < got_stats[1] <= HELD
    assert not got[live == 0].any() and got[live == 1].any()


def test_no_live_row_gives_zeros(took, interpret_kernel):
    out, stats = _run_op(_op_case(32), live=np.zeros(32, 'i4'))
    assert took() == {'kernel': 1, 'fallback': 0}
    assert out.shape == (32, L) and not out.any()
    assert stats.tolist() == [0, 0, 0, 1]


def test_a_share_no_row_chose_gives_zeros(took, interpret_kernel):
    """Experts 14 and 15 of 16 with every row's two choices forced onto
    expert 0: the share is held, nothing is touched, nothing is read."""
    case = _op_case(16, held=2, offset=14, k=1)
    case['bias'][0] = 100.0
    out, stats = _run_op(case)
    assert took() == {'kernel': 1, 'fallback': 0}
    assert not out.any() and stats.tolist() == [0, 0, 0, 1]


# -- what the chip's compiler is handed ---------------------------------------

def _lowered(call, rows=16, held=HELD, matrices=3):
    stack = jax.ShapeDtypeStruct((held, L, F), jnp.float32)
    return jax.jit(call).trace(
        jax.ShapeDtypeStruct((rows, L), jnp.float32),
        jax.ShapeDtypeStruct((rows, held), jnp.float32),
        jax.ShapeDtypeStruct((held,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
        stack, stack if matrices == 3 else None,
        jax.ShapeDtypeStruct((held, F, L), jnp.float32)).lower(
            lowering_platforms=('tpu',)).as_text()


def _kernel_body(call):
    """The serialized Mosaic body in the module `call` lowers to for a
    TPU (base64, as the custom call's configuration holds it)."""
    return re.search(r'\\22body\\22: \\22(.*?)\\22', _lowered(call),
                     re.S).group(1)


def test_the_kernel_lowers_for_the_chip_as_one_call_on_the_stack():
    """Cross-lowered for a TPU from here: one custom call, the stack
    handed over as it lies (no slice, no gather, no copy of it)."""
    text = _lowered(me.moe_experts)
    assert text.count('tpu_custom_call') == 1
    assert 'tensor<%dx%dx%dxf32>' % (HELD, L, F) in text
    assert not re.search(r'stablehlo\.(gather|dynamic_slice|slice)', text)


def test_the_kernels_body_holds_no_callers_lines():
    """ROADMAP S17, for this kernel: its serialized body, part of the
    decode executable's cache key, is the same from two call sites and
    names no file of the checkout, so no line of ops/moe_ops.py, the
    executor or serving/paged.py."""
    def one(*args):
        return me.moe_experts.__wrapped__(*args, act='silu')

    def other(*args):
        moved = [a for a in args]
        return me.moe_experts.__wrapped__(*moved, act='silu')

    # the jitted helpers' jaxprs are cached with their first caller's
    # lines: trace them from here first, as a program's other ops would
    jax.nn.silu(jnp.ones((16, 256)))
    jnp.where(jnp.ones((16, 6)) > 0, 1.0, 0.0).sum(axis=1, keepdims=True)
    body = _kernel_body(one)
    assert body == _kernel_body(other)
    raw = base64.b64decode(body)
    assert b'moe_experts' in raw
    assert os.path.dirname(os.path.abspath(me.__file__)).encode() not in raw
    assert b'.py' not in raw
