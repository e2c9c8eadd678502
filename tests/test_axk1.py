"""The A.X-K1 block (models/axk1.py) at tiny widths with seeded weights:
its whole-sequence program and its paged serving pair against the plain
reference (benchmarks/reference/axk1.py), the absorbed forms against the
equations as they stand, the YaRN table against its closed form, latent
pages through the prefix cache, preemption's snapshots and page
shipping, the 24 shares of the experts against the uncut layer, the
transpiler's reading of a saved model, and the loud refusals where a
page is read as K and V heads."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.models import axk1
from paddle_tpu.models.transformer import build_verify_program
from paddle_tpu.obs import telemetry, trace
from paddle_tpu.ops import latent_attention_ops as lat_ops
from paddle_tpu.transpiler.decode_transpiler import (
    DecodeTranspileError, extract_decode_spec)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks'))
from reference import axk1 as ref                 # noqa: E402
from builders import axk1 as builder              # noqa: E402

MODEL = {'vocab_size': 64, 'hidden_size': 32, 'num_attention_heads': 4,
         'num_hidden_layers': 3, 'first_k_dense_replace': 1,
         'q_lora_rank': 24, 'kv_lora_rank': 16, 'qk_nope_head_dim': 8,
         'qk_rope_head_dim': 8, 'v_head_dim': 8, 'intermediate_size': 48,
         'moe_intermediate_size': 16, 'n_shared_experts': 1,
         'n_routed_experts': 4, 'router_experts': 16, 'expert_offset': 4,
         'num_experts_per_tok': 4, 'n_group': 4, 'topk_group': 2,
         'routed_scaling_factor': 2.5, 'rms_norm_eps': 1e-6,
         'rope_theta': 10000,
         # positions on both sides of the original context of 16
         'rope_scaling': {'type': 'yarn', 'factor': 8,
                          'original_max_position_embeddings': 16,
                          'beta_fast': 32, 'beta_slow': 1, 'mscale': 1,
                          'mscale_all_dim': 1},
         'n_positions': 48,
         # wide enough weights that these narrow layers, the routed
         # experts among them, each move the logits by tens of percent
         'initializer_range': 0.3}
DIMS = ref.dims_of(MODEL)
SEED = 3600000011
# float32 both sides on the CPU; the program's absorbed attention, its
# batched expert products and its rank-based choice differ from the
# reference's loops and sorts by rounding only. The bf16-stored control
# reads more than 30 times this.
TOL = 2e-5


def _build(tmp, dims=DIMS):
    cfg = builder.model_config(dims)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, cfg.max_len, 1],
                                   dtype='int64', append_batch_size=False)
        logits = axk1.language_model_logits(tokens, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        builder.put_seeded_weights(
            scope, axk1.spec_from_config(cfg), dims, SEED)
        toks = np.random.default_rng(0).integers(
            1, dims.vocab, size=(1, cfg.max_len, 1))
        full, = exe.run(main, feed={'tokens': toks}, fetch_list=[logits])
        fluid.io.save_inference_model(str(tmp), ['tokens'], [logits], exe,
                                      main_program=main)
    pred = AnalysisPredictor(AnalysisConfig(str(tmp),
                                            place=fluid.CPUPlace()))
    return pred, toks[0, :, 0], full[0]


@pytest.fixture(scope='module')
def model(tmp_path_factory):
    return _build(tmp_path_factory.mktemp('axk1_lm'))


@pytest.fixture(scope='module')
def reference_logits(model):
    return np.asarray(ref.logits(ref.seed_key(SEED), DIMS, model[1]))


def _decoder(pred, **kw):
    kw = dict(dict(slots=3, page_tokens=4, kv_pages=40,
                   prefill_chunk=16), **kw)
    return pred.prepare_decoding(**kw)


def _prefill_out(dec, slot, prompt):
    """A prompt's last chunk: (token, logits)."""
    dec.open_stream(slot, prompt)
    out = None
    while out is None:
        out = dec.prefill_step(slot, return_logits=True)
    return out


def _prefill(dec, slot, prompt):
    return _prefill_out(dec, slot, prompt)[1]


def _decode(dec, slot, token, position):
    tokens = np.zeros(dec.slots, np.int64)
    positions = np.zeros(dec.slots, np.int32)
    tokens[slot], positions[slot] = token, position
    return dec.decode_step(tokens, positions, return_logits=True,
                           lanes=[slot])[1][slot]


def test_whole_sequence_program_is_the_reference(model, reference_logits):
    assert ref.rel_l2(model[2], reference_logits) < TOL


def test_chunked_prefill_then_decode_is_the_reference(model, reference_logits):
    """Positions 20..28 lie past the original context of 16: the
    stretched frequencies are in every compared row."""
    pred, toks, _ = model
    dec = _decoder(pred)
    n = 21           # two chunks, the second padded; no multiple of 4
    rows = [_prefill(dec, 1, toks[:n])]
    rows += [_decode(dec, 1, toks[j], j) for j in range(n, n + 8)]
    assert ref.rel_l2(np.stack(rows), reference_logits[n - 1:n + 8]) < TOL


def test_the_bf16_stored_control_fails_the_tolerance(model, reference_logits):
    control = np.asarray(ref.logits(ref.seed_key(SEED), DIMS, model[1],
                                    'bfloat16'))
    assert ref.rel_l2(control[20:29], reference_logits[20:29]) > 30 * TOL


def test_a_chunk_lands_between_the_steps_of_running_lanes(model,
                                                         reference_logits):
    """A stream prefilled chunk by chunk while two others decode between
    its chunks; the steps skip the lane that is mid prefill (its rows
    land in the null page and choose no expert)."""
    pred, toks, _ = model
    dec = _decoder(pred, prefill_chunk=8)
    at = {0: 6, 2: 11}
    for slot, n in at.items():
        _prefill(dec, slot, toks[:n])
    tokens, positions = np.zeros(3, np.int64), np.zeros(3, np.int32)

    def step(lanes):
        for slot in lanes:
            tokens[slot], positions[slot] = toks[at[slot]], at[slot]
        lg = dec.decode_step(tokens, positions, return_logits=True)[1]
        for slot in lanes:
            assert ref.rel_l2(lg[slot], reference_logits[at[slot]]) < TOL
            at[slot] += 1

    dec.open_stream(1, toks[:19])                  # three chunks of 8
    out = None
    while out is None:
        out = dec.prefill_step(1, return_logits=True)
        if out is None:
            step([0, 2])
    assert ref.rel_l2(out[1], reference_logits[18]) < TOL
    at[1] = 19
    for _ in range(3):
        step([0, 1, 2])


# -- rotary positions ---------------------------------------------------------

def test_the_yarn_table_is_the_closed_form():
    """The program's table against the formula written out here, at the
    published sizes: 64 rotary dimensions, factor 32 over 4096."""
    inv = lat_ops.yarn_inv_freq(64, 10000.0, 32.0, 4096, 32.0, 1.0)
    j = np.arange(32)
    f = 10000.0 ** (-2.0 * j / 64)

    def bound(beta):
        return 64 * np.log(4096 / (2 * np.pi * beta)) / (2 * np.log(10000.0))
    low, high = np.floor(bound(32.0)), np.ceil(bound(1.0))
    assert (low, high) == (10, 23)
    g = 1 - np.clip((j - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, (f / 32) * (1 - g) + f * g, rtol=1e-12)
    # the fast pairs are left as trained, the slow ones interpolated
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], f[23:] / 32, rtol=1e-12)
    assert lat_ops.yarn_mscale(32.0, 1.0) == pytest.approx(1.3466, abs=1e-4)
    cfg = axk1.AXK1Config(nope_dim=128, rope_dim=64, rope={
        'factor': 32.0, 'mscale': 1.0, 'mscale_all_dim': 1.0})
    assert cfg.sm_scale == pytest.approx(192 ** -0.5 * 1.3466 ** 2, rel=1e-4)
    # and the reference's own copy of the formula agrees
    d = ref.dims_of(dict(MODEL, qk_rope_head_dim=64, rope_scaling=dict(
        MODEL['rope_scaling'], factor=32,
        original_max_position_embeddings=4096)))
    np.testing.assert_allclose(ref.yarn_inv_freq(d), inv, rtol=1e-12)


@pytest.mark.parametrize('per', ['whole', 'row', 'lane'])
def test_rotary_op_turns_each_row_by_its_own_position(per):
    """Through the executor, on both sides of the original context:
    cos and sin of position x frequency on the split halves, the
    columns before `start` untouched."""
    from paddle_tpu.framework import Program, program_guard
    rng = np.random.default_rng(3)
    shape = {'whole': (2, 40, 3, 12), 'row': (1, 6, 3, 12),
             'lane': (6, 1, 3, 12)}[per]
    x = rng.standard_normal(shape).astype('f4')
    pos = {'whole': None, 'row': np.array([3, 15, 16, 17, 30, 47], 'i4'),
           'lane': np.array([0, 47, 16, 15, 2, 31], 'i4')}[per]
    attrs = {'dim': 8, 'base': 10000.0, 'factor': 8.0, 'original_max': 16,
             'beta_fast': 32.0, 'beta_slow': 1.0, 'mscale': 1.0,
             'mscale_all_dim': 1.0, 'per': 'lane' if per == 'lane' else 'row',
             'start': 4}
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        ins = {'X': [fluid.layers.data('x', list(shape), dtype='float32',
                                       append_batch_size=False)]}
        feed = {'x': x}
        if pos is not None:
            ins['Positions'] = [fluid.layers.data(
                'pos', [len(pos)], dtype='int32', append_batch_size=False)]
            feed['pos'] = pos
        out = prog.global_block().create_var(name='out', dtype='float32')
        prog.global_block().append_op(type='rotary_yarn', inputs=ins,
                                      outputs={'Out': [out]}, attrs=attrs)
    got, = fluid.Executor(fluid.CPUPlace()).run(prog, feed=feed,
                                                fetch_list=[out])
    inv = lat_ops.yarn_inv_freq(8, 10000.0, 8.0, 16, 32.0, 1.0)
    where = np.arange(40)[None, :] if pos is None else \
        (pos[:, None] if per == 'lane' else pos[None, :])
    ang = (np.broadcast_to(where, shape[:2])[..., None, None] * inv)
    x1, x2 = x[..., 4:8].astype(np.float64), x[..., 8:].astype(np.float64)
    want = np.concatenate([x[..., :4], x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], axis=-1)
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- the absorbed forms -------------------------------------------------------

def _latent_case(seed, lengths, heads=4, dn=8, dr=8, dv=8, dc=16, pt=4,
                 pages_per_slot=12, row=None):
    """Streams of `lengths` cached tokens in a shuffled pool of latent
    rows [c_KV | k_R | zeros], and what the equations need beside."""
    rng = np.random.default_rng(seed)
    row = row or -(-(dc + dr) // 8) * 8 + 8
    n_pages = 1 + sum(-(-n // pt) for n in lengths) + 2
    pool = np.zeros((n_pages, pt, row), 'f4')
    pool[..., :dc + dr] = rng.standard_normal((n_pages, pt, dc + dr))
    free = list(rng.permutation(np.arange(1, n_pages)))
    table = np.zeros((len(lengths), pages_per_slot), 'i4')
    for s, n in enumerate(lengths):
        for j in range(-(-n // pt)):
            table[s, j] = free.pop()
    w_ukv = (rng.standard_normal((dc, heads * (dn + dv))) / 4).astype('f4')
    return pool, table, w_ukv, rng


def _unabsorbed(q, rows, w_ukv, dn, sm_scale):
    """The equations as they stand, float64: q [T, H, dn + dr] (row t at
    position len(rows) - T + t) against the cached rows [J, dc + dr]."""
    q, rows = q.astype(np.float64), rows.astype(np.float64)
    heads = q.shape[1]
    dc = w_ukv.shape[0]
    w = w_ukv.astype(np.float64).reshape(dc, heads, -1)
    k_c = np.einsum('jc,chn->jhn', rows[:, :dc], w[..., :dn])
    v = np.einsum('jc,chv->jhv', rows[:, :dc], w[..., dn:])
    sc = (np.einsum('thn,jhn->htj', q[..., :dn], k_c)
          + np.einsum('thr,jr->htj', q[..., dn:], rows[:, dc:])) * sm_scale
    first = rows.shape[0] - q.shape[0]
    mask = np.arange(rows.shape[0])[None, :] \
        <= first + np.arange(q.shape[0])[:, None]
    sc = np.where(mask[None], sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum('htj,jhv->thv', p, v).reshape(q.shape[0], -1)


def _cached_rows(pool, table, s, n, width):
    pt = pool.shape[1]
    return np.concatenate([pool[table[s, j]] for j in range(-(-n // pt))]
                          )[:n, :width]


def test_absorbed_decode_is_the_unabsorbed_sum():
    lengths = [1, 9, 30, 17, 4]
    pool, table, w_ukv, rng = _latent_case(1, lengths)
    q = rng.standard_normal((len(lengths), 4, 16)).astype('f4')
    w_uk, w_uv = lat_ops._split_up(jnp.asarray(w_ukv), 4, 8)
    qa = lat_ops.absorb_query(jnp.asarray(q), w_uk, pool.shape[-1])
    u = lat_ops.decode_reference(qa, jnp.asarray(pool), jnp.asarray(table),
                                 jnp.asarray(lengths, jnp.int32) - 1, 0.3, 16)
    got = np.asarray(jnp.einsum('shc,chv->shv', u, w_uv)).reshape(5, -1)
    for s, n in enumerate(lengths):
        want = _unabsorbed(q[s:s + 1], _cached_rows(pool, table, s, n, 24),
                           w_ukv, 8, 0.3)
        np.testing.assert_allclose(got[s:s + 1], want, atol=2e-5)


@pytest.mark.parametrize('start,block', [(0, 1024), (21, 8), (40, 16),
                                         (3, 4), (33, 32)])
def test_a_prefill_chunk_is_the_unabsorbed_sum(start, block):
    """A chunk of 6 rows behind `start` cached tokens, folded in blocks
    of `block` tokens (one block, and several with a last partly past
    the chunk): the absorbed sum against the equations."""
    n = start + 6
    pool, table, w_ukv, rng = _latent_case(2 + start, [n])
    q = rng.standard_normal((6, 4, 16)).astype('f4')
    w_uk, w_uv = lat_ops._split_up(jnp.asarray(w_ukv), 4, 8)
    got = lat_ops.prefill_absorbed(jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table[0]),
             start + jnp.arange(6, dtype=jnp.int32), w_uk, w_uv, 0.3,
             block_tokens=block)
    want = _unabsorbed(q, _cached_rows(pool, table, 0, n, 24), w_ukv, 8, 0.3)
    np.testing.assert_allclose(np.asarray(got).reshape(6, -1), want,
                               atol=2e-5)


# -- the prefill kernel ---------------------------------------------------------

@pytest.fixture
def interpret_kernel():
    fluid.set_flags({'pallas_interpret': True})
    yield
    fluid.set_flags({'pallas_interpret': False})


# (cached tokens, live rows of a chunk of 16, tokens a tile, pages a block
# of 8 tokens each)
KERNEL_CASES = {
    'nothing cached, a whole chunk, blocks of one page': (0, 16, 8, 1),
    'one live row': (21, 1, 4, 2),
    'a whole chunk in one tile, a context inside its second block':
        (40, 16, 16, 4),
    'a length inside a tile': (3, 5, 4, 2),
    'the last live row is a page\'s last token': (19, 5, 8, 1),
    'a context that ends inside the one block': (33, 9, 8, 32),
    'a context of several blocks, the chunk over three of them':
        (60, 16, 8, 2),
    'the tile and the block the shapes give': (50, 11, None, None),
}


@pytest.mark.parametrize('name', list(KERNEL_CASES))
def test_the_prefill_kernel_is_the_fold_and_the_unabsorbed_sum(name):
    """pallas/latent_prefill.paged_latent_prefill in interpret mode
    (scratch never written holds NaNs there) against prefill_absorbed
    and against the equations, on the live rows; zeros on the rest.
    Pages past the last live row's are never copied: the table names a
    page of NaNs there."""
    from paddle_tpu.pallas import latent_prefill as lp
    start, n, tile, bp = KERNEL_CASES[name]
    C, H, dc, row, pt = 16, 4, 128, 256, 8
    pool, table, w_ukv, rng = _latent_case(
        7 + start, [start + n], dc=dc, pt=pt, row=row)
    q = rng.standard_normal((C, H, 16)).astype('f4')
    w_uk, w_uv = lat_ops._split_up(jnp.asarray(w_ukv), H, 8)
    assert lp.prefill_supported(C, H, pt, row, dc)
    want = lat_ops.prefill_absorbed(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table[0]),
        start + jnp.arange(C, dtype=jnp.int32), w_uk, w_uv, 0.3)
    poisoned, far = pool.copy(), table[0].copy()
    live_pages = -(-(start + n) // pt)
    spare = max(set(range(1, len(pool))) - set(far[:live_pages]))
    poisoned[spare] = np.nan
    far[live_pages:] = spare
    u = lp.paged_latent_prefill(
        lat_ops.absorb_query(jnp.asarray(q), w_uk, row) * 0.3,
        jnp.asarray(poisoned), jnp.asarray(far), jnp.int32(start),
        jnp.int32(n), value_dim=dc, tile=tile, block_pages=bp,
        interpret=True)
    got = np.asarray(jnp.einsum('thc,chv->thv', u, w_uv)).reshape(C, -1)
    assert np.isfinite(got).all()
    assert not got[n:].any()
    np.testing.assert_allclose(got[:n], np.asarray(want).reshape(C, -1)[:n],
                               atol=2e-5)
    eq = _unabsorbed(q[:n], _cached_rows(pool, table, 0, start + n, dc + 8),
                     w_ukv, 8, 0.3)
    np.testing.assert_allclose(got[:n], eq, atol=2e-5)


def test_the_prefill_kernel_tiles_what_the_shapes_give():
    from paddle_tpu.pallas import latent_prefill as lp
    assert lp.tile_tokens(256, 64) == 16          # the served chunk
    assert lp.tile_tokens(16, 4) == 16
    assert lp.tile_tokens(512, 64) == 16
    assert lp.tile_tokens(12, 2) == 12            # rows in whole sublanes,
    assert lp.tile_tokens(12, 3) == 0             # or no tile
    assert lp.prefill_supported(256, 64, 16, 640, 512)
    assert not lp.prefill_supported(256, 64, 16, 576, 512)
    assert not lp.prefill_supported(12, 3, 16, 640, 512)
    with pytest.raises(ValueError, match='tiles of 5'):
        lp.paged_latent_prefill(
            jnp.zeros((16, 4, 256)), jnp.zeros((3, 8, 256)),
            jnp.zeros((2,), jnp.int32), jnp.int32(0), jnp.int32(16),
            value_dim=128, tile=5)


def test_the_prefill_kernel_lowers_for_the_chip_at_the_published_row():
    """Cross-lowered for a TPU from here at the served shape: a chunk of
    256 tokens x 64 heads over rows of 640, 1024 pages a slot: one custom
    call, the pool handed over as it lies."""
    import functools
    from paddle_tpu.pallas import latent_prefill as lp
    C, H, row, N, pt, P = 256, 64, 640, 64, 16, 1024
    text = jax.jit(functools.partial(
        lp.paged_latent_prefill, value_dim=512)).trace(
            jax.ShapeDtypeStruct((C, H, row), jnp.float32),
            jax.ShapeDtypeStruct((N, pt, row), jnp.float32),
            jax.ShapeDtypeStruct((P,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)).lower(
                lowering_platforms=('tpu',)).as_text()
    assert text.count('tpu_custom_call') == 1
    assert 'tensor<%dx%dx%dxf32>' % (N, pt, row) in text


def _kernel_body(call):
    """The serialized Mosaic body in the module `call` lowers to for a
    TPU (base64, as the custom call's configuration holds it)."""
    import re
    text = jax.jit(call).trace(
        jax.ShapeDtypeStruct((16, 4, 256), jnp.float32),
        jax.ShapeDtypeStruct((8, 8, 256), jnp.float32),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).lower(
            lowering_platforms=('tpu',)).as_text()
    return re.search(r'\\22body\\22: \\22(.*?)\\22', text, re.S).group(1)


def test_the_prefill_kernels_body_holds_no_callers_lines():
    """ROADMAP S17, for this kernel: its serialized body, part of the
    prefill executable's cache key, is the same from two call sites and
    names no file of the checkout."""
    import base64
    from paddle_tpu.pallas import latent_prefill as lp

    def one(*args):
        return lp.paged_latent_prefill.__wrapped__(*args, value_dim=128)

    def other(*args):
        moved = [a for a in args]
        return lp.paged_latent_prefill.__wrapped__(*moved, value_dim=128)

    body = _kernel_body(one)
    assert body == _kernel_body(other)
    raw = base64.b64decode(body)
    assert b'paged_latent_prefill' in raw
    assert os.path.dirname(os.path.abspath(lp.__file__)).encode() not in raw
    assert b'.py' not in raw


# a latent of one whole lane row and pages of 8 tokens: what the kernels
# tile (MODEL's latent of 16 takes the compositions)
KERNEL_MODEL = dict(MODEL, kv_lora_rank=128)


@pytest.fixture(scope='module')
def kernel_model(tmp_path_factory):
    dims = ref.dims_of(KERNEL_MODEL)
    pred, toks, _ = _build(tmp_path_factory.mktemp('axk1_kernel_lm'), dims)
    return pred, toks, np.asarray(ref.logits(ref.seed_key(SEED), dims, toks))


def test_a_prompt_prefilled_through_the_kernel_is_the_prompt_folded(
        kernel_model, interpret_kernel):
    """21 tokens in chunks of 16 (the second has 5 live rows of 16) and
    a decode step behind them, on the tiny model through
    PagedDecodePredictor: the emitter takes the kernel under the flag
    and the fold without it, and both give the reference's token and
    logits."""
    pred, toks, want = kernel_model
    telemetry.enable()
    took = {k: telemetry.counter('ops.latent_prefill.' + k)
            for k in ('kernel', 'fallback')}
    before = {k: c.value for k, c in took.items()}
    dec = _decoder(pred, page_tokens=8)
    assert dec._pair.pool_shape == (40, 8, 256)
    tok, lg = _prefill_out(dec, 1, toks[:21])
    nxt = _decode(dec, 1, toks[21], 21)
    # three layers, one trace of the prefill program
    assert took['kernel'].value - before['kernel'] == 3
    assert took['fallback'].value == before['fallback']
    fluid.set_flags({'pallas_interpret': False})
    fold = _decoder(pred, page_tokens=8)
    ftok, flg = _prefill_out(fold, 1, toks[:21])
    assert took['fallback'].value - before['fallback'] == 3
    assert int(tok) == int(ftok) == int(np.argmax(want[20]))
    assert ref.rel_l2(lg, flg) < TOL
    assert ref.rel_l2(lg, want[20]) < TOL
    assert ref.rel_l2(nxt, want[21]) < TOL


# -- latent pages through what moves pages -------------------------------------

def test_the_pool_is_one_latent_row_a_token_and_no_v(model):
    pred, toks, _ = model
    dec = _decoder(pred)
    spec = dec._pair.spec
    assert spec.page_kind == 'latent'
    assert spec.pool_names() == ['kv_pool.layer%d.latent' % i
                                 for i in range(3)]
    # 16 + 8 values, stored as a whole number of lanes
    assert dec._pair.pool_shape == (40, 4, 128)
    assert spec.latent_row_bytes() == 4 * 128 * 3
    assert not dec.recurrent and dec._pair.state_names == []
    _prefill(dec, 0, toks[:9])
    page = np.asarray(dec._scope.find_var('kv_pool.layer1.latent'))[
        dec._tables[0].pages[0]]
    assert np.abs(page[:, :24]).min() > 0 and not page[:, 24:].any()


def test_a_stream_opened_on_registered_pages_is_the_stream_opened_cold(
        model, reference_logits):
    """The second stream's prompt is the first's and two tokens more,
    which end inside the page the first's prompt ends in (as far as the
    prefix cache connects a partly filled page): it opens on every page
    the first registered, that last one among them, prefills the rest
    only, and forks that page at its first append; its logits are the reference's, as the cold stream's
    are. The first stream's own next token forks its registered tail
    too."""
    pred, toks, _ = model
    telemetry.enable()
    hits = telemetry.counter('serving.prefix_hits').value
    admitted = telemetry.counter('serving.prompt_tokens_admitted').value
    dec = _decoder(pred)
    first = _prefill(dec, 0, toks[:18])            # 4 pages and a half
    assert ref.rel_l2(first, reference_logits[17]) < TOL
    plan = dec.open_stream(1, toks[:20])
    assert plan['shared_tokens'] == 18 and plan['chunks'] == 1
    shared_tail = dec._tables[1].pages[4]
    assert shared_tail == dec._tables[0].pages[4]
    out = None
    while out is None:
        out = dec.prefill_step(1, return_logits=True)
    assert ref.rel_l2(out[1], reference_logits[19]) < TOL
    assert dec._tables[1].pages[4] != shared_tail          # forked
    assert dec._tables[1].pages[:4] == dec._tables[0].pages[:4]
    got = _decode(dec, 1, toks[20], 20)
    assert ref.rel_l2(got, reference_logits[20]) < TOL
    got = _decode(dec, 0, toks[18], 18)            # forks its own tail
    assert ref.rel_l2(got, reference_logits[18]) < TOL
    assert dec._tables[0].pages[4] != shared_tail
    assert telemetry.counter('serving.prefix_hits').value == hits + 1
    assert telemetry.counter('serving.prompt_tokens_admitted').value \
        == admitted + 18 + 20
    # the same stream opened cold, on a decoder of its own
    cold = _decoder(pred)
    assert ref.rel_l2(_prefill(cold, 2, toks[:20]), out[1]) < TOL


def test_save_and_restore_carry_latent_pages(model, reference_logits):
    pred, toks, _ = model
    dec = _decoder(pred)
    n = 13
    _prefill(dec, 2, toks[:n])
    snap = dec.save_stream(2)
    assert [d.shape for d in snap['data']] == [(4, 4, 128)] * 3
    assert 'state' not in snap
    dec.release(2)
    _prefill(dec, 2, toks[20:40])
    dec.restore_stream(0, snap)                    # into another slot
    got = _decode(dec, 0, toks[n], n)
    assert ref.rel_l2(got, reference_logits[n]) < TOL


def test_page_shipping_carries_latent_pages(model, reference_logits):
    """export_prefix -> an SRV_PAGES frame -> install_prefix on another
    decoder: the frame is sized by the pool's row, and a stream opened
    on the installed pages gives the reference's logits."""
    from paddle_tpu.serving import disagg
    pred, toks, _ = model
    sender, receiver = _decoder(pred), _decoder(pred)
    prompt = toks[:19]
    _prefill(sender, 0, prompt)
    export = sender.export_prefix(prompt)
    assert export['tokens'] == 16
    meta, value = disagg.pack_pages(prompt, export)
    assert value.shape == (3, 4, 4, 128)           # pools, pages, pt, row
    assert receiver.install_prefix(
        prompt, meta['keys'], disagg.unpack_rows(meta, value)) == (4, 0)
    plan = receiver.open_stream(1, prompt)
    assert plan['shared_tokens'] == 16
    out = None
    while out is None:
        out = receiver.prefill_step(1, return_logits=True)
    assert ref.rel_l2(out[1], reference_logits[18]) < TOL


def test_latent_rows_and_bytes_are_counted(model):
    pred, toks, _ = model
    telemetry.enable()
    trace.clear()
    rows = telemetry.counter('serving.latent.rows_read').value
    dec = _decoder(pred)
    _prefill(dec, 0, toks[:9])
    _prefill(dec, 2, toks[:14])
    tokens, positions = np.zeros(3, np.int64), np.zeros(3, np.int32)
    tokens[[0, 2]], positions[[0, 2]] = toks[[9, 14]], [9, 14]
    dec.decode_step(tokens, positions)
    # each live lane's tokens so far and the one it appends, 3 layers
    assert telemetry.counter('serving.latent.rows_read').value - rows \
        == 3 * (10 + 15)
    span = [s for s in trace.spans() if s['name'] == 'paged.decode.tables']
    assert span[-1]['latent_rows'] == 3 * (10 + 15)
    in_use = dec.pool_stats()['pages_in_use']
    assert telemetry.snapshot()['gauges']['serving.latent.cache_bytes'] \
        == in_use * 4 * 4 * 128 * 3


# -- the transpiler and the refusals -------------------------------------------

def test_the_transpiler_reads_the_model_back(model):
    spec = extract_decode_spec(model[0]._program)
    want = builder.model_config(DIMS)
    assert spec.kinds == ('latent_attention',) * 3
    assert spec.recurrent_layers == [] and spec.kv_layers == [0, 1, 2]
    assert spec.expert_layers == [1, 2]
    got = vars(spec.cfg)
    assert got == vars(want)
    assert spec.pool_shape(10, 4) == (10, 4, 128)
    assert spec.cfg.sm_scale == pytest.approx(DIMS.sm_scale)


def test_the_expert_layers_count_on_the_device(model):
    pred, toks, _ = model
    dec = _decoder(pred)
    _prefill(dec, 1, toks[:21])                    # chunks of 16 and 5 rows
    for j in range(21, 24):
        _decode(dec, 1, toks[j], j)
    jax.block_until_ready(jax.live_arrays())
    c = dec.moe_counters()
    assert c['layer_calls'] == 5 * 2 and c['decode.layer_calls'] == 3 * 2
    assert c['pairs_dropped'] == 0
    assert 0 < c['pairs'] <= 24 * 4 * 2


@pytest.mark.parametrize('what', ['verify', 'speculative', 'mesh'])
def test_what_cannot_serve_the_block_says_so_by_name(model, what):
    pred, toks, _ = model
    if what == 'verify':
        with pytest.raises(DecodeTranspileError, match='latent_attention'):
            build_verify_program(extract_decode_spec(pred._program),
                                 2, 3, 10, 4, 12)
    elif what == 'speculative':
        with pytest.raises(DecodeTranspileError,
                           match='speculative decoding.*latent_attention'):
            pred.prepare_decoding(slots=2, page_tokens=4, kv_pages=40,
                                  speculative=True, spec_k=2,
                                  draft_layers=1)
    else:
        with pytest.raises(DecodeTranspileError,
                           match='mesh serving.*latent_attention'):
            pred.prepare_decoding(slots=2, page_tokens=4, kv_pages=40,
                                  mesh='tp=2')


# -- the shares ----------------------------------------------------------------

def test_the_24_shares_add_up_to_the_uncut_layer():
    """192 experts in 8 groups, 4 groups kept, 8 a token, as published;
    24 chips hold 8 experts each. The routed parts the 24 shares give
    through the program's op, plus what every chip computes alike (the
    shared expert) counted once, are the uncut layer of the reference."""
    from paddle_tpu.ops import moe_ops
    model = dict(MODEL, router_experts=192, n_routed_experts=8,
                 expert_offset=0, n_group=8, topk_group=4,
                 num_experts_per_tok=8)
    share0 = ref.dims_of(model)
    whole = share0._replace(held=192, offset=0)
    key = ref.seed_key(SEED)
    p = ref.layer_weights(key, 1, whole, ref.FFN_ROLES['experts'][:5])
    u = jax.random.normal(jax.random.PRNGKey(5), (29, whole.dim))
    want = ref.routed_part(
        u, p, whole, 'float32',
        lambda e: ref.expert_weights(key, 1, e, whole)) \
        + ref.shared_part(u, p, whole, 'float32')
    w_all = moe_ops.served_weights(u, p['router'], p['bias'], 8, 2.5, 8, 4)
    assert (np.asarray(w_all != 0).sum(-1) == 8).all()
    total = np.zeros((29, whole.dim), 'f4')
    for offset in range(0, 192, 8):
        share = share0._replace(offset=offset)
        w1, w3, w2 = (ref.layer_tensor(key, 1, r, share)
                      for r in ('w1', 'w3', 'w2'))
        part = moe_ops.held_gated_experts(
            u, w_all[:, offset:offset + 8], w1, w3, w2)
        if offset in (0, 88, 184):      # the reference given the same share
            mine = ref.routed_part(
                u, p, share, 'float32',
                lambda e: ref.expert_weights(key, 1, e, share))
            assert ref.rel_l2(np.asarray(part), np.asarray(mine)) < TOL
        total += np.asarray(part)
    got = total + np.asarray(ref.shared_part(u, p, whole, 'float32'))
    assert ref.rel_l2(got, np.asarray(want)) < TOL
