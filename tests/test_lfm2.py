"""The LFM2 block with routed experts (models/lfm2.py) at tiny widths
with seeded weights: its whole-sequence program and its paged serving
pair against the plain reference (benchmarks/reference/lfm2.py) for
conv-only, attention-only and mixed stacks; recurrent rows that live BY
THE PAGE (a stream that opens on any resident page boundary or on a
registered tail, copy-on-write, save and restore, and that no snapshot
machinery exists for it); `short_conv`'s forms; heads of 64 packed two a
lane row, through the reference lowering and through the Pallas kernel
in interpret mode."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.models import lfm2
from paddle_tpu.models.transformer import DecodeTranspileError
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import delta_rule_ops
from paddle_tpu.pallas import paged_attention as pa
from paddle_tpu.transpiler.decode_transpiler import extract_decode_spec

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks'))
from reference import lfm2 as ref                 # noqa: E402
from builders import lfm2 as builder              # noqa: E402

MIXED = ['conv', 'conv', 'full_attention', 'conv', 'full_attention']
MODEL = {'vocab_size': 64, 'hidden_size': 32, 'num_attention_heads': 4,
         'num_key_value_heads': 2, 'head_dim': 64, 'conv_L_cache': 3,
         'conv_bias': False, 'intermediate_size': 48, 'num_dense_layers': 2,
         'num_experts': 8, 'num_experts_per_tok': 3,
         'moe_intermediate_size': 24, 'norm_topk_prob': True,
         'use_expert_bias': True, 'routed_scaling_factor': 1,
         'rope_theta': 1000000, 'norm_eps': 1e-5, 'layer_types': MIXED,
         'num_hidden_layers': 5, 'n_positions': 64,
         # wide enough weights that these narrow layers, the routed
         # experts among them, each move the logits by tens of percent
         'initializer_range': 0.3}
STACKS = {'mixed': MIXED, 'conv_only': ['conv'] * 4,
          'attention_only': ['full_attention'] * 3}
SEED = 6000000011
# float32 both sides on the CPU; the program's batched expert products
# and its fused orders differ from the reference's loops by rounding
# only. The bf16-stored control reads more than 30 times this.
TOL = 2e-5


def _dims(stack):
    kinds = STACKS[stack]
    return ref.dims_of(dict(MODEL, layer_types=kinds,
                            num_hidden_layers=len(kinds)))


def _build(tmp, dims):
    cfg = builder.model_config(dims)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, cfg.max_len, 1],
                                   dtype='int64', append_batch_size=False)
        logits = lfm2.language_model_logits(tokens, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        builder.put_seeded_weights(scope, lfm2.spec_from_config(cfg), dims,
                                   SEED)
        toks = np.random.default_rng(0).integers(
            1, dims.vocab, size=(1, cfg.max_len, 1))
        full, = exe.run(main, feed={'tokens': toks}, fetch_list=[logits])
        fluid.io.save_inference_model(str(tmp), ['tokens'], [logits], exe,
                                      main_program=main)
    pred = AnalysisPredictor(AnalysisConfig(str(tmp),
                                            place=fluid.CPUPlace()))
    return pred, toks[0, :, 0], full[0]


@pytest.fixture(scope='module', params=sorted(STACKS))
def stack(request, tmp_path_factory):
    dims = _dims(request.param)
    pred, toks, full = _build(tmp_path_factory.mktemp('lfm2_' + request.param),
                              dims)
    want = np.asarray(ref.logits(ref.seed_key(SEED), dims, toks))
    return pred, toks, full, want, dims


@pytest.fixture(scope='module')
def model(tmp_path_factory):
    dims = _dims('mixed')
    pred, toks, full = _build(tmp_path_factory.mktemp('lfm2_lm'), dims)
    return pred, toks, np.asarray(ref.logits(ref.seed_key(SEED), dims, toks))


def _decoder(pred, **kw):
    # chunks of 6 over pages of 4: a chunk never ends where a page does
    kw = dict(dict(slots=3, page_tokens=4, kv_pages=60, prefill_chunk=6),
              **kw)
    return pred.prepare_decoding(**kw)


def _prefill(dec, slot, prompt):
    info = dec.open_stream(slot, prompt)
    out = None
    while out is None:
        out = dec.prefill_step(slot, return_logits=True)
    return out[1], info['shared_tokens']


def _decode(dec, slot, token, position):
    tokens = np.zeros(dec.slots, np.int64)
    positions = np.zeros(dec.slots, np.int32)
    tokens[slot], positions[slot] = token, position
    return dec.decode_step(tokens, positions, return_logits=True,
                           lanes=[slot])[1][slot]


def _rows(dec, slot, toks, n, steps):
    rows, shared = _prefill(dec, slot, toks[:n])
    return np.stack([rows] + [_decode(dec, slot, toks[j], j)
                              for j in range(n, n + steps)]), shared


# -- (a), (b), (g): the programs against the reference ------------------------

def test_whole_sequence_program_is_the_reference(stack):
    _, _, full, want, _ = stack
    assert ref.rel_l2(full, want) < TOL


def test_chunked_prefill_then_decode_is_the_reference(stack):
    pred, toks, _, want, _ = stack
    dec = _decoder(pred)
    n = 21       # four chunks of 6, the last of 3; five pages and a row
    got, _ = _rows(dec, 1, toks, n, 12)
    assert ref.rel_l2(got, want[n - 1:n + 12]) < TOL


def test_the_bf16_stored_control_fails_the_tolerance(model):
    _, toks, want = model
    control = np.asarray(ref.logits(ref.seed_key(SEED), _dims('mixed'), toks,
                                    'bfloat16'))
    assert ref.rel_l2(control[20:33], want[20:33]) > 30 * TOL


def test_the_selection_bias_changes_which_experts_a_row_takes():
    """At the published router's sizes (32 experts, 4 a token, 2048
    wide): the bias changes the choice of a good share of the rows and
    still leaves the experts' load even, as a trained checkpoint's
    does: 14 rows, a step's lanes in the cell, touch most of the 32."""
    dims = _dims('mixed')._replace(dim=2048, experts=32, top_k=4)
    key = ref.seed_key(SEED)
    p = {role: ref.tensor(ref._role_key(key, 3, role), role, dims)
         for role in ('router', 'bias')}
    u = jax.random.normal(jax.random.PRNGKey(1), (504, dims.dim))
    with_bias, _ = ref.route(u, p, dims)
    without, _ = ref.route(u, dict(p, bias=jnp.zeros_like(p['bias'])), dims)
    changed = np.mean(np.any(np.sort(with_bias) != np.sort(without), axis=-1))
    assert 0.2 < changed < 0.6
    chosen = np.asarray(with_bias)
    touched = np.mean([len(np.unique(chosen[i:i + 14]))
                       for i in range(0, 504, 14)])
    assert touched > 24


def test_all_three_taps_matter():
    taps = np.asarray(ref.tensor(jax.random.PRNGKey(0), 'conv',
                                 _dims('mixed')))
    assert np.all(np.mean(np.abs(taps), axis=1) > 0.3)


# -- the transpiler's reading, and what is not built --------------------------

def test_the_transpiler_reads_the_model_back(model):
    spec = extract_decode_spec(model[0]._program)
    assert isinstance(spec, lfm2.Lfm2DecodeSpec)
    assert spec.kinds == tuple(MIXED)
    assert spec.page_state_layers == [0, 1, 3] and spec.kv_layers == [2, 4]
    assert spec.recurrent_layers == [] and spec.state_names() == []
    assert spec.expert_layers == [2, 3, 4]
    assert vars(spec.cfg) == vars(builder.model_config(_dims('mixed')))
    # two heads of 64 a lane row; a conv layer's pool holds K-1 rows
    assert spec.head_pack == 2
    assert spec.pool_shape(10, 4) == (10, 4, 1, 128)
    assert spec.page_state_shape(10) == (10, 2, 32)
    assert spec.page_state_names() == ['page_state.layer0',
                                       'page_state.layer1',
                                       'page_state.layer3']


def test_no_snapshot_machinery_is_built(model):
    dec = _decoder(model[0])
    pair = dec._pair
    assert not dec.recurrent and pair.snapshot_rows == 0
    assert pair.snapshot_program is None and pair.adopt_program is None
    assert 'prefill_state_slot' not in pair.prefill_feeds
    assert 'decode_live' in pair.decode_feeds
    assert pair.cache_names == pair.spec.pool_names() \
        + pair.spec.page_state_names()
    shapes = dict(pair.cache_shapes())
    assert shapes['page_state.layer1'] == (60, 2, 32)
    assert shapes['kv_pool.layer2.k'] == (60, 4, 1, 128)
    with pytest.raises(ValueError, match='without recurrent state'):
        _decoder(model[0], snapshot_rows=4)


def test_what_cannot_serve_it_refuses_it_by_name(model):
    from paddle_tpu.models.transformer import build_verify_program
    spec = extract_decode_spec(model[0]._program)
    with pytest.raises(DecodeTranspileError, match='conv layers'):
        build_verify_program(spec, 2, 3, 20, 4, 8)
    with pytest.raises(DecodeTranspileError, match='conv layers'):
        _decoder(model[0], mesh='tp=2')


# -- (c): a prefix is pages, conv rows included -------------------------------

def test_a_stream_opens_on_any_page_boundary_and_on_a_tail(model):
    pred, toks, want = model
    telemetry.enable()
    dec = _decoder(pred)
    base = telemetry.snapshot()['counters']
    n = 22                     # five whole pages and a tail of two
    first, shared = _rows(dec, 0, toks, n, 0)
    assert shared == 0
    # a second stream whose prompt leaves the first's inside its third
    # page: it opens on two whole pages, where no prompt ever ended
    other = np.concatenate([toks[:10], (toks[10:30] + 1) % 63 + 1])
    got, shared = _rows(dec, 1, other, 20, 6)
    assert shared == 8
    cold = _decoder(pred)
    alone, none = _rows(cold, 1, other, 20, 6)
    assert none == 0
    np.testing.assert_allclose(got, alone, rtol=2e-5, atol=2e-5)
    # a third that resends the first's prompt and goes on: it opens on
    # the registered tail, in the middle of a page, and forks it
    got, shared = _rows(dec, 2, toks, 30, 8)
    assert shared == n
    assert ref.rel_l2(got, want[29:38]) < TOL
    # the first stream decodes on unharmed: its own append forked the
    # tail page it had registered, conv rows and all
    rows = np.stack([_decode(dec, 0, toks[j], j) for j in range(n, n + 8)])
    assert ref.rel_l2(np.concatenate([first, rows]),
                      want[n - 1:n + 8]) < TOL
    now = telemetry.snapshot()['counters']

    def moved(name):
        return now.get(name, 0) - base.get(name, 0)

    assert moved('serving.prefix.offprompt_tokens') == 8
    assert moved('serving.page_state.streams_adopted') == 2
    # the first prompt's four chunks touched 2 + 2 + 3 + 2 pages; K-1 = 2
    # rows a page in each of three conv layers
    assert moved('serving.page_state.rows_chunk') >= 9 * 2 * 3
    # the cold decoder's six steps count too (one registry)
    assert moved('serving.page_state.rows_step') == (6 + 6 + 8 + 8) * 2 * 3
    assert moved('serving.cow.pages') >= 1
    assert telemetry.snapshot()['gauges']['serving.page_state.bytes'] \
        == dec.pool_stats()['pages_in_use'] * 3 * 2 * 32 * 4


def test_a_prompt_that_ended_on_a_page_boundary_is_not_offprompt(model):
    pred, toks, _ = model
    telemetry.enable()
    dec = _decoder(pred)
    _rows(dec, 0, toks, 16, 0)          # ends where its fourth page does
    before = telemetry.snapshot()['counters'].get(
        'serving.prefix.offprompt_tokens', 0)
    _, shared = _rows(dec, 1, toks, 30, 0)
    assert shared == 16
    assert telemetry.snapshot()['counters'].get(
        'serving.prefix.offprompt_tokens', 0) == before


# -- (d): save and restore ----------------------------------------------------

def test_save_and_restore_carry_the_conv_rows_bit_exact(model):
    pred, toks, want = model
    dec = _decoder(pred)
    n = 19
    _rows(dec, 0, toks, n, 3)
    snap = dec.save_stream(0)
    assert len(snap['data']) == len(dec._pair.cache_names)
    straight = np.stack([_decode(dec, 0, toks[j], j)
                         for j in range(n + 3, n + 9)])
    dec.release(0)
    # other streams scribble over the freed pages in between
    _rows(dec, 1, (toks + 7) % 63 + 1, 40, 2)
    dec.restore_stream(2, snap)
    again = np.stack([_decode(dec, 2, toks[j], j)
                      for j in range(n + 3, n + 9)])
    np.testing.assert_array_equal(again, straight)
    assert ref.rel_l2(again, want[n + 3:n + 9]) < TOL


# -- (e): short_conv's forms --------------------------------------------------

def test_short_conv_with_silu_is_what_it_was():
    rng = np.random.default_rng(3)
    xx = jnp.asarray(rng.normal(size=(2, 11, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    was = jax.nn.silu(sum(xx[..., j:j + 8, :] * w[j] for j in range(4)) + b)
    np.testing.assert_array_equal(
        delta_rule_ops._conv_rows(xx, w, 8, b), was)
    np.testing.assert_array_equal(
        delta_rule_ops._conv_rows(xx, w, 8, b, 'silu'), was)
    plain = sum(xx[..., j:j + 8, :] * w[j] for j in range(4))
    np.testing.assert_array_equal(
        delta_rule_ops._conv_rows(xx, w, 8, None, 'none'), plain)


def test_paged_short_conv_keeps_each_page_at_its_fill_point():
    rng = np.random.default_rng(5)
    k, c, pt, pages = 3, 8, 4, 16
    w = jnp.asarray(rng.normal(size=(k, c)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(40, c)), jnp.float32)
    want = delta_rule_ops._conv_rows(
        jnp.pad(v, ((k - 1, 0), (0, 0))), w, 40, None, 'none')
    table = jnp.asarray([[3, 5, 7, 9, 11, 2, 4, 6, 8, 10]], jnp.int32)
    pool = jnp.full((pages, k - 1, c), jnp.nan)
    out, pos = [], 0
    for n in (5, 6, 3, 6):                      # ragged chunks of 6 rows
        x = jnp.zeros((1, 6, c)).at[0, :n].set(v[pos:pos + n])
        o, pool = delta_rule_ops._paged_conv_chunk(
            pool, x, w, None, 'none', table,
            jnp.arange(pos, pos + 6, dtype=jnp.int32), jnp.int32(n), pt)
        out.append(o[0, :n])
        pos += n
    lanes = jnp.zeros((3, 10), jnp.int32).at[1].set(table[0])
    for i in range(pos, 40):                    # lane 1 of 3 goes on
        x = jnp.zeros((3, 1, c)).at[1, 0].set(v[i])
        o, pool = delta_rule_ops._paged_conv_step(
            pool, x, w, None, 'none', lanes,
            jnp.asarray([0, i, 0], jnp.int32),
            jnp.asarray([False, True, False]), pt)
        out.append(o[1])
    np.testing.assert_allclose(jnp.concatenate(out), want, rtol=1e-6,
                               atol=1e-6)
    for j in range(10):                         # every page: its last rows
        np.testing.assert_array_equal(pool[int(table[0, j])],
                                      v[(j + 1) * pt - 2:(j + 1) * pt])
    # dead lanes and untouched pages were never written
    assert bool(jnp.all(jnp.isnan(pool[0]))) \
        and bool(jnp.all(jnp.isnan(pool[12])))


# -- (f): heads of 64, two a lane row -----------------------------------------

def _plain_attention(q, k, v, n, scale):
    """q [H, dh] against k, v [T, KVH, dh], positions 0..n."""
    rep = q.shape[0] // k.shape[1]
    kk, vv = (np.repeat(a[:n + 1], rep, axis=1) for a in (k, v))
    sc = np.einsum('hd,thd->ht', q, kk) * scale
    p = np.exp(sc - sc.max(-1, keepdims=True))
    return np.einsum('ht,thd->hd', p / p.sum(-1, keepdims=True), vv)


def test_supported_takes_heads_of_64():
    assert pa.supported(16, 64) and pa.supported(16, 128)
    assert not pa.supported(16, 96) and not pa.supported(12, 64)


def test_the_d64_kernel_is_plain_attention():
    rng = np.random.default_rng(7)
    lanes, heads, kvh, dh, pt, width, pages = 5, 32, 8, 64, 8, 6, 40
    lengths = [1, 9, 23, 40, 47]                # ragged, some mid-page
    k = rng.normal(size=(lanes, width * pt, kvh, dh)).astype(np.float32)
    v = rng.normal(size=(lanes, width * pt, kvh, dh)).astype(np.float32)
    q = rng.normal(size=(lanes, heads, dh)).astype(np.float32)
    table = rng.permutation(np.arange(1, pages))[:lanes * width] \
        .reshape(lanes, width).astype(np.int32)
    k_pool = np.zeros((pages, pt, kvh // 2, 2 * dh), np.float32)
    v_pool = np.zeros_like(k_pool)
    for s in range(lanes):
        for j in range(width):
            k_pool[table[s, j]] = k[s, j * pt:(j + 1) * pt].reshape(
                pt, kvh // 2, 2 * dh)
            v_pool[table[s, j]] = v[s, j * pt:(j + 1) * pt].reshape(
                pt, kvh // 2, 2 * dh)
    positions = np.asarray(lengths, np.int32) - 1
    got = pa.paged_attention_d64(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(table), jnp.asarray(positions), sm_scale=dh ** -0.5,
        interpret=True)
    want = np.stack([_plain_attention(q[s], k[s], v[s], positions[s],
                                      dh ** -0.5) for s in range(lanes)])
    assert got.shape == (lanes, heads, dh)
    assert ref.rel_l2(np.asarray(got), want) < 1e-5


def test_the_decode_program_takes_the_kernel_under_interpret(model):
    pred, toks, want = model
    dec = _decoder(pred, page_tokens=8, kv_pages=40, prefill_chunk=12)
    n = 21
    plain, _ = _rows(dec, 1, toks, n, 4)
    fluid.set_flags({'pallas_interpret': True})
    try:
        kern = _decoder(pred, page_tokens=8, kv_pages=40, prefill_chunk=12)
        got, _ = _rows(kern, 1, toks, n, 4)
    finally:
        fluid.set_flags({'pallas_interpret': False})
    assert ref.rel_l2(got, want[n - 1:n + 4]) < TOL
    np.testing.assert_allclose(got, plain, rtol=1e-4, atol=1e-4)


# -- the prefix cache's side, host only ---------------------------------------

def _cache(pages=40, pt=4):
    from paddle_tpu.serving.paging import PagePool, PageTable, PrefixCache
    pool = PagePool(pages, pt)
    cache = PrefixCache(pool)

    def prefilled(prompt):
        table = PageTable(pool, 16)
        shared_pages, shared = cache.match(prompt, limit=len(prompt) - 1)
        if shared:
            table.adopt_shared(shared_pages, shared)
        pair = table.cow_for_append(shared)
        table.ensure(len(prompt))
        if pair is not None:
            pool.unref(pair[0])
        table.length = len(prompt)
        cache.register(prompt, table)
        return table, shared

    return pool, cache, prefilled


@pytest.mark.parametrize('more', [1, 3, 4, 9, 17])
def test_a_tail_connects_where_the_resident_chain_ends(more):
    """A follow-up that resends a prompt of 4 whole pages and 2 tokens
    opens on all 18, however far it goes on: within the tail's page,
    to its end, or pages past it."""
    pool, cache, prefilled = _cache()
    first = list(range(1, 19))
    table, shared = prefilled(first)
    assert shared == 0
    follow, shared = prefilled(first + list(range(50, 50 + more)))
    assert shared == 18
    assert follow.pages[:4] == table.pages[:4]
    assert follow.pages[4] != table.pages[4]        # the tail forked
    assert cache.offprompt_tokens == 0
    pool.check()


def test_offprompt_tokens_count_boundaries_no_prompt_ended_on():
    pool, cache, prefilled = _cache()
    system = list(range(1, 17))                     # four whole pages
    prefilled(system + [40, 41, 42, 43, 44, 45])    # never sent alone
    _, shared = prefilled(system + [60, 61, 62])
    assert shared == 16 and cache.offprompt_tokens == 16
    # the system prompt sent alone opens on its first three pages (its
    # last token is always computed): no prompt ended there either
    _, shared = prefilled(system)
    assert shared == 12 and cache.offprompt_tokens == 28
    # now a prompt HAS ended on the boundary: later hits there are not
    _, shared = prefilled(system + [70, 71])
    assert shared == 16 and cache.offprompt_tokens == 28
    # a hit that runs on into a tail ended on a prompt too
    _, shared = prefilled(system + [60, 61, 62, 63, 64])
    assert shared == 19 and cache.offprompt_tokens == 28
    pool.check()
