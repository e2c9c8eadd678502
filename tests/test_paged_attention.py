"""The decode step's attention reads the live pages, not the window.

What must hold:

- the Pallas kernel (pallas/paged_attention.py), run in interpreter
  mode under FLAGS_pallas_interpret through the `paged_attention` op,
  agrees with the op's reference lowering to 2e-5 of the largest
  output (float32 throughout; the same pools stored in bfloat16 miss
  that by two orders), for every way a page table can look;
- off the TPU and without the flag the op IS the composition the decode
  program used to hold (kv_page_gather, matmul, paged_decode_mask,
  softmax, matmul), bit for bit;
- the decode program holds one paged_attention a layer and gathers
  nothing; the prefill and verify programs still gather;
- `paged.decode.tables` says how many pages the step's attention reads
  of how many a gathered window holds, and the registry counts both.
"""
import collections
import functools

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.models.transformer import TransformerConfig
from paddle_tpu.obs import telemetry, trace

from test_paged import _save_lm

DH = 128
# |kernel - reference| over the largest |reference| of a case. Both
# sides are float32 sums of the same products in another order: seen
# 2e-7..3e-6 here. bfloat16 pages give 2e-3..4e-3 (test below).
TOL = 2e-5


def _run_op(q, kpool, vpool, table, positions, op_type='paged_attention',
            window=0):
    """One decode-attention program through the real Executor:
    the op (with a `window`: the sliding layers', under its own type),
    or (op_type='composition') the ops it replaced."""
    prog, startup = Program(), Program()
    L = fluid.layers
    with program_guard(prog, startup):
        block = prog.global_block()
        feeds = {'q': q, 'kpool': kpool, 'vpool': vpool, 'table': table,
                 'positions': positions}
        v = {n: L.data(n, list(a.shape), append_batch_size=False,
                       dtype=str(a.dtype)) for n, a in feeds.items()}
        alpha = float(1.0 / np.sqrt(q.shape[-1]))
        if op_type == 'paged_attention':
            out = block.create_var(name='ctx', dtype='float32')
            block.append_op(
                type='paged_window_attention' if window
                else 'paged_attention',
                inputs={'Q': [v['q']], 'KPool': [v['kpool']],
                        'VPool': [v['vpool']], 'Table': [v['table']],
                        'Positions': [v['positions']]},
                outputs={'Out': [out]},
                attrs=dict({'sm_scale': alpha, 'head_axis': ''},
                           **({'window': window} if window else {})))
        else:
            def gathered(pool):
                g = block.create_var(name='g.' + pool.name, dtype='float32')
                block.append_op(type='kv_page_gather',
                                inputs={'Pool': [pool],
                                        'Table': [v['table']]},
                                outputs={'Out': [g]})
                return L.transpose(g, perm=[0, 2, 1, 3])
            qt = L.transpose(v['q'], perm=[0, 2, 1, 3])
            scores = L.matmul(qt, gathered(v['kpool']), transpose_y=True,
                              alpha=alpha)
            masked = block.create_var(name='masked', dtype='float32')
            block.append_op(type='paged_decode_mask',
                            inputs={'X': [scores],
                                    'Positions': [v['positions']]},
                            outputs={'Out': [masked]})
            ctx = L.matmul(L.softmax(masked), gathered(v['vpool']))
            out = L.transpose(ctx, perm=[0, 2, 1, 3])
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        got, = exe.run(prog, feed=feeds, fetch_list=[out])
    return np.asarray(got)


def _pools(rng, n_pages, pt, heads, dh=DH):
    shape = (n_pages, pt, heads, dh)
    return (rng.standard_normal(shape).astype('f4'),
            rng.standard_normal(shape).astype('f4'))


def _tables(rng, lengths, pt, pages_per_slot, n_pages, share=()):
    """Shuffled, non-contiguous page tables for streams of `lengths`
    tokens (0 = an idle lane: zero row, position 0). `share` pairs
    (child, parent): the child's table starts with the parent's pages,
    as a copy-on-write fork's does."""
    free = list(rng.permutation(np.arange(1, n_pages)))
    table = np.zeros((len(lengths), pages_per_slot), 'int32')
    for s, n in enumerate(lengths):
        for j in range(-(-n // pt)):
            table[s, j] = free.pop()
    for child, parent in share:
        n = min(lengths[child], lengths[parent]) // pt
        table[child, :n] = table[parent, :n]
    positions = np.array([max(n - 1, 0) for n in lengths], 'int32')
    return table, positions


PT = 8
CASES = {
    # name: (heads, pages_per_slot, lengths, shared (child, parent))
    'one_token': (16, 4, [1], ()),
    'one_page': (16, 4, [PT], ()),
    'page_plus_one': (16, 4, [PT + 1], ()),
    'full_window': (16, 4, [4 * PT], ()),
    'one_head': (1, 4, [1, PT + 3, 4 * PT], ()),
    # 11 pages a slot are one block of 8 and a tail of 3
    'window_not_a_multiple_of_the_block': (16, 11, [11 * PT, 9 * PT - 2, 8 * PT,
                                                    8 * PT + 1], ()),
    'sixteen_lanes_mixed_and_idle': (
        16, 11, [0, 5, 88, 0, 17, 64, 65, 1, 0, 33, 80, 8, 9, 0, 71, 40],
        ()),
    'pages_shared_between_lanes': (16, 11, [50, 70, 19, 50, 0, 44],
                                   ((1, 0), (3, 0), (5, 2))),
}


@pytest.fixture
def interpret_kernel():
    fluid.set_flags({'pallas_interpret': True})
    yield
    fluid.set_flags({'pallas_interpret': False})


def _case(name):
    heads, pages_per_slot, lengths, share = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    n_pages = 1 + sum(-(-n // PT) for n in lengths) + 3
    kpool, vpool = _pools(rng, n_pages, PT, heads)
    table, positions = _tables(rng, lengths, PT, pages_per_slot, n_pages,
                               share)
    q = rng.standard_normal((len(lengths), 1, heads, DH)).astype('f4')
    return q, kpool, vpool, table, positions


@pytest.mark.parametrize('name', sorted(CASES))
def test_kernel_matches_reference_lowering(name, interpret_kernel):
    args = _case(name)
    got = _run_op(*args)
    fluid.set_flags({'pallas_interpret': False})
    want = _run_op(*args)
    assert got.shape == want.shape == args[0].shape
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


def test_bfloat16_pages_miss_the_tolerance(interpret_kernel):
    """The tolerance is a float32 one: the kernel over the same pools
    rounded to bfloat16 is far outside it."""
    import jax.numpy as jnp
    q, kpool, vpool, table, positions = _case('sixteen_lanes_mixed_and_idle')
    rounded = [np.asarray(jnp.asarray(p).astype(jnp.bfloat16)
                          .astype(jnp.float32)) for p in (kpool, vpool)]
    got = _run_op(q, rounded[0], rounded[1], table, positions)
    fluid.set_flags({'pallas_interpret': False})
    want = _run_op(q, kpool, vpool, table, positions)
    assert np.abs(got - want).max() > 50 * TOL * np.abs(want).max()


@pytest.mark.parametrize('heads,dh,pt', [(2, 16, 4), (16, 128, 8)])
def test_reference_lowering_is_the_old_composition_bit_for_bit(heads, dh,
                                                               pt):
    """Off the TPU and without the flag (and, at dh=16, for pages the
    kernel does not tile) the op is kv_page_gather + matmul +
    paged_decode_mask + softmax + matmul as the decode program held
    them."""
    rng = np.random.default_rng(3)
    lengths = [0, 1, pt, pt + 1, 5 * pt, 3 * pt - 1]
    n_pages = 40
    kpool, vpool = _pools(rng, n_pages, pt, heads, dh)
    table, positions = _tables(rng, lengths, pt, 5, n_pages)
    q = rng.standard_normal((len(lengths), 1, heads, dh)).astype('f4')
    got = _run_op(q, kpool, vpool, table, positions)
    old = _run_op(q, kpool, vpool, table, positions, 'composition')
    assert np.array_equal(got, old)


# ---------------------------------------------------------------------------
# K/V heads fewer than query heads
# ---------------------------------------------------------------------------

def _dense_attention(q, kpool, vpool, table, positions, window=0):
    """Each lane's attention over its own tokens (the last `window` of
    them where one is given), a query head against K/V head
    h // (H / KVH), in float64 numpy: no pages, no kernel."""
    pt, rep = kpool.shape[1], q.shape[2] // kpool.shape[2]
    out = np.zeros(q.shape, np.float64)
    for s, pos in enumerate(positions):
        n = int(pos) + 1
        rows = [(table[s, j // pt], j % pt)
                for j in range(max(0, n - window) if window else 0, n)]
        k = np.stack([kpool[p, o] for p, o in rows]).astype(np.float64)
        v = np.stack([vpool[p, o] for p, o in rows]).astype(np.float64)
        for h in range(q.shape[2]):
            sc = k[:, h // rep] @ q[s, 0, h].astype(np.float64) \
                / np.sqrt(q.shape[-1])
            w = np.exp(sc - sc.max())
            out[s, 0, h] = (w / w.sum()) @ v[:, h // rep]
    return out


@pytest.mark.parametrize('heads,kv_heads,lengths', [
    (32, 2, [1, PT, PT + 3, 11 * PT, 0, 70]),   # 16 query rows a K/V head
    (8, 4, [5, 88, 0, 17]),
    (4, 1, [9 * PT - 2, 1]),                    # every query on one head
])
def test_fewer_kv_heads_kernel_and_lowering_are_dense_attention(
        heads, kv_heads, lengths, interpret_kernel):
    rng = np.random.default_rng(heads)
    n_pages = 1 + sum(-(-n // PT) for n in lengths) + 3
    kpool, vpool = _pools(rng, n_pages, PT, kv_heads)
    table, positions = _tables(rng, lengths, PT, 11, n_pages)
    q = rng.standard_normal((len(lengths), 1, heads, DH)).astype('f4')
    want = _dense_attention(q, kpool, vpool, table, positions)
    live = np.array(lengths) > 0          # an idle lane's row is not read
    kernel = _run_op(q, kpool, vpool, table, positions)
    fluid.set_flags({'pallas_interpret': False})
    lowering = _run_op(q, kpool, vpool, table, positions)
    for got in (kernel, lowering):
        assert got.shape == q.shape
        assert np.abs(got - want)[live].max() <= TOL * np.abs(want).max()


def test_equal_head_counts_read_the_blocks_they_read():
    """The accepted cells' pools (16 and 32 heads a page of 16 tokens)
    keep their 8-page blocks; 2 K/V heads a page take more pages a
    block, for the same bytes."""
    from paddle_tpu.pallas import paged_attention as pa
    assert pa.block_pages(128, 16, 16, 128) == 8
    assert pa.block_pages(192, 16, 32, 128) == 8
    assert pa.block_pages(128, 16, 2, 128) == 32
    assert pa.block_pages(4, 16, 2, 128) == 4


# ---------------------------------------------------------------------------
# the programs, and the counter
# ---------------------------------------------------------------------------

CFG = TransformerConfig(vocab=64, dim=32, heads=2, layers=2, ffn=64,
                        max_len=32, use_tp=False, use_sp=False)


@pytest.fixture(scope='module')
def lm_predictor(tmp_path_factory):
    return _save_lm(tmp_path_factory.mktemp('paged_attention_lm'), CFG, 11)


def _op_counts(program):
    return collections.Counter(op.type for op in
                               program.global_block().ops)


def test_decode_program_reads_pages_and_the_others_still_gather(
        lm_predictor):
    dec = lm_predictor.prepare_decoding(slots=2, page_tokens=4,
                                        prefill_chunk=8, speculative=True,
                                        spec_k=2, draft_layers=1)
    pair = dec._spair
    decode = _op_counts(pair.target.decode_program)
    assert decode['paged_attention'] == CFG.layers
    assert decode['kv_page_append'] == 2 * CFG.layers
    # a decode step's fork is copied in front of the program (PR 44);
    # a chunk's and a verify pass's inside theirs, as before
    assert decode['kv_page_cow'] == 0
    for gone in ('kv_page_gather', 'paged_decode_mask', 'softmax',
                 'matmul'):
        assert decode[gone] == 0, gone
    assert _op_counts(pair.draft.decode_program)['paged_attention'] == 1
    for program, mask in ((pair.target.prefill_program,
                           'paged_prefill_mask'),
                          (pair.verify_program, 'spec_verify_mask')):
        ops = _op_counts(program)
        assert ops['paged_attention'] == 0
        assert ops['kv_page_gather'] == 2 * CFG.layers
        assert ops['kv_page_cow'] == 2 * CFG.layers
        assert ops[mask] == ops['softmax'] == CFG.layers
        assert ops['matmul'] == 2 * CFG.layers


def test_decode_tables_span_counts_pages_read_of_the_window(lm_predictor):
    """Three streams of 3, 9 and 17 tokens in 4 slots of 8 pages of 4:
    a step at positions 3, 9 and 17 reads 1 + 3 + 5 pages of 32; the
    next one, at 4, 10 and 18, reads 2 + 3 + 5. The idle slot reads
    nothing that counts."""
    telemetry.reset()
    trace.clear()
    telemetry.enable()
    try:
        dec = lm_predictor.prepare_decoding(slots=4,
                                            page_tokens=4, prefill_chunk=8)
        prompts = {0: list(range(1, 4)), 2: list(range(1, 10)),
                   3: list(range(1, 18))}
        first = dec.prefill(list(prompts.values()), list(prompts))
        tokens = np.zeros((4,), np.int64)
        positions = np.zeros((4,), np.int32)
        for i, slot in enumerate(prompts):
            tokens[slot], positions[slot] = first[i], len(prompts[slot])
        for _ in range(2):
            ids = dec.decode_step(tokens, positions)
            for slot in prompts:
                tokens[slot] = ids[slot]
                positions[slot] += 1
        tables = [s for s in trace.spans()
                  if s['name'] == 'paged.decode.tables']
        assert [s['pages_read'] for s in tables] == [9, 10]
        counters = telemetry.snapshot()['counters']
        assert counters['serving.decode_pages_read'] == 19
    finally:
        telemetry.disable(final_flush=False)
        telemetry.reset()
        trace.clear()


# ---------------------------------------------------------------------------
# a mesh, and the chip's compiler
# ---------------------------------------------------------------------------

def _op_emitted(mesh, backend, heads=4, pt=8, n_pages=12, slots=2,
                pages_per_slot=4):
    """The paged_attention op's emitter, traced and lowered for
    `backend` under `mesh` with the pools sharded over its 'tp' axis:
    the lowered text."""
    from unittest import mock
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu import registry
    from paddle_tpu.executor import EmitContext
    prog = Program()
    with program_guard(prog, Program()):
        block = prog.global_block()
        names = ('q', 'kpool', 'vpool', 'table', 'positions')
        for n in names + ('ctx',):
            block.create_var(name=n, dtype='float32')
        block.append_op(
            type='paged_attention',
            inputs={'Q': ['q'], 'KPool': ['kpool'], 'VPool': ['vpool'],
                    'Table': ['table'], 'Positions': ['positions']},
            outputs={'Out': ['ctx']},
            attrs={'sm_scale': DH ** -0.5, 'head_axis': 'tp'})
    op, = block.ops

    def f(*arrays):
        ctx = EmitContext(dict(zip(names, arrays)), block, None, True)
        ctx.mesh = mesh
        registry._REGISTRY['paged_attention'].emit(ctx, op)
        return ctx.get('ctx')

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
            if mesh is not None else None)
    heads_on_tp = P(None, None, 'tp', None)
    args = (arg((slots, 1, heads, DH), jnp.float32, heads_on_tp),
            arg((n_pages, pt, heads, DH), jnp.float32, heads_on_tp),
            arg((n_pages, pt, heads, DH), jnp.float32, heads_on_tp),
            arg((slots, pages_per_slot), jnp.int32, P()),
            arg((slots,), jnp.int32, P()))
    with mock.patch.object(jax, 'default_backend', return_value=backend):
        return jax.jit(f).trace(*args).lower(
            lowering_platforms=(backend,)).as_text()


def test_kernel_lowers_for_the_chip_alone_and_per_shard_of_the_heads():
    """Cross-lowered for a TPU from here (the Pallas -> Mosaic lowering
    runs without a chip): one custom call; under a tp=2 mesh it sees
    half the heads of every page, inside shard_map. Off the TPU the
    same op lowers to the gather composition and no custom call."""
    import jax
    from jax.sharding import Mesh
    text = _op_emitted(None, 'tpu')
    assert text.count('tpu_custom_call') == 1
    assert 'tensor<12x%dx%dxf32>' % (8 * 4, DH) in text
    mesh = Mesh(np.array(jax.devices()[:2]), ('tp',))
    text = _op_emitted(mesh, 'tpu')
    assert text.count('tpu_custom_call') == 1
    assert 'tensor<12x%dx%dxf32>' % (8 * 2, DH) in text
    assert 'tpu_custom_call' not in _op_emitted(None, 'cpu')


def test_kernel_runs_per_shard_under_a_serving_mesh(tmp_path, monkeypatch,
                                                    interpret_kernel):
    """prepare_decoding(mesh='tp=2') with the kernel on: the decode
    program's paged_attention ops take the kernel inside shard_map (2 of
    4 heads a device) and the greedy stream is the single-chip
    reference lowering's."""
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    from paddle_tpu.pallas import paged_attention as pa
    cfg = TransformerConfig(vocab=64, dim=4 * DH, heads=4, layers=2, ffn=64,
                            max_len=64)
    _save_lm(tmp_path, cfg, 7)
    prompt, n = [3, 11, 5, 2, 9, 9, 1, 4, 7], 20

    def decoder(**kw):
        pred = AnalysisPredictor(AnalysisConfig(str(tmp_path),
                                                place=fluid.CPUPlace()))
        return pred.prepare_decoding(slots=2, page_tokens=8,
                                     prefill_chunk=8, **kw)
    seen = []
    kernel = pa.paged_attention
    monkeypatch.setattr(
        pa, 'paged_attention',
        lambda q, *a, **kw: seen.append(q.shape) or kernel(q, *a, **kw))
    got = decoder(mesh='tp=2').generate(prompt, n)
    assert seen == [(2, 2, DH)] * cfg.layers
    fluid.set_flags({'pallas_interpret': False})
    assert got == decoder().generate(prompt, n)
    assert len(seen) == cfg.layers


# -- latent rows: one pool, the values inside the keys -------------------------

def _latent_op(q, pool, table, positions, w_ukv, nope_dim, sm_scale):
    """One paged_latent_attention op through the real Executor."""
    prog, startup = Program(), Program()
    feeds = {'q': q, 'pool': pool, 'table': table, 'positions': positions,
             'w': w_ukv}
    with program_guard(prog, startup):
        block = prog.global_block()
        v = {n: fluid.layers.data(n, list(a.shape), append_batch_size=False,
                                  dtype=str(a.dtype))
             for n, a in feeds.items()}
        out = block.create_var(name='ctx', dtype='float32')
        block.append_op(
            type='paged_latent_attention',
            inputs={'Q': [v['q']], 'Pool': [v['pool']], 'Table': [v['table']],
                    'Positions': [v['positions']], 'WUKV': [v['w']]},
            outputs={'Out': [out]},
            attrs={'nope_dim': nope_dim, 'sm_scale': sm_scale})
    with fluid.scope_guard(fluid.Scope()):
        got, = fluid.Executor(fluid.CPUPlace()).run(prog, feed=feeds,
                                                    fetch_list=[out])
    return np.asarray(got)


LATENT_CASES = {
    # name: (heads, pages_per_slot, lengths, shared (child, parent))
    'one_token': (4, 4, [1], ()),
    'page_plus_one': (4, 4, [PT + 1], ()),
    # 40 pages a slot are one block of 32 and a tail of 8
    'past_one_block': (8, 40, [40 * PT, 33 * PT - 2, 32 * PT, 32 * PT + 1],
                       ()),
    'lanes_mixed_and_idle': (
        8, 11, [0, 5, 88, 0, 17, 64, 65, 1, 0, 33, 80, 8], ()),
    'pages_shared_between_lanes': (8, 11, [50, 70, 19, 50, 0, 44],
                                   ((1, 0), (3, 0), (5, 2))),
}


@pytest.mark.parametrize('name', sorted(LATENT_CASES))
def test_latent_kernel_matches_reference_lowering(name, interpret_kernel):
    """A row of 128 latent + 64 rotary values stored as 256; 32 + 64 wide
    query heads against it. The kernel in interpret mode and the op's
    absorbed composition over the gathered window are the same sum."""
    heads, pages_per_slot, lengths, share = LATENT_CASES[name]
    rng = np.random.default_rng(sorted(LATENT_CASES).index(name))
    dc, dr, dn, dv = 128, 64, 32, 32
    n_pages = 1 + sum(-(-n // PT) for n in lengths) + 3
    pool = np.zeros((n_pages, PT, 256), 'f4')
    pool[..., :dc + dr] = rng.standard_normal((n_pages, PT, dc + dr))
    table, positions = _tables(rng, lengths, PT, pages_per_slot, n_pages,
                               share)
    q = rng.standard_normal((len(lengths), 1, heads, dn + dr)).astype('f4')
    w = (rng.standard_normal((dc, heads * (dn + dv))) / 8).astype('f4')
    args = (q, pool, table, positions, w, dn, 0.1)
    got = _latent_op(*args)
    fluid.set_flags({'pallas_interpret': False})
    want = _latent_op(*args)
    assert got.shape == want.shape == (len(lengths), 1, heads * dv)
    live = np.array(lengths) > 0          # an idle lane's row is not read
    assert np.abs(got - want)[live].max() <= TOL * np.abs(want).max()


def test_latent_kernel_lowers_for_the_chip_at_the_published_row():
    """Cross-lowered for a TPU from here at the served shape: 64 heads
    over rows of 640 (512 + 64 stored in whole lanes), 1024 pages a slot
    in blocks of 32. One custom call, the pool handed over as it lies."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.pallas import paged_attention as pa
    S, H, row, N, pt, P = 4, 64, 640, 64, 16, 1024
    f = functools.partial(pa.paged_latent_attention, sm_scale=0.13,
                          value_dim=512)
    text = jax.jit(f).trace(
        jax.ShapeDtypeStruct((S, H, row), jnp.float32),
        jax.ShapeDtypeStruct((N, pt, row), jnp.float32),
        jax.ShapeDtypeStruct((S, P), jnp.int32),
        jax.ShapeDtypeStruct((S,), jnp.int32)).lower(
            lowering_platforms=('tpu',)).as_text()
    assert text.count('tpu_custom_call') == 1
    assert 'tensor<%dx%dx%dxf32>' % (N, pt, row) in text
    assert pa.latent_supported(16, 640, 512)
    assert not pa.latent_supported(16, 576, 512)


# ---------------------------------------------------------------------------
# a window: the sliding layers' calls
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('window,lengths', [
    # pos < window; the window's edge inside a page; on a page boundary;
    # the last page full; one token
    (20, [5, 19, 20, 21, 27, 28, 29, 88, 0, 1]),
    (PT, [PT, PT + 1, 2 * PT, 3 * PT - 1, 70]),        # one page of keys
    (3 * PT, [3 * PT, 3 * PT + 1, 4 * PT, 11 * PT, 2]),
    (1, [1, PT, PT + 1, 40]),                          # the own position
])
def test_window_kernel_and_lowering_are_a_dense_band(window, lengths,
                                                     interpret_kernel):
    """4 K/V heads under 8 query heads: lane s attends to the last
    `window` positions up to its own, through the kernel (which starts
    its walk at the window's first page) and through the reference
    lowering alike, and neither reads a page behind the window: those
    table entries name a page of NaNs."""
    rng = np.random.default_rng(window)
    n_pages = 2 + sum(-(-n // PT) for n in lengths) + 3
    kpool, vpool = _pools(rng, n_pages, PT, 4)
    table, positions = _tables(rng, lengths, PT, 11, n_pages - 1)
    q = rng.standard_normal((len(lengths), 1, 8, DH)).astype('f4')
    want = _dense_attention(q, kpool, vpool, table, positions, window)
    # what lies wholly behind a lane's window was given up long ago
    poisoned = table.copy()
    kpool[n_pages - 1] = vpool[n_pages - 1] = np.nan
    for s, n in enumerate(lengths):
        poisoned[s, :max(0, n - window) // PT] = n_pages - 1
    live = np.array(lengths) > 0
    kernel = _run_op(q, kpool, vpool, poisoned, positions, window=window)
    fluid.set_flags({'pallas_interpret': False})
    lowering = _run_op(q, kpool, vpool, table, positions, window=window)
    for got in (kernel, lowering):
        assert got.shape == q.shape
        assert np.abs(got - want)[live].max() <= TOL * np.abs(want).max()
    # and a band it is: the whole history gives another answer
    far = np.array(lengths) > window
    whole = np.abs(_run_op(q, kpool, vpool, table, positions) - want)[far]
    assert whole.reshape(far.sum(), -1).max(axis=1).min() \
        > 100 * TOL * np.abs(want).max()


def test_window_kernel_carries_its_own_name_and_lowers_for_the_chip():
    """The sliding layers' calls are told from the full layers' in a
    device trace: another op type for the harness's labels, another
    kernel name in the custom call; and the window form passes the
    Pallas -> Mosaic lowering at 4 K/V heads a page of 16 tokens."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.pallas import paged_attention as pa
    f4 = jnp.float32
    args = (jax.ShapeDtypeStruct((48, 28, 128), f4),
            jax.ShapeDtypeStruct((64, 16, 4, 128), f4),
            jax.ShapeDtypeStruct((64, 16, 4, 128), f4),
            jax.ShapeDtypeStruct((48, 273), jnp.int32),
            jax.ShapeDtypeStruct((48,), jnp.int32))
    texts = {w: jax.jit(lambda *a, w=w: pa.paged_attention(
        *a, sm_scale=0.0884, window=w)).trace(*args).lower(
            lowering_platforms=('tpu',)).as_text() for w in (0, 4096)}
    assert 'paged_window_attention' in texts[4096]
    assert 'paged_window_attention' not in texts[0]
    assert all(t.count('tpu_custom_call') == 1 for t in texts.values())


@pytest.mark.parametrize('window', [0, 5])
def test_prefill_mask_is_causal_or_a_band(window):
    """paged_prefill_mask: row i sees column j iff j <= positions[i]
    and, with a window, positions[i] - window < j."""
    prog = Program()
    positions = np.array([3, 4, 9, 12], 'int32')
    x = np.random.default_rng(0).standard_normal((1, 2, 4, 16)).astype('f4')
    with program_guard(prog, Program()):
        block = prog.global_block()
        xv = fluid.layers.data('x', list(x.shape), append_batch_size=False)
        pv = fluid.layers.data('p', [4], append_batch_size=False,
                               dtype='int32')
        out = block.create_var(name='masked', dtype='float32')
        block.append_op(type='paged_prefill_mask',
                        inputs={'X': [xv], 'Positions': [pv]},
                        outputs={'Out': [out]},
                        attrs={'window': window} if window else {})
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        got, = exe.run(prog, feed={'x': x, 'p': positions},
                       fetch_list=[out])
    j = np.arange(16)
    seen = j[None, :] <= positions[:, None]
    if window:
        seen &= j[None, :] > positions[:, None] - window
    assert np.array_equal(np.asarray(got) == x,
                          np.broadcast_to(seen, x.shape))
    assert np.all(np.asarray(got)[~np.broadcast_to(seen, x.shape)] == -1e9)
