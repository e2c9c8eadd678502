"""A decode step's copy-on-write, in front of the decode program
(models/transformer.py `build_page_copy_program`, serving/paged.py
`_fork_pages` / `_copy_pages`): the decode program copies no page, and
the pair's page copy program runs, one dispatch for all pools, in front
of a decode step only when the host's table says a page forks in it.
The prefill and verify programs keep their own kv_page_cow.

What must hold, for each of the four served blocks (GPT-2, the hybrid
and Nemotron-H blocks with recurrent state, the A.X-K1 block whose page
is one pool of latent rows):

- the decode program holds no `kv_page_cow` op and no `*cow*` feed, the
  prefill program one a pool as before; the copy program is one such op
  a pool and nothing else;
- a step without a fork dispatches nothing for it (the counters
  `serving.cow.dispatches` / `serving.cow.pages` do not move; the null
  run in front of the first decode step is not counted, nor a chunk's
  copy inside its program); a step with a fork dispatches exactly one copy, of its
  pairs, and the stream's tokens are those of a stream that shared
  nothing;
- nothing compiles at a fork: the copy program compiled where the
  decode program did, at the predictor's first decode step, forking or
  not;
- a step that forked and then ran out of pages dispatches nothing and
  leaves tables and refcounts as they were;
- inside a copy all sources are read before any destination is written;
- two streams on one registered prefix, forking behind a deferred step
  in flight, give the tokens of two streams that share nothing;
- the draft pair of a speculative predictor forks through its own copy
  program, verify inside its program; a mesh keeps the pools' sharding
  through a copy;
- README's Observability section names the counters and the span, and
  says that they count forks only.
"""
import contextlib
import os

import numpy as np
import pytest

from paddle_tpu.models.transformer import TransformerConfig
from paddle_tpu.obs import telemetry, trace
from paddle_tpu.serving.paging import CacheExhaustedError

from test_axk1 import _build as _build_axk1
from test_hybrid_serving import _build as _build_hybrid
from test_nemotron_h import _build as _build_nemotron_h
from test_paged import _save_lm
from test_spans import registry_on          # noqa: F401 (a fixture)

GPT2 = TransformerConfig(vocab=64, dim=32, heads=2, layers=2, ffn=64,
                         max_len=48, use_tp=False, use_sp=False)
BLOCKS = ['gpt2', 'hybrid', 'nemotron_h', 'axk1']
# the blocks whose streams the prefix cache serves (no recurrent state)
PREFIX_BLOCKS = ['gpt2', 'axk1']


@pytest.fixture(scope='module')
def gpt2_served(tmp_path_factory):
    rng = np.random.RandomState(43)
    return _save_lm(tmp_path_factory.mktemp('copy_gpt2'), GPT2, 9), \
        [int(t) for t in rng.randint(1, GPT2.vocab, size=48)]


def _built(build, tmp):
    pred, toks, _ = build(tmp)
    return pred, [int(t) for t in toks]


@pytest.fixture(scope='module')
def hybrid_served(tmp_path_factory):
    return _built(_build_hybrid, tmp_path_factory.mktemp('copy_hybrid'))


@pytest.fixture(scope='module')
def nemotron_h_served(tmp_path_factory):
    return _built(_build_nemotron_h,
                  tmp_path_factory.mktemp('copy_nemotron_h'))


@pytest.fixture(scope='module')
def axk1_served(tmp_path_factory):
    return _built(_build_axk1, tmp_path_factory.mktemp('copy_axk1'))


@pytest.fixture(params=BLOCKS)
def served(request):
    """(predictor, 48 token ids to cut prompts from), a block kind."""
    return request.getfixturevalue(request.param + '_served')


@pytest.fixture(params=PREFIX_BLOCKS)
def prefix_served(request):
    return request.getfixturevalue(request.param + '_served')


def _decoder(pred, **kw):
    kw = dict(dict(slots=3, page_tokens=4, kv_pages=40,
                   prefill_chunk=8), **kw)
    return pred.prepare_decoding(**kw)


def _counters():
    snap = telemetry.snapshot()['counters']
    return (snap.get('serving.cow.dispatches', 0),
            snap.get('serving.cow.pages', 0))


def _forget():
    """The references' forks are not the decoder's under test."""
    telemetry.reset()
    trace.clear()


def _cow_spans():
    return [s for s in trace.spans() if s['name'] == 'paged.cow']


def _record_copies(dec):
    """Every call of the copy program on this predictor that copies a
    page, as its list of (src, dst) pairs (wrapped on the instance; the
    run over null pairs that compiles the program is left out)."""
    calls, run = [], dec._copy_pages

    def copy_pages(pairs):
        if pairs:
            calls.append([tuple(int(p) for p in pair) for pair in pairs])
        return run(pairs)
    dec._copy_pages = copy_pages
    return calls


@contextlib.contextmanager
def _jax_compile_events():
    """The names of JAX's own trace / lower / compile / cache-read
    events while the block runs: a jit that met a new signature shows
    here even where the executor counts no new segment."""
    import jax.monitoring
    seen = []

    def listen(event, duration, **_):
        if event.startswith(('/jax/core/compile/',
                             '/jax/compilation_cache/')):
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _pages(dec, page):
    """The rows of physical page `page`, a pool."""
    return [np.asarray(dec._scope.find_var(name))[page]
            for name in dec._pair.cache_names]


def _share(dec, slot):
    """Make the page the stream's next append lands on a shared one, as
    the prefix cache or a second stream would: one more ref, held here,
    and the table index marked. -> (table index, the page)."""
    table = dec._tables[slot]
    idx = table.length // dec.page_tokens
    dec._pool.share(table.pages[idx])
    table.mark_shared(idx)
    return idx, table.pages[idx]


def _step(dec, feed, **kw):
    """One decode step over the lanes of feed = {slot: (token,
    position)} -> ids [slots] (or what a deferred step hands back)."""
    tokens = np.zeros(dec.slots, np.int64)
    positions = np.zeros(dec.slots, np.int32)
    for slot, (tok, pos) in feed.items():
        tokens[slot], positions[slot] = tok, pos
    return dec.decode_step(tokens, positions, lanes=sorted(feed), **kw)


# --------------------------------------------------------------------------
# the programs
# --------------------------------------------------------------------------

def _ops(program):
    return [op.type for op in program.global_block().ops]


def test_the_decode_program_copies_no_page_and_the_copy_program_only_copies(
        served):
    pair = _decoder(served[0])._pair
    pools = pair.spec.pool_names()
    assert pools
    assert 'kv_page_cow' not in _ops(pair.decode_program)
    assert not [f for f in pair.decode_feeds if 'cow' in f]
    # a chunk's fork stays inside its program: one copy a pool, one pair
    assert _ops(pair.prefill_program).count('kv_page_cow') == len(pools)
    assert [f for f in pair.prefill_feeds if 'cow' in f] == \
        ['prefill_cow_src', 'prefill_cow_dst']
    assert _ops(pair.copy_program) == ['kv_page_cow'] * len(pools)
    assert pair.copy_feeds == ['page_copy_src', 'page_copy_dst']
    block = pair.copy_program.global_block()
    assert [op.single_output('Out') for op in block.ops] == list(pools) \
        == [op.single_input('Pool') for op in block.ops]
    for name in pair.copy_feeds:
        assert tuple(block.vars[name].shape) == (pair.slots,)


def test_the_latent_page_is_one_pool_a_layer_in_the_same_program(
        axk1_served):
    pair = _decoder(axk1_served[0])._pair
    assert pair.spec.page_kind == 'latent'
    assert len(pair.pool_shape) == 3
    assert len(_ops(pair.copy_program)) == len(pair.spec.kv_layers)


# --------------------------------------------------------------------------
# when it runs
# --------------------------------------------------------------------------

def test_a_step_copies_only_when_a_page_forks_and_compiles_nothing_then(
        served, registry_on):
    pred, toks = served
    dec, plain = _decoder(pred), _decoder(pred)
    # built: nothing has compiled, nothing has run
    assert dec.jit_cache_stats()['compiled_segments'] == 0
    copies = _record_copies(dec)
    # a prompt of whole pages leaves no partly filled page to share:
    # neither its chunk nor the appends that follow fork anything
    first = {d: int(d.prefill([toks[:8]], [1])[0]) for d in (dec, plain)}
    assert first[dec] == first[plain]
    assert dec.jit_cache_stats()['compiled_segments'] == 1      # the chunk
    tok = {d: first[d] for d in (dec, plain)}
    for pos in (8, 9):
        for d in (dec, plain):
            tok[d] = int(_step(d, {1: (tok[d], pos)})[1])
    # the first decode step compiled the copy program beside its own,
    # in a run over null pairs: no fork, and counted as none
    assert copies == [] and _counters() == (0, 0) and not _cow_spans()
    compiled = dec.jit_cache_stats()['compiled_segments']
    assert compiled == 3               # the chunk, the copy, the step
    # the page the next append lands on becomes a shared one: the step
    # runs one copy in front of its program, of that page; nothing is
    # traced, lowered or compiled for it then
    idx, src = _share(dec, 1)
    before = _pages(dec, src)
    tok[plain] = int(_step(plain, {1: (tok[plain], 10)})[1])
    with _jax_compile_events() as compiled_then:
        tok[dec] = int(_step(dec, {1: (tok[dec], 10)})[1])
    assert compiled_then == []
    dst = dec._tables[1].pages[idx]
    assert dst != src and idx not in dec._tables[1].shared
    assert copies == [[(src, dst)]]
    assert _counters() == (1, 1)
    span, = _cow_spans()
    assert span['pages'] == 1
    assert len([s for s in trace.spans() if s['name'] == 'exe.run'
                and s.get('psid') == span['sid']]) == 1
    for was, kept, forked in zip(before, _pages(dec, src),
                                 _pages(dec, dst)):
        assert np.array_equal(kept, was)           # the source: untouched
        assert np.array_equal(forked[:2], was[:2])  # positions 8 and 9
        assert np.any(forked[:2] != 0)
    assert dec.jit_cache_stats()['compiled_segments'] == compiled
    # the fork is private from here on: no further copy, and the tokens
    # are those of the stream that never shared a page
    for pos in (11, 12):
        for d in (dec, plain):
            tok[d] = int(_step(d, {1: (tok[d], pos)})[1])
        assert tok[dec] == tok[plain]
    assert len(copies) == 1 and _counters() == (1, 1)
    assert dec.jit_cache_stats()['compiled_segments'] == compiled


def test_exhaustion_in_a_step_that_forked_dispatches_nothing(served,
                                                             registry_on):
    """Lane 0 forks its last page, which takes the pool's last free
    page; lane 1 then cannot grow. Nothing runs, and the fork is undone:
    tables, refcounts and the free list are those before the call."""
    pred, toks = served
    dec = _decoder(pred, kv_pages=6)
    first = dec.prefill([toks[:6], toks[8:16]], [0, 1])
    while dec._prefix.evict_one():      # no cache entry left to give way
        pass
    idx, src = _share(dec, 0)
    assert dec._pool.pages_free == 1
    copies = _record_copies(dec)
    tables = {s: (list(t.pages), set(t.shared), t.length)
              for s, t in dec._tables.items()}
    refs, free = list(dec._pool._ref), sorted(dec._pool._free)
    runs = dec.jit_cache_stats()
    feed = {0: (int(first[0]), 6), 1: (int(first[1]), 8)}
    with pytest.raises(CacheExhaustedError) as ei:
        _step(dec, feed)
    assert ei.value.slots == (1,)
    assert copies == [] and _counters() == (0, 0) and not _cow_spans()
    assert dec.jit_cache_stats() == runs            # no dispatch at all
    assert {s: (list(t.pages), set(t.shared), t.length)
            for s, t in dec._tables.items()} == tables
    assert list(dec._pool._ref) == refs
    assert sorted(dec._pool._free) == free
    dec._pool.check()
    # the victim gone, the same feed's survivor forks and runs
    dec.release(1)
    _step(dec, {0: feed[0]})
    assert copies == [[(src, dec._tables[0].pages[idx])]]
    assert _counters() == (1, 1)


def test_a_copy_reads_every_source_before_it_writes(served):
    """Pairs (1 -> 2) and (2 -> 3) in one copy: page 3 takes what page
    2 held before the copy, not what page 1 just put there: a page
    freed and handed out again inside one step still donates what it
    held before the step."""
    dec = _decoder(served[0])
    rng = np.random.RandomState(5)
    names = dec._pair.cache_names
    before = [rng.standard_normal(dec._pair.pool_shape).astype(np.float32)
              for _ in names]
    for name, pool in zip(names, before):
        dec._scope.set_var(name, pool.copy())
    dec._copy_pages([(1, 2), (2, 3)])
    for name, was in zip(names, before):
        now = np.asarray(dec._scope.find_var(name))
        assert np.array_equal(now[2], was[1])
        assert np.array_equal(now[3], was[2])
        keep = [p for p in range(len(was)) if p not in (2, 3)]
        assert np.array_equal(now[keep], was[keep])


# --------------------------------------------------------------------------
# two streams on one registered prefix, a deferred step in flight
# --------------------------------------------------------------------------

def test_streams_on_one_prefix_fork_behind_a_step_in_flight(prefix_served,
                                                            registry_on):
    """A's prompt ends inside its second page, which the prefix cache
    registers; B's prompt is A's and one token more, so B opens on that
    very page. A's first append forks it (a deferred step, left in
    flight); B's chunk forks it inside its program, behind that step;
    B's first append forks the tail B registered, again with a step in
    flight. Both streams are those of two decoders that share
    nothing."""
    pred, toks = prefix_served
    pa, pb = toks[:6], toks[:7]
    want_a = list(_decoder(pred).generate(pa, 6))
    want_b = list(_decoder(pred).generate(pb, 5))
    _forget()
    dec = _decoder(pred)
    got_a = [int(dec.prefill([pa], [0])[0])]
    assert _step(dec, {0: (got_a[0], 6)}, defer=True) is None
    assert dec.in_flight and _counters() == (1, 1)
    assert dec.open_stream(2, pb)['shared_tokens'] == 6
    got_b = [int(dec.prefill_step(2))]
    # the chunk's own copy is the prefill program's: not counted here
    assert dec.in_flight and _counters() == (1, 1)
    ids = _step(dec, {0: (0, 7), 2: (got_b[0], 7)}, carry=[0], defer=True)
    got_a.append(int(ids[0]))
    assert _counters() == (2, 2)
    for pos in (8, 9, 10):
        ids = _step(dec, {0: (0, pos), 2: (0, pos)}, carry=[0, 2],
                    defer=True)
        got_a.append(int(ids[0]))
        got_b.append(int(ids[2]))
    ids = dec.collect()
    got_a.append(int(ids[0]))
    got_b.append(int(ids[2]))
    assert got_a == want_a and got_b == want_b
    assert _counters() == (2, 2)
    assert [s['pages'] for s in _cow_spans()] == [1, 1]


def test_two_lanes_forking_in_one_step_share_one_copy(prefix_served,
                                                      registry_on):
    pred, toks = prefix_served
    prompts = {0: toks[:6], 2: toks[20:27]}
    want = {s: list(_decoder(pred).generate(p, 3))
            for s, p in prompts.items()}
    _forget()
    dec = _decoder(pred)
    copies = _record_copies(dec)
    first = dec.prefill(list(prompts.values()), list(prompts))
    got = {s: [int(t)] for s, t in zip(prompts, first)}
    assert copies == []
    for k in range(2):
        ids = _step(dec, {s: (got[s][-1], len(prompts[s]) + k)
                          for s in prompts})
        for s in prompts:
            got[s].append(int(ids[s]))
    assert got == want
    # both registered tails forked in the first step: one copy, two pairs
    assert len(copies) == 1 and len(copies[0]) == 2
    assert _counters() == (1, 2)
    assert [s['pages'] for s in _cow_spans()] == [2]


# --------------------------------------------------------------------------
# speculative verify and its draft; a mesh
# --------------------------------------------------------------------------

def test_the_draft_forks_through_its_own_copy_and_verify_inside_its_program(
        gpt2_served, registry_on):
    pred, toks = gpt2_served
    prompt, n = toks[:6], 6
    want = list(_decoder(pred).generate(prompt, n))
    _forget()
    spec = _decoder(pred, speculative=True, spec_k=2, draft_layers=1)
    assert spec._pair.copy_program is not spec.draft._pair.copy_program
    assert _ops(spec._spair.verify_program).count('kv_page_cow') \
        == 2 * GPT2.layers
    target, draft = _record_copies(spec), _record_copies(spec.draft)
    got = [int(spec.prefill([prompt], [0])[0])]
    assert target == [] and draft == []
    tokens = np.zeros(spec.slots, np.int64)
    positions = np.zeros(spec.slots, np.int32)
    pos = len(prompt)
    while len(got) < n:
        tokens[0], positions[0] = got[-1], pos
        out = spec.spec_step(tokens, positions)[0]
        got.extend(int(t) for t in out)
        pos += len(out)
    assert got[:n] == want
    # each cache registered the prompt's tail, so each side's first
    # append forked it: the target's inside the verify program, the
    # draft's, a decode step, through the draft pair's copy program
    assert target == [] and len(draft) == 1
    assert _counters() == (1, 1)
    assert spec.spec_stats()['fallback_steps'] == 0


def test_a_mesh_keeps_the_pools_sharded_through_a_copy(tmp_path):
    import paddle_tpu as fluid
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    cfg = TransformerConfig(vocab=64, dim=32, heads=4, layers=2, ffn=64,
                            max_len=32)
    _save_lm(tmp_path, cfg, 7)

    def predictor():
        return AnalysisPredictor(AnalysisConfig(str(tmp_path),
                                                place=fluid.CPUPlace()))
    prompt = [3, 11, 5, 2, 9, 7]
    want = predictor().prepare_decoding(
        slots=2, page_tokens=4, prefill_chunk=8).generate(prompt, 8)
    dec = predictor().prepare_decoding(slots=2, page_tokens=4,
                                       prefill_chunk=8, mesh='tp=2')
    copies = _record_copies(dec)

    def specs():
        return {tuple(dec._scope.find_var(n).sharding.spec)
                for n in dec._pair.cache_names}
    assert specs() == {(None, None, 'tp', None)}   # the null run's too
    assert dec.generate(prompt, 8) == want
    assert len(copies) == 1                  # the registered tail forked
    assert specs() == {(None, None, 'tp', None)}
    # a second stream opens on that tail (its prompt ends inside it):
    # its chunk forks it in its program, its first append forks the
    # tail it registered, through the copy
    before = dec.jit_cache_stats()['compiled_segments']
    dec.release(0)
    longer = prompt + [4]
    assert dec.generate(longer, 8, slot=1) == \
        predictor().prepare_decoding(
            slots=2, page_tokens=4, prefill_chunk=8).generate(longer, 8)
    assert len(copies) == 2
    assert specs() == {(None, None, 'tp', None)}
    assert dec.jit_cache_stats()['compiled_segments'] == before


# --------------------------------------------------------------------------
# what README says of them
# --------------------------------------------------------------------------

def test_readme_names_the_counters_and_their_rule():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, 'README.md')) as f:
        text = f.read()
    section = text[text.index('Observability'):]
    for name in ('serving.cow.dispatches', 'serving.cow.pages',
                 'paged.cow'):
        assert '`%s`' % name in section, name
    at = section.index('`serving.cow.dispatches`')
    assert 'count forks only' in ' '.join(section[at:at + 1200].split())
