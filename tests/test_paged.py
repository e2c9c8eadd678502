"""Paged KV cache: allocator invariants, page-op units, bit-exact
parity, chunked prefill, copy-on-write isolation, prefix sharing, and
typed exhaustion.

The contract under test (ISSUE 13 acceptance):
- PagePool refcounting survives randomized alloc/free/share churn with
  the free list and the ref>0 set always partitioning the pool, no
  leak, no double free (property-style, pool.check() as the oracle)
- greedy decode over the page pool is BIT-EXACT against full recompute
  (np.array_equal, not allclose), with each paged program compiling
  exactly once (jit_cache_stats)
- chunked prefill produces the same first token + logits as a
  whole-prompt prefill
- two streams sharing a prefix never cross-talk: the first divergent
  append forks the shared page (COW) and the parent's subsequent
  logits are unchanged
- two streams sharing a 512-token system prompt: the second prefills
  ONE suffix chunk instead of five (zero recompute over the shared
  pages), bit-exact against its own cold prefill
- pool exhaustion is a typed, retryable CacheExhaustedError naming the
  victim slots with that step's allocations rolled back (COVERAGE
  divergence 8: never a silent slide) — and the fleet
  router requeues such a failure as a shed instead of failing the
  stream
"""
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.models.transformer import (TransformerConfig,
                                           language_model_logits)
from paddle_tpu.serving.paging import (CacheExhaustedError, PagePool,
                                       PageTable, PrefixCache)
from op_test import OpTest

CFG = TransformerConfig(vocab=64, dim=32, heads=2, layers=2, ffn=64,
                        max_len=16, use_tp=False, use_sp=False)
# long-context shape for the 512-token shared-system-prompt test
BIG = TransformerConfig(vocab=64, dim=16, heads=2, layers=1, ffn=32,
                        max_len=576, use_tp=False, use_sp=False)


# --------------------------------------------------------------------------
# host-side allocator: property-style invariants
# --------------------------------------------------------------------------

def test_page_pool_random_churn_preserves_invariants():
    rng = np.random.RandomState(0)
    pool = PagePool(17, 4)
    held = []                 # one entry per ref WE own (dupes = shares)
    for _ in range(2000):
        r = rng.rand()
        if r < 0.45:
            try:
                held.append(pool.alloc())
            except CacheExhaustedError:
                assert pool.pages_free == 0
        elif r < 0.80 and held:
            pool.unref(held.pop(rng.randint(len(held))))
        elif held:
            held.append(pool.share(held[rng.randint(len(held))]))
        pool.check()
    for p in held:
        pool.unref(p)
    pool.check()
    assert pool.pages_in_use == 0 and pool.pages_free == 16
    with pytest.raises(ValueError, match='double free'):
        pool.unref(1)
    with pytest.raises(ValueError, match='null page'):
        pool.unref(0)


def test_page_pool_alloc_many_all_or_nothing():
    pool = PagePool(5, 4)                 # 4 usable pages
    pool.alloc_many(3)
    with pytest.raises(CacheExhaustedError):
        pool.alloc_many(2)
    pool.check()
    assert pool.pages_free == 1           # the failed batch took nothing


def test_page_table_cow_never_mutates_parent():
    pool = PagePool(9, 4)
    parent = PageTable(pool, 2)
    parent.ensure(6)
    parent.length = 6
    before = list(parent.pages)
    child = PageTable(pool, 2)
    child.adopt_shared(list(parent.pages), 6)
    pair = child.cow_for_append(6)        # first divergent append
    assert pair is not None
    src, dst = pair
    assert src == before[1] and dst not in before
    assert parent.pages == before         # parent untouched
    assert child.pages[0] == before[0] and child.pages[1] == dst
    # deferred unref: the child's ref on src survives until the device
    # copy actually ran (what lets a failed step roll back safely)
    assert pool.refcount(src) == 2
    pool.unref(src)                       # what paged.py does post-run
    pool.check()
    child.release()
    parent.release()
    pool.check()
    assert pool.pages_in_use == 0


def test_prefix_cache_register_match_evict():
    pool = PagePool(17, 4)
    table = PageTable(pool, 4)
    prompt = list(range(10))              # 2 full pages + 2-token tail
    table.ensure(10)
    table.length = 10
    cache = PrefixCache(pool)
    shared = cache.register(prompt, table)
    assert shared == [0, 1, 2]            # both full pages + the tail
    assert len(cache) == 3
    # limit=len-1 keeps the last token out: only the full pages match
    pages, tokens = cache.match(prompt, limit=9)
    assert tokens == 8 and len(pages) == 2
    # a different continuation still matches full pages + the tail
    pages, tokens = cache.match(prompt + [99, 98], limit=11)
    assert tokens == 10 and len(pages) == 3
    assert cache.hits == 2 and cache.tokens_reused == 18
    # leaf-first LRU: the tail, then the now-leaf chain nodes
    for expect_left in (2, 1, 0):
        assert cache.evict_one()
        assert len(cache) == expect_left
        pool.check()                      # table refs keep pages live
    assert not cache.evict_one()
    table.release()
    pool.check()
    assert pool.pages_in_use == 0


def _registered(cache, pool, prompt):
    """A stream that prefilled `prompt`, registered it with its state
    and ended: (row handed out, pages the cache still holds)."""
    table = PageTable(pool, 8)
    pages, shared, snap = cache.match_state(prompt, limit=len(prompt) - 1)
    if shared:
        table.adopt_shared(pages, shared)
        cache.unpin(snap)
    pair = table.cow_for_append(shared)
    table.ensure(len(prompt))
    if pair is not None:
        pool.unref(pair[0])
    table.length = len(prompt)
    row = cache.register_state(prompt, table)
    table.release()
    return row


@pytest.mark.parametrize('case', ['longest_boundary', 'row_lru_releases',
                                  'pages_take_the_snapshot', 'pinned',
                                  'same_prompt_twice', 'spent_goes_first'])
def test_prefix_cache_where_a_prefix_is_pages_and_state(case):
    """PrefixCache with snapshot rows, on the host alone: a match ends
    at a boundary that has a snapshot; rows are bounded and LRU, those
    a conversation has moved on from first; a snapshot and the pages
    that only it made usable go together."""
    pool = PagePool(40, 4)
    cache = PrefixCache(pool, snapshot_rows=4 if case == 'spent_goes_first'
                        else 2)
    pool.set_evict(cache.evict_one)
    system = list(range(100, 108))                    # two whole pages
    turn1 = system + list(range(200, 207))            # + a page and 3
    if case == 'longest_boundary':
        assert _registered(cache, pool, system) is not None
        assert _registered(cache, pool, turn1) is not None
        assert cache.snapshots == 2 and len(cache) == 2 + 2
        # past both boundaries: the longer; pages and a tail
        pages, n, snap = cache.match_state(turn1 + [1, 2, 3], limit=17)
        assert (n, len(pages), snap.pins) == (15, 4, 1)
        cache.unpin(snap)
        # the same pages and another continuation inside the tail page:
        # the tail's boundary is not this prompt's, the system's is
        pages, n, snap = cache.match_state(turn1[:13] + [9, 9, 9], limit=15)
        assert (n, len(pages)) == (8, 2)
        cache.unpin(snap)
        # whole pages in common and no boundary among them: nothing
        assert cache.match_state(system[:4] + [5] * 8, limit=11) == \
            ([], 0, None)
        # the boundary itself is under the limit of its own prompt + 1
        assert cache.match_state(system, limit=7)[1] == 0
        assert cache.match_state(system + [1], limit=8)[1] == 8
    elif case == 'row_lru_releases':
        _registered(cache, pool, system)
        _registered(cache, pool, turn1)
        held = pool.pages_in_use
        pages, n, snap = cache.match_state(system + [7], limit=8)   # touch
        cache.unpin(snap)
        other = system + list(range(300, 305))
        assert _registered(cache, pool, other) is not None
        # turn1's snapshot went for its row, with the page and the tail
        # that only it kept; the system's pages serve the newcomer
        assert cache.snapshots == 2 and cache.snapshots_dropped == 1
        assert pool.pages_in_use == held
        assert cache.match_state(turn1 + [1], limit=15)[1] == 8
        pool.check()
    elif case == 'pages_take_the_snapshot':
        _registered(cache, pool, turn1)
        assert cache.snapshots == 1
        assert cache.evict_one()                      # the tail, a leaf
        assert cache.snapshots == 0 and cache.snapshots_dropped == 1
        # three whole pages are still registered and lead to no boundary
        assert len(cache) == 3
        assert cache.match_state(turn1 + [1], limit=15) == ([], 0, None)
        assert len(cache._free_rows) == 2
    elif case == 'pinned':
        _registered(cache, pool, system)
        _registered(cache, pool, turn1)
        a = cache.match_state(system + [1], limit=8)[2]
        b = cache.match_state(turn1 + [1], limit=15)[2]
        other = [5] * 9
        before = len(cache)
        assert _registered(cache, pool, other) is None  # no row to give
        assert len(cache) == before                   # and no pages kept
        cache.unpin(b)
        assert _registered(cache, pool, other) is not None
        assert b.gone and not a.gone
        cache.unpin(a)
        pool.check()
    elif case == 'spent_goes_first':
        other1 = system + list(range(300, 305))
        turn2 = turn1 + list(range(400, 406))
        for prompt in (system, turn1, other1, turn2):
            assert _registered(cache, pool, prompt) is not None
        # two sessions opened on the system prompt: a shared prefix; one
        # opened on turn1 and has registered turn2: a conversation that
        # moved on, whose row goes before older ones
        at = {s.tokens: s for rows in cache._snaps.values()
              for s in rows.values()}
        assert [at[n].spent for n in (8, 15, 13, 21)] == \
            [False, True, False, False]
        assert at[8].passed and at[8].reads == 2
        assert at[15].stamp > at[13].stamp > at[8].stamp
        assert _registered(cache, pool, [5] * 9) is not None
        assert at[15].gone and cache.snapshots_dropped == 1

        def opens_on(prompt):
            _, n, snap = cache.match_state(prompt, limit=len(prompt) - 1)
            if snap is not None:
                cache.unpin(snap)
            return n
        assert opens_on(turn2 + [1, 2]) == 21
        assert opens_on(other1 + [1, 2]) == 13
        # what turn1 alone could serve falls back to the system prompt
        assert opens_on(turn1 + [1, 2]) == 8
        # a boundary two streams went on from is a shared prefix, not a
        # conversation that moved on: the least recently used rows go
        for more in ([7], [8]):
            assert _registered(cache, pool, other1 + more) is not None
        assert at[13].passed and at[13].reads == 3 and not at[13].spent
        assert not at[13].gone and not at[8].gone and at[21].gone
        assert cache.snapshots == 4 and cache.snapshots_dropped == 3
        pool.check()
    else:
        row = _registered(cache, pool, turn1)
        assert row is not None
        assert _registered(cache, pool, turn1[:12]) is not None
        # the boundary has its snapshot: nothing to copy, nothing taken
        assert _registered(cache, pool, turn1) is None
        assert cache.snapshots == 2 and cache.snapshots_dropped == 0
        assert not cache._free_rows


# --------------------------------------------------------------------------
# page op units (ops/attention_ops.py)
# --------------------------------------------------------------------------

class TestKVPageCow(OpTest):
    def test_copy_pairs_and_null_padding(self):
        rng = np.random.RandomState(3)
        pool = rng.rand(4, 2, 2, 2).astype('f4')
        src = np.array([2, 0], 'int32')    # (0, 0) is the no-op pad
        dst = np.array([1, 0], 'int32')
        expect = pool.copy()
        expect[1] = pool[2]
        self.op_type = 'kv_page_cow'
        self.inputs = {'Pool': pool, 'Src': src, 'Dst': dst}
        self.outputs = {'Out': expect}
        self.check_output()


class TestKVPageWrite(OpTest):
    def test_chunk_scatter_with_dead_rows(self):
        rng = np.random.RandomState(4)
        pool = rng.rand(5, 2, 2, 3).astype('f4')      # pt=2
        x = rng.rand(1, 4, 2, 3).astype('f4')         # C=4 chunk
        table = np.array([[3, 1]], 'int32')           # P=2
        positions = np.array([1, 2, 3, 4], 'int32')   # start=1
        length = np.array([3], 'int32')               # row 3 is padding
        expect = pool.copy()
        expect[3, 1] = x[0, 0]            # pos 1 -> page 3 off 1
        expect[1, 0] = x[0, 1]            # pos 2 -> page 1 off 0
        expect[1, 1] = x[0, 2]            # pos 3 -> page 1 off 1
        expect[0, 0] = x[0, 3]            # dead row -> null page
        self.op_type = 'kv_page_write'
        self.inputs = {'Pool': pool, 'X': x, 'Table': table,
                       'Positions': positions, 'Len': length}
        self.outputs = {'Out': expect}
        self.check_output()


class TestKVPageAppend(OpTest):
    def test_per_slot_append_and_null_redirect(self):
        rng = np.random.RandomState(5)
        pool = rng.rand(4, 2, 2, 2).astype('f4')
        x = rng.rand(3, 1, 2, 2).astype('f4')
        table = np.array([[2, 3], [0, 0], [1, 0]], 'int32')
        positions = np.array([3, 0, 1], 'int32')
        expect = pool.copy()
        expect[3, 1] = x[0, 0]            # slot 0: pos 3 -> page 3 off 1
        expect[0, 0] = x[1, 0]            # slot 1: idle -> null page
        expect[1, 1] = x[2, 0]            # slot 2: pos 1 -> page 1 off 1
        self.op_type = 'kv_page_append'
        self.inputs = {'Pool': pool, 'X': x, 'Table': table,
                       'Positions': positions}
        self.outputs = {'Out': expect}
        self.check_output()


class TestKVPageGather(OpTest):
    def test_table_order_assembly(self):
        rng = np.random.RandomState(6)
        pool = rng.rand(4, 2, 2, 2).astype('f4')
        table = np.array([[1, 3], [2, 0]], 'int32')
        expect = pool[table].reshape(2, 4, 2, 2)
        self.op_type = 'kv_page_gather'
        self.inputs = {'Pool': pool, 'Table': table}
        self.outputs = {'Out': expect}
        self.check_output()


class TestPagedDecodeMask(OpTest):
    def test_absolute_position_validity(self):
        x = np.zeros((2, 2, 1, 4), 'f4')
        positions = np.array([1, 3], 'int32')
        expect = np.full_like(x, -1e9)
        expect[0, :, :, :2] = 0.0         # j <= 1
        expect[1] = 0.0                   # j <= 3: everything
        self.op_type = 'paged_decode_mask'
        self.inputs = {'X': x, 'Positions': positions}
        self.outputs = {'Out': expect}
        self.check_output()


class TestPagedPrefillMask(OpTest):
    def test_causal_within_chunk(self):
        x = np.zeros((1, 1, 2, 4), 'f4')
        positions = np.array([1, 2], 'int32')
        expect = np.full_like(x, -1e9)
        expect[0, 0, 0, :2] = 0.0         # chunk row at pos 1
        expect[0, 0, 1, :3] = 0.0         # chunk row at pos 2
        self.op_type = 'paged_prefill_mask'
        self.inputs = {'X': x, 'Positions': positions}
        self.outputs = {'Out': expect}
        self.check_output()


# --------------------------------------------------------------------------
# shared tiny-LM predictors
# --------------------------------------------------------------------------

def _save_lm(tmp, cfg, seed):
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = seed
    with unique_name.guard(), program_guard(prog, startup):
        toks = fluid.layers.data(name='tokens',
                                 shape=[1, cfg.max_len, 1],
                                 dtype='int64', append_batch_size=False)
        logits = language_model_logits(toks, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp), ['tokens'], [logits],
                                      exe, main_program=prog)
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    return AnalysisPredictor(AnalysisConfig(str(tmp),
                                            place=fluid.CPUPlace()))


@pytest.fixture(scope='module')
def lm_predictor(tmp_path_factory):
    return _save_lm(tmp_path_factory.mktemp('paged_lm'), CFG, 7)


@pytest.fixture(scope='module')
def big_predictor(tmp_path_factory):
    return _save_lm(tmp_path_factory.mktemp('paged_big'), BIG, 11)


def _ref_step(pred, cfg, toks):
    feed = np.zeros((1, cfg.max_len, 1), np.int64)
    feed[0, :len(toks), 0] = toks
    lg = pred.run({'tokens': feed})[0]
    return lg[0, len(toks) - 1]


# --------------------------------------------------------------------------
# bit-exact parity: paged vs full recompute, compile-once
# --------------------------------------------------------------------------

def test_paged_parity_bit_exact_and_compiles_once(lm_predictor):
    paged = lm_predictor.prepare_decoding(slots=3,
                                          page_tokens=4,
                                          prefill_chunk=CFG.max_len)
    prompt = [3, 1, 4, 1, 5]
    pids, plg = paged.prefill([prompt], [1], return_logits=True)
    assert np.array_equal(plg[0], _ref_step(lm_predictor, CFG, prompt))
    tok, pos = int(pids[0]), len(prompt)
    toks = np.zeros((3,), np.int64)
    poss = np.zeros((3,), np.int32)
    stream = [tok]
    for _ in range(CFG.max_len - len(prompt)):
        toks[1], poss[1] = tok, pos
        pn, pl = paged.decode_step(toks, poss, return_logits=True)
        assert np.array_equal(
            pl[1], _ref_step(lm_predictor, CFG, prompt + stream)), \
            'paged decode step %d diverges from recompute' % len(stream)
        tok = int(pn[1])
        stream.append(tok)
        pos += 1
    # ONE compiled program per phase across the whole loop — page
    # tables, COW pairs and positions are feeds, never recompiles
    # (the third is the page copy program, compiled with the decode one)
    stats = paged.jit_cache_stats()
    assert stats['prepared_programs'] == 3
    assert stats['compiled_segments'] == 3


def test_chunked_prefill_matches_whole_prompt(lm_predictor):
    whole = lm_predictor.prepare_decoding(slots=2,
                                          page_tokens=4,
                                          prefill_chunk=CFG.max_len)
    chunked = lm_predictor.prepare_decoding(slots=2,
                                            page_tokens=4,
                                            prefill_chunk=4)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9]      # 13 tokens
    wi, wl = whole.prefill([prompt], [0], return_logits=True)
    chunked.open_stream(0, prompt)
    steps, out = 0, None
    while out is None:
        out = chunked.prefill_step(0, return_logits=True)
        steps += 1
    assert steps == 4                     # ceil(13 / 4) chunks
    ci, cl = out
    assert int(ci) == int(wi[0])
    assert np.array_equal(cl, wl[0])


def test_cow_streams_never_cross_talk(lm_predictor):
    paged = lm_predictor.prepare_decoding(slots=2,
                                          page_tokens=4,
                                          prefill_chunk=CFG.max_len)
    prompt = [7, 3, 7, 4, 2, 9]
    n = 6
    # the isolated reference: the same stream alone, cold, on a pool of
    # its own that fits it
    ref_a = lm_predictor.prepare_decoding(
        slots=1, page_tokens=4, kv_pages=8,
        prefill_chunk=CFG.max_len).generate(prompt, n)
    # stream A prefills cold (registers the prefix), stream B adopts
    # the shared page and both decode interleaved — divergent appends
    # COW-fork, so A's tokens must stay exactly its isolated stream
    ida = paged.prefill([prompt], [0])
    b = paged.open_stream(1, prompt)
    assert b['shared_tokens'] == 4        # one full page; tail recomputed
    idb = paged.prefill_step(1)
    assert int(idb) == int(ida[0])        # same prompt, same first token
    toks = np.array([int(ida[0]), int(idb)], np.int64)
    poss = np.array([len(prompt), len(prompt)], np.int32)
    out_a, out_b = [int(ida[0])], [int(idb)]
    for _ in range(n - 1):
        ids = paged.decode_step(toks, poss)
        out_a.append(int(ids[0]))
        out_b.append(int(ids[1]))
        toks = np.asarray(ids, np.int64)
        poss += 1
    assert out_a == ref_a and out_b == ref_a


# --------------------------------------------------------------------------
# typed exhaustion (COVERAGE divergence 8)
# --------------------------------------------------------------------------

def test_generate_past_window_raises_typed_not_slides(lm_predictor):
    # a stream that outgrows its window never slides silently: the
    # typed, retryable error
    paged = lm_predictor.prepare_decoding(slots=1,
                                          page_tokens=4,
                                          prefill_chunk=CFG.max_len)
    with pytest.raises(CacheExhaustedError) as ei:
        paged.generate([5, 9, 2], CFG.max_len + 6)
    assert ei.value.slots == (0,)
    assert ei.value.retryable
    from paddle_tpu.serving.replica import _retryable
    assert _retryable(ei.value)           # sheds, not stream-fatal


def test_decode_exhaustion_rolls_back_and_retries(lm_predictor):
    # 2 streams compete for a pool that can only grow one of them:
    # the step must run NOTHING, name the victim, leave the survivor's
    # state untouched, and succeed bit-exact after a release
    paged = lm_predictor.prepare_decoding(slots=2,
                                          page_tokens=4, kv_pages=6,
                                          prefill_chunk=CFG.max_len)
    pa = [1, 2, 3, 4, 5, 6, 7, 8]         # 2 full pages each
    pb = [8, 7, 6, 5, 4, 3, 2, 1]
    ida = paged.prefill([pa], [0])
    idb = paged.prefill([pb], [1])
    in_use = paged.pool_stats()['pages_in_use']
    toks = np.array([int(ida[0]), int(idb[0])], np.int64)
    poss = np.array([8, 8], np.int32)     # both need a 3rd page; 1 left
    with pytest.raises(CacheExhaustedError) as ei:
        paged.decode_step(toks, poss)
    assert len(ei.value.slots) == 1
    assert paged.pool_stats()['pages_in_use'] == in_use   # rolled back
    victim = ei.value.slots[0]
    survivor = 1 - victim
    paged.release(victim)
    ids = paged.decode_step(toks, poss)   # identical feed now succeeds
    ref = _ref_step(lm_predictor, CFG,
                    (pa if survivor == 0 else pb) + [int(toks[survivor])])
    assert int(ids[survivor]) == int(np.argmax(ref))


# --------------------------------------------------------------------------
# 512-token shared system prompt: suffix-only prefill, end to end
# --------------------------------------------------------------------------

def test_shared_system_prompt_prefills_suffix_only(big_predictor):
    from paddle_tpu.serving import ServingEngine
    dec = big_predictor.prepare_decoding(slots=2,
                                         page_tokens=32,
                                         prefill_chunk=128)
    rng = np.random.RandomState(13)
    sysp = list(rng.randint(1, BIG.vocab, 512))
    a = dec.open_stream(0, sysp + [5, 3])
    assert a['shared_tokens'] == 0 and a['chunks'] == 5   # cold: 514/128
    while dec.prefill_step(0) is None:
        pass
    b = dec.open_stream(1, sysp + [7, 1])
    assert b['shared_tokens'] == 512      # 16 pages adopted read-only
    assert b['chunks'] == 1
    warm = dec.prefill_step(1, return_logits=True)
    assert warm is not None               # ONE chunk covered the suffix
    st = dec.pool_stats()
    assert st['prefix_hits'] == 1 and st['prefix_tokens_reused'] == 512
    # bit-exactness at scale: the warm stream's first token + logits
    # equal its own cold prefill (fresh pool, no prefix cache)
    dec.release(0)
    dec.release(1)
    dec.reset()
    dec.open_stream(1, sysp + [7, 1])
    cold = None
    while cold is None:
        cold = dec.prefill_step(1, return_logits=True)
    assert int(warm[0]) == int(cold[0])
    assert np.array_equal(warm[1], cold[1])
    # engine end to end: second submission reuses the first's pages
    dec.reset()
    with ServingEngine(dec) as eng:
        ra = eng.submit(sysp + [5, 3], max_new_tokens=3)
        ra.result(600)
        rb = eng.submit(sysp + [7, 1], max_new_tokens=3)
        rb.result(600)
        kv = eng.stats()['kv']
    assert kv['prefix_hits'] == 1
    assert kv['prefix_tokens_reused'] == 512


# --------------------------------------------------------------------------
# telemetry + stats plumbing
# --------------------------------------------------------------------------

def test_paged_telemetry_counters_and_gauges(lm_predictor):
    from paddle_tpu.obs import telemetry
    telemetry.enable()
    telemetry.reset()
    try:
        dec = lm_predictor.prepare_decoding(slots=2,
                                            page_tokens=4,
                                            prefill_chunk=4)
        dec.prefill([[1, 2, 3, 4, 5, 6]], [0])        # 2 chunks
        dec.open_stream(1, [1, 2, 3, 4, 9])
        while dec.prefill_step(1) is None:
            pass
        snap = telemetry.snapshot()
        assert snap['gauges']['serving.kv_pages_in_use'] > 0
        assert snap['gauges']['serving.kv_pages_free'] > 0
        assert snap['counters']['serving.prefix_hits'] == 1
        assert snap['counters']['serving.prefix_tokens_reused'] == 4
        hist = snap['hists']['serving.prefill_chunks']
        assert hist['count'] == 2         # one observation per prompt
    finally:
        telemetry.disable(final_flush=False)
        telemetry.reset()


def test_lmserver_stats_expose_cache_pressure(lm_predictor):
    from paddle_tpu.serving import LMServer
    dec = lm_predictor.prepare_decoding(slots=2,
                                        page_tokens=4)
    srv = LMServer(dec)
    try:
        h = srv.submit([3, 1, 4], max_new_tokens=8)
        saw_tokens = 0
        deadline = time.time() + 30
        while time.time() < deadline:
            st = srv.stats()
            saw_tokens = max(saw_tokens, st['cache_tokens'])
            if srv.poll(h)['state'] not in ('QUEUED', 'RUNNING'):
                break
            time.sleep(0.001)
        srv.result(h, timeout=60)
        st = srv.stats()
        assert saw_tokens >= 3            # the live stream was visible
        assert st['cache_tokens'] == 0    # and released on completion
        assert st['cache_capacity'] == (dec.num_pages - 1) * 4
        assert isinstance(st['slot_tokens'], list)
        assert st['kv']['num_pages'] == dec.num_pages
    finally:
        srv.close()


def test_fleet_ingests_cache_pressure_and_sheds_exhaustion():
    from paddle_tpu.serving import fleet as fl
    router = fl.FleetRouter(['127.0.0.1:7001', '127.0.0.1:7002'])
    a = router._reps['127.0.0.1:7001']
    b = router._reps['127.0.0.1:7002']
    for rep in (a, b):
        rep.healthy = True
        rep.capacity = 4
    # equal lane load, hotter cache on a -> dispatch prefers b
    a.cache_tokens, a.cache_capacity = 90, 100
    b.cache_tokens, b.cache_capacity = 10, 100
    req = fl.FleetRequest([1, 2], 4, None, None)
    assert router._pick_locked(req) is b
    # a CacheExhausted FAILED poll is a shed with retry, not a failure
    req.state = fl.RUNNING
    a.active[req.id] = req
    router._apply_poll(a, req, {
        'state': fl.FAILED, 'tokens': [],
        'error': "RuntimeError('CacheExhaustedError: KV page pool "
                 "exhausted for slot(s) 0')"})
    assert req.state == fl.QUEUED and req.cache_sheds == 1
    assert router._hold and router._hold[req.priority][0] is req
    assert req.id not in a.active
    # the retry budget bounds saturation livelock: the 6th is fatal
    router._hold.clear()
    req.state = fl.RUNNING
    req.cache_sheds = 5
    a.active[req.id] = req
    router._apply_poll(a, req, {
        'state': fl.FAILED, 'tokens': [],
        'error': 'CacheExhaustedError: dry'})
    assert req.state == fl.FAILED
