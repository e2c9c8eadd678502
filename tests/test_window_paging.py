"""The second page table of a model with sliding layers, on the host
alone (serving/paging.py): a table with a window gives up exactly the
pages that lie wholly behind the next position's window and never a
shared page's last reference; the prefix cache keeps a window page
beside the full one, hands out a boundary only with its window tail
resident, falls back to the deepest boundary that has it, and its window
pool's eviction drops a window page alone. And the expert op's ReGLU."""
import numpy as np
import pytest

from paddle_tpu.serving.paging import (CacheExhaustedError, PagePool,
                                       PageTable, PrefixCache)

PT, WINDOW = 4, 8


def _stream(pool, wpool, n, cache=None, prompt=None, chunk=8):
    """A stream's two tables after a prompt of n tokens went through in
    chunks: grown, booked and slid as serving/paged.py does it."""
    table = PageTable(pool, 64)
    wtable = PageTable(wpool, 5, window=WINDOW)
    freed = 0
    for start in range(0, n, chunk):
        end = min(n, start + chunk)
        for t in (table, wtable):
            t.ensure(end)
            t.length = end
        if end == n and cache is not None:
            cache.register(prompt, table, wtable)
        freed += wtable.slide()
    return table, wtable, freed


@pytest.mark.parametrize('n', [1, 7, 8, 9, 11, 12, 13, 30, 37, 64])
def test_a_window_table_holds_the_window_and_no_more(n):
    pool, wpool = PagePool(40, PT), PagePool(12, PT)
    table, wtable, freed = _stream(pool, wpool, n)
    # the next row, at n, reads n - WINDOW + 1 .. n - 1
    first = max(0, n - WINDOW + 1) // PT
    assert wtable.first == first == freed
    assert wtable.base == first * PT
    assert len(wtable.pages) == -(-n // PT) - first
    assert wtable.index(n - 1) == len(wtable.pages) - 1
    assert wpool.pages_in_use == len(wtable.pages)
    assert len(table.pages) == -(-n // PT) and table.first == 0
    assert table.slide() == 0                   # no window: nothing goes
    wtable.release(), table.release()
    pool.check(), wpool.check()
    assert wpool.pages_in_use == 0 and wtable.first == 0


def test_decode_steps_slide_a_page_at_a_time_and_need_no_wider_table():
    wpool = PagePool(12, PT)
    wtable = PageTable(wpool, 3, window=WINDOW)     # ceil(8 / 4) + 1
    for pos in range(40):
        wtable.ensure(pos + 1)
        assert wtable.index(pos) < 3
        wtable.length = pos + 1
        assert wtable.slide() == (1 if pos >= WINDOW - 1
                                  and (pos - WINDOW + 2) % PT == 0 else 0)
    assert wtable.first == 8 and len(wtable.pages) == 2
    with pytest.raises(CacheExhaustedError):
        wtable.ensure(40 + 3 * PT)                  # past the table's width


def test_a_page_the_cache_also_holds_lives_on_there():
    pool, wpool = PagePool(40, PT), PagePool(12, PT)
    cache = PrefixCache(pool, window_pool=wpool, window=WINDOW)
    prompt = list(range(1, 31))
    table, wtable, _ = _stream(pool, wpool, 30, cache, prompt)
    # registered before the last slide: the pages the last chunk's first
    # row still read (from 24 - 7 = 17: page 4) and the tail
    held = {k: n.wpage for k, n in enumerate(
        cache._nodes[d] for d in cache._digests(prompt, 7))}
    assert [k for k, p in held.items() if p is not None] == [4, 5, 6]
    assert wtable.first == 5 and wtable.shared == {0, 1, 2}
    assert wpool.refcount(held[4]) == 1         # the stream's ref is gone
    assert wpool.refcount(held[5]) == 2
    # decode on: the stream's refs go page by page, the cache's stay
    for pos in range(30, 44):
        pair = wtable.cow_for_append(pos)
        if pair is not None:                    # the shared tail forks
            assert pos == 30
            wpool.unref(pair[0])
        wtable.ensure(pos + 1)
        wtable.length = pos + 1
        wtable.slide()
    assert wtable.first == 9
    assert all(wpool.refcount(held[k]) == 1 for k in (4, 5, 6))
    wtable.release(), table.release()
    pool.check(), wpool.check()
    assert wpool.pages_in_use == 4              # 4, 5, 6 and the tail


def test_match_hands_out_a_boundary_only_with_its_window_tail():
    pool, wpool = PagePool(60, PT), PagePool(20, PT)
    cache = PrefixCache(pool, window_pool=wpool, window=WINDOW)
    doc = list(range(100, 132))                 # 32 tokens: whole pages
    for t in _stream(pool, wpool, 32, cache, doc)[:2]:
        t.release()
    follow = doc + [7, 8, 9]
    pages, tokens, wpages, wfirst = cache.match_window(follow, len(follow) - 1)
    # a row at 32 reads from 25: pages 6 and 7
    assert tokens == 32 and len(pages) == 8 and wfirst == 6
    assert len(wpages) == 2
    assert (cache.hits, cache.window_tail_misses) == (1, 0)
    # a prompt that ends inside the document opens on the deepest
    # boundary whose tail is there: 28 needs pages 5, 6 (held: 4..7)
    pages, tokens, wpages, wfirst = cache.match_window(doc[:30] + [1], 30)
    assert tokens == 28 and wfirst == 5 and len(wpages) == 2
    # ... and none under 24 has one: 20 needs page 3, long given up
    assert cache.match_window(doc[:22], 21) == ([], 0, [], 0)
    assert cache.window_tail_misses == 1 and cache.misses == 1
    # the window pool's eviction takes window pages alone, the least
    # recently matched first: page 7, which the shorter prompt passed by
    nodes = [cache._nodes[d] for d in cache._digests(doc, 8)]
    assert cache.evict_window_one() and nodes[7].wpage is None
    assert all(n.page is not None for n in nodes) and len(cache) == 8
    assert [n.wpage is not None for n in nodes[4:]] == [True] * 3 + [False]
    # 32 lost its tail; 28 (pages 5, 6) still has its own
    assert cache.match_window(follow, len(follow) - 1)[1] == 28
    assert cache.window_tail_misses == 2
    for _ in range(3):
        assert cache.evict_window_one()
    assert not cache.evict_window_one() and wpool.pages_in_use == 0
    assert cache.match_window(follow, len(follow) - 1) == ([], 0, [], 0)
    # the full pool's eviction takes an entry with whatever it holds
    while cache.evict_one():
        pass
    pool.check(), wpool.check()
    assert pool.pages_in_use == 0


@pytest.mark.parametrize('windowed', [False, True])
@pytest.mark.parametrize('more', [1, 2, PT, 3 * PT + 1])
def test_a_tail_connects_however_far_the_prompt_goes_on(windowed, more):
    """One rule for match and match_window: where the resident run of
    whole pages ends, the longest registered tail the prompt goes on
    with, whether the follow-up adds a token or pages."""
    pool, wpool = PagePool(60, PT), PagePool(20, PT)
    cache = PrefixCache(pool, window_pool=wpool, window=WINDOW) \
        if windowed else PrefixCache(pool)
    parent = list(range(100, 130))              # 7 pages and 2 tokens
    if windowed:
        tables = _stream(pool, wpool, 30, cache, parent)[:2]
    else:
        table = PageTable(pool, 64)
        table.ensure(30)
        table.length = 30
        cache.register(parent, table)
        tables = [table]
    for t in tables:
        t.release()
    follow = parent + list(range(1, more + 1))
    got = cache.match_window(follow, len(follow) - 1) if windowed \
        else cache.match(follow, len(follow) - 1)
    assert got[1] == 30 and len(got[0]) == 8
    # another continuation inside the tail's page does not take it
    other = parent[:29] + [5] * (more + 1)
    got = cache.match_window(other, len(other) - 1) if windowed \
        else cache.match(other, len(other) - 1)
    assert got[1] == 28


def test_a_second_stream_gives_an_entry_the_window_page_it_lost():
    pool, wpool = PagePool(60, PT), PagePool(20, PT)
    cache = PrefixCache(pool, window_pool=wpool, window=WINDOW)
    doc = list(range(100, 132))
    for t in _stream(pool, wpool, 32, cache, doc)[:2]:
        t.release()
    while cache.evict_window_one():
        pass
    table, wtable, _ = _stream(pool, wpool, 32, cache, doc)
    assert cache.match_window(doc + [1, 2], 33)[1] == 32
    # its own full pages are not the cache's (those came first): private
    assert table.shared == set() and wtable.shared == {0, 1}
    wtable.release(), table.release()
    pool.check(), wpool.check()


@pytest.mark.parametrize('act', ['silu', 'relu'])
def test_gated_experts_take_their_activation(act):
    """op moe_experts' three-matrix form: W2 (act(W1 l) * W3 l), silu
    where no `act` is given, against the plain formula; the two differ."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops
    rng = np.random.default_rng(3)
    lat = rng.standard_normal((5, 16)).astype('f4')
    w = np.abs(rng.standard_normal((5, 4))).astype('f4')
    w1, w3 = (rng.standard_normal((4, 16, 24)).astype('f4') for _ in 'ab')
    w2 = rng.standard_normal((4, 24, 16)).astype('f4')
    fn = {'silu': jax.nn.silu, 'relu': jax.nn.relu}[act]
    want = sum(w[:, e:e + 1] * ((fn(lat @ w1[e]) * (lat @ w3[e])) @ w2[e])
               for e in range(4))
    got = moe_ops.held_gated_experts(lat, jnp.asarray(w), w1, w3, w2, act)
    assert np.allclose(got, want, rtol=1e-4, atol=1e-4)
    if act == 'silu':
        assert np.array_equal(
            got, moe_ops.held_gated_experts(lat, jnp.asarray(w), w1, w3, w2))
    else:
        other = moe_ops.held_gated_experts(lat, jnp.asarray(w), w1, w3, w2)
        assert np.abs(np.asarray(got) - np.asarray(other)).max() > 0.1
