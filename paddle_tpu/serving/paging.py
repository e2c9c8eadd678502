"""Host-side paged KV-cache bookkeeping: PagePool / PageTable /
PrefixCache.

The device side (ops/attention_ops.py kv_page_* ops, the paged program
pair from models/transformer.py) is pure address arithmetic over feed
values; everything stateful lives HERE, on the host, in plain Python:

  PagePool     free list + per-page refcounts over the physical pool.
               Physical page 0 is the reserved null page (never
               allocated, the redirect target for dead writes). An
               empty free list first asks the eviction callback (the
               PrefixCache LRU) to give a page back, then raises the
               typed, retryable CacheExhaustedError (COVERAGE
               divergence 8: never a silent slide).
               save_pages/restore_pages move page contents device<->
               host for the preempt-first capacity engine
               (serving/preempt.py): float32 copies onto freshly
               allocated pages, so a swapped-out stream resumes
               bit-exact.
  PageTable    one stream's logical -> physical mapping. Pages adopted
               from the prefix cache are marked SHARED; the first
               append into a shared page forks it (copy-on-write): a
               fresh page is allocated, a (src, dst) copy instruction
               is returned for the device program, and the shared ref
               is dropped. Because the device copy reads all sources
               before writing any destination, a page freed and
               reallocated within the same step still copies its
               pre-step contents.
               A table with a `window` is a sliding layer's: a SLIDING
               LIST over a pool of its own. pages[j] is logical page
               first + j; once a chunk or a step is booked, every page
               that lies wholly behind the next position's window is
               given up (unref: one the prefix cache also holds lives
               on there) and `first` moves up, so the table never holds
               more than the window and a chunk. What the device is fed
               is the list as it stands and positions counted from its
               first page (`base`): the kernels see a table and
               positions, as they do for a full layer.
  PrefixCache  content-hash chain over FULL pages (h_k = sha1(h_{k-1}
               || tokens of page k) -> physical page) plus
               partial-tail entries keyed by (chain hash, tail tokens)
               — RadixAttention-style sharing restricted to page
               granularity. The cache holds its own +1 ref on every
               registered page so shared prefixes survive stream
               churn; entries are evicted leaf-first by LRU when the
               pool runs dry. A page number may index more pools than
               the K/V ones (a model whose recurrent layers keep their
               rows by the page, models/lfm2.py): the cache neither
               knows nor cares, a page it hands out carries whatever
               every pool holds under its number. For a model with
               sliding layers an entry
               may hold a page of the window pool beside its page of
               the full pool: what the registering stream still held,
               the last `window` tokens' pages. A boundary is handed
               out only with the window pages that cover its last
               `window` tokens (match_window); the window pool's
               eviction drops an entry's window page alone.

Sharing is capped at prompt[:-1]: the last prompt token is always
recomputed, because its logits produce the stream's first output
token. Everything here is deterministic — no clocks, no randomness —
so greedy decode over shared pages stays bit-exact with the same
stream prefilled cold.
"""
from __future__ import annotations

import collections
import hashlib

import numpy as np

__all__ = ['CacheExhaustedError', 'PagePool', 'PageTable', 'PrefixCache',
           'chain_keys']

NULL_PAGE = 0


class CacheExhaustedError(RuntimeError):
    """The page pool is empty (after prefix-cache eviction): the stream
    cannot grow. Retryable — a shed, not a model error: the serving
    engine requeues the victim and the fleet router retries it on a
    less-loaded replica (replica.py already marks RuntimeError
    subclasses retryable on the wire)."""

    retryable = True

    def __init__(self, msg, slots=()):
        super(CacheExhaustedError, self).__init__(msg)
        self.slots = tuple(slots)


class PagePool(object):
    """Refcounted free-list allocator over `num_pages` physical pages.

    Page 0 is pinned as the null page and never handed out. `evict` is
    an optional zero-arg callable returning True if it released at
    least one page (the PrefixCache's LRU drop) — alloc() keeps asking
    it until a page frees or it gives up."""

    def __init__(self, num_pages, page_tokens, evict=None):
        num_pages = int(num_pages)
        if num_pages < 2:
            raise ValueError('page pool needs >= 2 pages (one is the '
                             'reserved null page), got %d' % num_pages)
        self.num_pages = num_pages
        self.page_tokens = int(page_tokens)
        self._free = collections.deque(range(1, num_pages))
        self._ref = [0] * num_pages
        self._ref[NULL_PAGE] = 1            # pinned forever
        self._evict = evict

    def set_evict(self, evict):
        self._evict = evict

    # -- accounting --------------------------------------------------------
    @property
    def pages_free(self):
        return len(self._free)

    @property
    def pages_in_use(self):
        return self.num_pages - 1 - len(self._free)

    def refcount(self, page):
        return self._ref[page]

    def check(self):
        """Invariant sweep (the property test's oracle): the free list
        and the ref>0 set partition pages 1..N-1 exactly."""
        free = set(self._free)
        assert len(free) == len(self._free), 'free list holds duplicates'
        assert NULL_PAGE not in free, 'null page leaked into free list'
        assert self._ref[NULL_PAGE] >= 1, 'null page pin lost'
        for p in range(1, self.num_pages):
            assert self._ref[p] >= 0, 'negative refcount on page %d' % p
            assert (self._ref[p] == 0) == (p in free), \
                'page %d: ref %d but free=%s' % (p, self._ref[p], p in free)

    # -- alloc / ref -------------------------------------------------------
    def alloc(self):
        while not self._free:
            if self._evict is None or not self._evict():
                raise CacheExhaustedError(
                    'KV page pool exhausted: %d pages all referenced '
                    '(and no prefix-cache entry left to evict)'
                    % (self.num_pages - 1))
        page = self._free.popleft()
        self._ref[page] = 1
        return page

    def alloc_many(self, n):
        """All-or-nothing batch alloc: returns n pages or raises with
        none taken (so a failed admission never strands pages)."""
        out = []
        try:
            for _ in range(int(n)):
                out.append(self.alloc())
        except CacheExhaustedError:
            for p in out:
                self.unref(p)
            raise
        return out

    def share(self, page):
        if page == NULL_PAGE or self._ref[page] <= 0:
            raise ValueError('cannot share dead page %d' % page)
        self._ref[page] += 1
        return page

    def unref(self, page):
        if page == NULL_PAGE:
            raise ValueError('cannot unref the null page')
        if self._ref[page] <= 0:
            raise ValueError('double free of page %d' % page)
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free.append(page)

    # -- host swap (preempt-first capacity, serving/preempt.py) ------------
    def save_pages(self, pools, page_ids):
        """Device -> host: gather the `page_ids` rows of every pool
        array into float32 host copies (one np.ndarray per pool, shape
        [len(page_ids), page_tokens, ...]). A pure read — refcounts and
        the free list are untouched; the caller releases the stream's
        refs AFTER the copy so a failed gather never strands a page.
        Float32 bytes copy exactly, so a later restore_pages is
        bit-identical."""
        idx = [int(p) for p in page_ids]
        for p in idx:
            if p == NULL_PAGE or not 0 < p < self.num_pages \
                    or self._ref[p] <= 0:
                raise ValueError('cannot save dead/null page %d' % p)
        idx = np.asarray(idx, np.int32)
        return [np.asarray(pool[idx]) for pool in pools]

    def restore_pages(self, pools, saved):
        """Host -> device: allocate len(saved[0]) FRESH pages
        (all-or-nothing — raises the retryable CacheExhaustedError
        with nothing taken when the pool cannot fit, so a resuming
        stream just stays queued) and write each saved row back at the
        new physical ids. Returns (page_ids, pools); device-resident
        pools are functionally updated (`.at[ids].set`), so the caller
        must reinstall the returned arrays in its scope."""
        n = len(saved[0]) if saved else 0
        ids = self.alloc_many(n)
        idx = np.asarray(ids, np.int32)
        out = []
        for pool, host in zip(pools, saved):
            if hasattr(pool, 'at'):            # jax array: functional
                pool = pool.at[idx].set(host)
            else:                              # numpy: in-place
                pool[idx] = host
            out.append(pool)
        return ids, out


class PageTable(object):
    """One stream's page index: logical position j lives at
    pages[j // page_tokens - first] offset j % page_tokens. `shared`
    marks table indices whose page is referenced elsewhere (prefix cache
    or another stream) and therefore read-only for this stream. `first`
    is 0 and stays 0 unless the table has a `window` (the sliding list
    of the module docstring): slide() then gives up the pages behind
    the window and counts `first` up."""

    def __init__(self, pool, width, window=0):
        self.pool = pool
        self.width = int(width)             # table entries (P)
        self.window = int(window)           # tokens a row sees; 0: all
        self.pages = []                     # physical page ids
        self.first = 0                      # logical page of pages[0]
        self.length = 0                     # tokens written so far
        self.shared = set()                 # read-only table indices

    @property
    def capacity(self):
        return self.width * self.pool.page_tokens

    @property
    def base(self):
        """The position of the table's first row: what a sliding
        layer's positions are counted from."""
        return self.first * self.pool.page_tokens

    def index(self, position):
        """The table index of the page that holds `position`."""
        return int(position) // self.pool.page_tokens - self.first

    def adopt_shared(self, pages, tokens, first=0):
        """Seed a fresh table with prefix-cache pages (the cache's own
        refs are untouched; this stream takes one more each); `first`,
        the logical page of pages[0] (a window's tail)."""
        assert not self.pages and not self.length
        if tokens > (first + len(pages)) * self.pool.page_tokens:
            raise ValueError('shared prefix %d tokens > %d pages'
                             % (tokens, first + len(pages)))
        self.first = int(first)
        for p in pages:
            self.pool.share(p)
            self.shared.add(len(self.pages))
            self.pages.append(p)
        self.length = int(tokens)

    def mark_shared(self, index):
        self.shared.add(int(index))

    def ensure(self, tokens):
        """Grow the table so positions [0, tokens) are addressable.
        All-or-nothing; raises CacheExhaustedError past `width` pages
        or an empty pool. Idempotent for already-covered extents."""
        tokens = int(tokens)
        need = -(-tokens // self.pool.page_tokens) - self.first   # ceil
        if need > self.width:
            raise CacheExhaustedError(
                'stream needs %d pages, table width is %d (%d-token '
                'window)' % (need, self.width, self.capacity))
        if need > len(self.pages):
            self.pages.extend(self.pool.alloc_many(need - len(self.pages)))

    def cow_for_append(self, position):
        """Make the page holding `position` writable. Returns a
        (src, dst) physical copy pair for the device program when the
        page was shared and had to fork, else None. This stream's ref
        on src is deliberately NOT dropped here: the caller unrefs it
        only AFTER the device copy actually ran, so a step that fails
        after this fork can roll back (restore src, unref dst) without
        ever touching a freed page."""
        idx = self.index(position)
        if idx >= len(self.pages) or idx not in self.shared:
            return None
        dst = self.pool.alloc()
        src = self.pages[idx]
        self.pages[idx] = dst
        self.shared.discard(idx)
        return (src, dst)

    def row(self, out):
        """Fill `out` (a length-width int32 view) with the physical
        page ids, null-padded."""
        out[:] = NULL_PAGE
        out[:len(self.pages)] = self.pages
        return out

    def slide(self):
        """Give up every page that lies wholly behind the window of the
        next position (`length`: the rows 0..length - window of a
        stream are never read again) and return how many went; nothing
        without a window. Called once a chunk or a step is booked, so a
        call that raised has given up nothing."""
        if not self.window:
            return 0
        keep = max(0, self.length - self.window + 1) \
            // self.pool.page_tokens
        n = min(keep - self.first, len(self.pages))
        if n <= 0:
            return 0
        for p in self.pages[:n]:
            self.pool.unref(p)
        del self.pages[:n]
        self.first += n
        self.shared = {i - n for i in self.shared if i >= n}
        return n

    def release(self):
        for p in self.pages:
            self.pool.unref(p)
        self.pages = []
        self.shared = set()
        self.length = self.first = 0


def _digest(prev, tokens):
    h = hashlib.sha1(prev)
    h.update(b','.join(b'%d' % int(t) for t in tokens))
    return h.digest()


def chain_keys(tokens, page_tokens, limit=None):
    """Hex hash-chain keys over the FULL pages of tokens[:limit] — the
    content address every disagg page ship and fleet prefix-directory
    entry is keyed by. A pure function of the tokens and the page size,
    so a receiver can recompute the chain and refuse a shipment whose
    keys do not match its own hash of the prompt."""
    pt = int(page_tokens)
    toks = [int(t) for t in tokens]
    limit = len(toks) if limit is None else min(int(limit), len(toks))
    out, chain = [], b''
    for k in range(limit // pt):
        chain = _digest(chain, toks[k * pt:(k + 1) * pt])
        out.append(chain.hex())
    return out


class _Node(object):
    __slots__ = ('page', 'wpage', 'parent', 'children', 'tails', 'stamp',
                 'ended')

    def __init__(self, page, parent):
        self.page = page
        self.ended = False       # a registered prompt ended on this page
        self.wpage = None        # its page of the window pool, if held
        self.parent = parent     # chain digest of the previous node
        self.children = 0
        self.tails = 0
        self.stamp = 0


class _Tail(object):
    __slots__ = ('page', 'wpage', 'tokens', 'chain', 'stamp')

    def __init__(self, page, tokens, chain):
        self.page = page
        self.wpage = None
        self.tokens = tokens
        self.chain = chain
        self.stamp = 0


class _Snap(object):
    """The recurrent state at one prefix boundary: `chain` is the digest
    of its full pages and `rest` the tokens on its tail page (so it
    needs the nodes up to `chain` and, with a rest, the tail entry
    (chain, rest)); `row` is where the state lies on the device. `pins`
    counts the streams that matched it and have not copied it yet: its
    row is not handed out again before they have. `reads` counts the
    streams that opened on it, and `passed` says that a prompt which ran
    over this boundary has registered a later one: with one reader at
    most that is a conversation which has moved on, and its row goes
    before any other (`spent`); a second reader makes it a shared
    prefix, and a shared prefix's row goes last."""
    __slots__ = ('row', 'chain', 'rest', 'tokens', 'stamp', 'pins', 'gone',
                 'reads', 'passed')

    def __init__(self, row, chain, rest, tokens):
        self.row, self.chain, self.rest = row, chain, rest
        self.tokens = tokens
        self.stamp = self.pins = self.reads = 0
        self.gone = self.passed = False

    @property
    def spent(self):
        return self.passed and self.reads <= 1


class PrefixCache(object):
    """Content-hash page index for shared prefixes.

    Full pages form a hash CHAIN (a radix tree collapsed to page
    granularity): node k is keyed by sha1 over all tokens of pages
    0..k and maps to the physical page holding page k's K/V. A prompt
    matches greedily along the chain; an optional partial TAIL entry
    (chain digest + the tail's exact tokens) shares the last,
    partially filled page — where the prompt's resident chain ends the
    matcher picks the longest registered tail that the prompt goes on
    with, however far it goes on after it. The cache owns one ref
    per registered page; evict_one() drops the least-recently-used
    LEAF (no children, no tails) so interior chain pages are never
    orphaned while still reachable."""

    def __init__(self, pool, snapshot_rows=0, window_pool=None, window=0,
                 block=0):
        self.pool = pool
        # a model that generates by diffusion over blocks of `block`
        # tokens: a token's K/V depends on the later tokens of its
        # block, so a prefix is handed out (match) and registered
        # (register) at boundaries that are whole blocks only. Every
        # whole page is one (the block divides a page); the partly
        # filled last page is what the two guards are for
        self.block = int(block)
        if self.block and pool.page_tokens % self.block:
            raise ValueError('pages of %d tokens do not hold whole blocks '
                             'of %d' % (pool.page_tokens, self.block))
        # a model with sliding layers: their pool, and the tokens a row
        # of theirs sees (match_window / register's second table)
        self.window_pool, self.window = window_pool, int(window)
        self.window_tail_misses = 0
        self._nodes = {}          # chain digest -> _Node
        self._tails = {}          # chain digest -> {tokens: _Tail}
        # recurrent state at prefix boundaries (match_state /
        # register_state; nothing here is touched without them)
        self._snaps = {}          # chain digest -> {rest tokens: _Snap}
        self._free_rows = list(range(int(snapshot_rows)))
        self.snapshots_dropped = 0
        self._clock = 0
        self.hits = 0
        self.misses = 0
        self.tokens_reused = 0
        # of them (match() alone counts it): tokens handed out at a
        # boundary of whole pages on which no registered prompt ended
        self.offprompt_tokens = 0
        # what the two evictions cost: entries their scans looked at,
        # and the calls that gave a ref up
        self.entries_scanned = 0
        self.evictions = 0
        # delta logs for the fleet prefix directory (drained through
        # SRV_HEALTH): hex chain keys of full-page nodes registered /
        # evicted since the last drain_events(). Bounded by cache
        # churn between probes — tails are never logged (the directory
        # tracks full pages only).
        self._announced = []
        self._evicted = []

    def _touch(self, entry):
        self._clock += 1
        entry.stamp = self._clock

    def _digests(self, tokens, full):
        """The chain digest behind each of the first `full` whole pages
        of `tokens`, in order: the one place the chain is hashed."""
        pt = self.pool.page_tokens
        chain = b''
        for k in range(full):
            chain = _digest(chain, tokens[k * pt:(k + 1) * pt])
            yield chain

    def _resident(self, tokens, full):
        """(digest, node) along the chain of the first `full` whole
        pages of `tokens`, as far as it is resident. Because eviction
        is leaf-first, the resident part of a chain is a prefix of it."""
        for digest in self._digests(tokens, full):
            node = self._nodes.get(digest)
            if node is None:
                return
            yield digest, node

    def _longest_tail(self, chain, prompt, at, limit):
        """The longest registered tail behind `chain`, the resident run
        of whole pages that ends at token `at`, that prompt[at:limit]
        goes on with, however far it goes on after it; or None. A tail
        is less than a page, so no more of the prompt is looked at."""
        tails = self._tails.get(chain)
        if not tails:
            return None
        rest = tuple(int(t) for t in prompt[at:min(
            limit, at + self.pool.page_tokens - 1)])
        best = None
        for tail_tokens, tail in tails.items():
            if self.block and len(tail_tokens) % self.block:
                continue        # no boundary of whole blocks
            if rest[:len(tail_tokens)] == tail_tokens and \
                    (best is None or len(tail_tokens) > len(best.tokens)):
                best = tail
        return best

    # -- lookup ------------------------------------------------------------
    def match(self, prompt, limit=None):
        """Longest shared prefix of `prompt` (at most `limit` tokens;
        callers pass len(prompt) - 1 so the last token is always
        computed). Returns (pages, tokens): the physical pages to adopt
        (the last may be partial) and how many tokens they carry. The
        caller must adopt_shared() them promptly — match() itself takes
        no refs. The match is the resident run of the chain of whole
        pages and, where that run ends, the longest registered tail
        whose tokens the prompt goes on with. Tokens handed out at a
        boundary of whole pages on which no registered prompt ended add
        to `offprompt_tokens`."""
        pt = self.pool.page_tokens
        limit = len(prompt) if limit is None else min(limit, len(prompt))
        if self.block:
            limit -= limit % self.block
        full = limit // pt
        pages, chain, ended = [], b'', True
        for chain, node in self._resident(prompt, full):
            self._touch(node)
            pages.append(node.page)
            ended = node.ended
        k = len(pages)
        tokens = k * pt
        best = self._longest_tail(chain, prompt, tokens, limit)
        if best is not None:
            self._touch(best)
            pages.append(best.page)
            tokens += len(best.tokens)
            ended = True
        if tokens:
            self.hits += 1
            self.tokens_reused += tokens
            if not ended:
                self.offprompt_tokens += tokens
        elif limit > 0:
            # a shareable prompt found nothing — the miss half of the
            # fleet_prefix_hit_rate metric (a 1-token prompt, limit 0,
            # can never share and counts as neither)
            self.misses += 1
        return pages, tokens

    def match_window(self, prompt, limit=None):
        """match() where a prefix is pages of two pools (the same run
        of whole pages and the same tail where it ends): the deepest
        boundary under `limit` whose last `window` tokens' pages are
        resident in the window pool too (a row at the boundary reads
        the positions boundary - window + 1 .. boundary - 1 of a
        sliding layer; the rows before them it never reads, so their
        window pages may be long gone). Returns (pages, tokens, wpages,
        wfirst): the full pool's pages from logical page 0, and the
        window pool's from logical page `wfirst`. A boundary whose
        window tail is not resident is passed over for the deepest one
        that has it (window_tail_misses counts the match); nothing
        where none has."""
        pt = self.pool.page_tokens
        limit = len(prompt) if limit is None else min(limit, len(prompt))
        full = limit // pt
        nodes, chain = [], b''
        for chain, node in self._resident(prompt, full):
            nodes.append(node)
        entries, tokens = list(nodes), len(nodes) * pt
        best = self._longest_tail(chain, prompt, tokens, limit)
        if best is not None:
            entries.append(best)
            tokens += len(best.tokens)
        resident = bool(entries)
        # the run of entries with a window page that ends at each one
        run, runs = 0, []
        for e in entries:
            run = run + 1 if e.wpage is not None else 0
            runs.append(run)
        while entries:
            wfirst = max(0, tokens - self.window + 1) // pt
            if runs[len(entries) - 1] >= len(entries) - wfirst:
                break
            entries.pop()
            tokens = len(entries) * pt
        if resident and len(entries) < len(runs):
            self.window_tail_misses += 1
        if not entries:
            if limit > 0:
                self.misses += 1
            return [], 0, [], 0
        for e in entries:
            self._touch(e)
        self.hits += 1
        self.tokens_reused += tokens
        return ([e.page for e in entries], tokens,
                [e.wpage for e in entries[wfirst:]], wfirst)

    def chain(self, prompt, limit=None):
        """Walk the FULL-page hash chain registered for prompt[:limit]
        (no hit/LRU accounting — a pure read for the disagg shipper
        and directory). Returns (digests, pages): the longest resident
        leading run. Because eviction is leaf-first, the resident part
        of a chain is always a prefix of it."""
        pt = self.pool.page_tokens
        toks = [int(t) for t in prompt]
        limit = len(toks) if limit is None else min(int(limit), len(toks))
        digests, pages = [], []
        for digest, node in self._resident(toks, limit // pt):
            digests.append(digest)
            pages.append(node.page)
        return digests, pages

    def extend_chain(self, parent, digests, pages):
        """Graft externally prefilled full pages onto the chain at
        `parent` (b'' = the root): digests[i] hangs off digests[i-1].
        Each page arrives with the caller's fresh-alloc ref, which
        BECOMES the cache's ref (no extra share). A digest already
        present — a racing install — keeps the resident page and the
        duplicate ref is returned to the pool. The disagg install path
        (serving/disagg.py): shipped bytes were computed by the same
        deterministic prefill on the sender, so the content address
        guarantees byte-identical pages."""
        chain = parent
        for d, p in zip(digests, pages):
            node = self._nodes.get(d)
            if node is not None:
                self.pool.unref(p)
                self._touch(node)
                chain = d
                continue
            node = _Node(p, chain)
            self._nodes[d] = node
            par = self._nodes.get(chain)
            if par is not None:
                par.children += 1
            self._touch(node)
            self._announced.append(d.hex())
            chain = d

    # -- registration ------------------------------------------------------
    def _keep_window_page(self, entry, k, wtable):
        """Give `entry` (logical page k) the page of the window pool
        that `wtable` holds for it, if the table still holds one and
        the entry has none; the table's index is marked shared where
        the entry's page is (now) the table's."""
        idx = k - wtable.first
        if not 0 <= idx < len(wtable.pages):
            return
        if entry.wpage is None:
            entry.wpage = self.window_pool.share(wtable.pages[idx])
        if entry.wpage == wtable.pages[idx]:
            wtable.mark_shared(idx)

    def register(self, prompt, table, wtable=None):
        """Index a freshly prefilled prompt's pages for future sharing.
        Takes one cache ref per newly registered page and returns the
        TABLE indices that are now shared (the caller marks them so the
        stream's own appends fork instead of scribbling on cached
        pages). With `wtable`, the stream's table of its sliding
        layers: every entry along the prompt also takes the window page
        that the stream still holds for it (the last `window` tokens'
        pages), marked shared there in the same way."""
        pt = self.pool.page_tokens
        if self.block:
            prompt = prompt[:len(prompt) - len(prompt) % self.block]
        full = len(prompt) // pt
        chain = b''
        newly_shared = []
        for k, nxt in enumerate(self._digests(
                prompt, min(full, len(table.pages)))):
            node = self._nodes.get(nxt)
            if node is None:
                node = _Node(self.pool.share(table.pages[k]), chain)
                self._nodes[nxt] = node
                parent = self._nodes.get(chain)
                if parent is not None:
                    parent.children += 1
                newly_shared.append(k)
                self._announced.append(nxt.hex())
            elif node.page == table.pages[k]:
                newly_shared.append(k)       # already cache-shared
            if wtable is not None:
                self._keep_window_page(node, k, wtable)
            self._touch(node)
            chain = nxt
        rest = tuple(int(t) for t in prompt[full * pt:])
        if not rest and chain in self._nodes:
            self._nodes[chain].ended = True
        if rest and full < len(table.pages):
            tails = self._tails.setdefault(chain, {})
            if rest not in tails:
                tail = _Tail(self.pool.share(table.pages[full]),
                             rest, chain)
                tails[rest] = tail
                node = self._nodes.get(chain)
                if node is not None:
                    node.tails += 1
                newly_shared.append(full)
            elif tails[rest].page == table.pages[full]:
                newly_shared.append(full)
            if wtable is not None:
                self._keep_window_page(tails[rest], full, wtable)
            self._touch(tails[rest])
        for idx in newly_shared:
            table.mark_shared(idx)
        return newly_shared

    # -- pages and state (a model with recurrent layers) --------------------
    def match_state(self, prompt, limit=None):
        """match() where a prefix is pages AND state: the longest
        boundary under `limit` that has a snapshot and whose pages are
        all resident. Returns (pages, tokens, snapshot); the snapshot
        comes pinned, and the caller unpin()s it once its row is copied
        (or the stream is given up). (., 0, None) where nothing
        matches: pages that run past the newest surviving snapshot are
        never handed out."""
        pt = self.pool.page_tokens
        limit = len(prompt) if limit is None else min(limit, len(prompt))
        toks = [int(t) for t in prompt[:limit]]
        nodes = [node for _, node in self._resident(toks, limit // pt)]
        best = None
        for k, chain in enumerate(
                [b''] + list(self._digests(toks, len(nodes)))):
            for rest, snap in self._snaps.get(chain, {}).items():
                if tuple(toks[k * pt:k * pt + len(rest)]) == rest \
                        and (best is None or snap.tokens > best[0].tokens):
                    best = (snap, k)
        if best is None:
            if limit > 0:
                self.misses += 1
            return [], 0, None
        snap, k = best
        for node in nodes[:k]:
            self._touch(node)
        pages = [node.page for node in nodes[:k]]
        if snap.rest:
            tail = self._tails[snap.chain][snap.rest]
            self._touch(tail)
            pages.append(tail.page)
        self._touch(snap)
        snap.pins += 1
        snap.reads += 1
        self.hits += 1
        self.tokens_reused += snap.tokens
        return pages, snap.tokens, snap

    def unpin(self, snap):
        snap.pins -= 1
        if snap.gone and not snap.pins:
            self._free_rows.append(snap.row)

    def register_state(self, prompt, table):
        """register() where a prefix is pages AND state: index the
        prompt's pages and name the row that is to hold the recurrent
        state at exactly len(prompt) tokens. Returns that row (the
        caller copies the slot's state there, behind the chunk that
        ended the prompt), or None where nothing is to be copied: the
        boundary has its snapshot already, or every row is pinned, and
        then the pages are not registered either (never pages without
        their state). A full house gives up a snapshot with the pages
        that only it kept: the least recently used of those a
        conversation has moved on from (`_Snap.spent`: the boundary this
        prompt opened on becomes one here, unless other streams read it
        too), else the least recently used of those that fewer than two
        streams have opened on, else of all. So a session in progress
        holds one row and not the two that its last turns touched, a
        burst of turns does not take the boundary that a waiting
        session's next turn will open on, and single turns behind a
        system prompt, whose own ends nobody reads, take turns in the
        rows the system prompts leave them."""
        pt = self.pool.page_tokens
        full = len(prompt) // pt
        chains = [b''] + list(self._digests(prompt, full))
        chain = chains[-1]
        rest = tuple(int(t) for t in prompt[full * pt:])
        have = self._snaps.get(chain, {}).get(rest)
        if have is not None:
            self._touch(have)
            self.register(prompt, table)
            return None
        if not self._free_rows:
            idle = [s for at in self._snaps.values() for s in at.values()
                    if not s.pins]
            if not idle:
                return None
            self._drop_snap(
                min(idle, key=lambda s: (not s.spent, s.reads > 1, s.stamp)),
                release=True)
        self.register(prompt, table)
        # the nearest boundary this prompt ran over
        for k in range(full, -1, -1):
            over = [s for r, s in self._snaps.get(chains[k], {}).items()
                    if tuple(int(t) for t in prompt[k * pt:k * pt + len(r)])
                    == r]
            if over:
                max(over, key=lambda s: s.tokens).passed = True
                break
        snap = _Snap(self._free_rows.pop(), chain, rest, len(prompt))
        self._snaps.setdefault(chain, {})[rest] = snap
        self._touch(snap)
        return snap.row

    @property
    def snapshots(self):
        return sum(len(at) for at in self._snaps.values())

    def _drop_snap(self, snap, release):
        """Forget a snapshot; its row is free once no stream has it
        pinned. With `release`, the pages that no other snapshot can
        use go too: its tail, then its chain from the end up to the
        first node that has a child, a tail or a snapshot of its own."""
        del self._snaps[snap.chain][snap.rest]
        if not self._snaps[snap.chain]:
            del self._snaps[snap.chain]
        snap.gone = True
        self.snapshots_dropped += 1
        if not snap.pins:
            self._free_rows.append(snap.row)
        if not release:
            return
        if snap.rest:
            self._evict_entry('tail', (snap.chain, snap.rest),
                              self._tails[snap.chain][snap.rest])
        digest = snap.chain
        while digest in self._nodes and digest not in self._snaps:
            node = self._nodes[digest]
            if node.children or node.tails:
                break
            self._evict_entry('node', digest, node)
            digest = node.parent

    # -- eviction ----------------------------------------------------------
    def _leaves(self, leaves_only=True):
        """(stamp, (kind, key, entry)) of every entry that may go: the
        leaves, or every entry where `leaves_only` is off (a window page
        may go from the middle of a chain)."""
        for digest, node in self._nodes.items():
            if not leaves_only or not (node.children or node.tails):
                yield node.stamp, ('node', digest, node)
        for chain, tails in self._tails.items():
            for tokens, tail in tails.items():
                yield tail.stamp, ('tail', (chain, tokens), tail)

    def evict_one(self):
        """Drop the LRU leaf entry and unref its page; True if a page
        ref was released (it only FREES the page if no live stream
        still shares it — alloc() loops until one actually frees)."""
        self.entries_scanned += len(self)
        best = min(self._leaves(), default=None, key=lambda e: e[0])
        if best is None:
            return False
        self.evictions += 1
        _, (kind, key, entry) = best
        self._evict_entry(kind, key, entry)
        # a snapshot whose pages go is gone with them
        at = self._snaps.get(key if kind == 'node' else key[0], {})
        snap = at.get(() if kind == 'node' else key[1])
        if snap is not None:
            self._drop_snap(snap, release=False)
        return True

    def evict_window_one(self):
        """The window pool's eviction: the least recently used entry
        that holds a window page gives up that page alone (its page of
        the full pool stays; a boundary that needed the window page is
        no longer handed out). True if a ref was released."""
        self.entries_scanned += len(self)
        entry = min((e for _, (_, _, e) in self._leaves(leaves_only=False)
                     if e.wpage is not None),
                    default=None, key=lambda e: e.stamp)
        if entry is None:
            return False
        self.evictions += 1
        self.window_pool.unref(entry.wpage)
        entry.wpage = None
        return True

    def _evict_entry(self, kind, key, entry):
        if kind == 'node':
            del self._nodes[key]
            parent = self._nodes.get(entry.parent)
            if parent is not None:
                parent.children -= 1
            self._evicted.append(key.hex())
        else:
            chain, tokens = key
            del self._tails[chain][tokens]
            if not self._tails[chain]:
                del self._tails[chain]
            node = self._nodes.get(chain)
            if node is not None:
                node.tails -= 1
        self.pool.unref(entry.page)
        if entry.wpage is not None:
            self.window_pool.unref(entry.wpage)
            entry.wpage = None

    def drain_events(self):
        """Take (and clear) the registered/evicted delta since the last
        drain — the replica's SRV_HEALTH reply carries these so the
        router's prefix directory follows replica truth instead of
        guessing from dispatch history."""
        new, gone = self._announced, self._evicted
        self._announced, self._evicted = [], []
        return {'new': new, 'evicted': gone}

    @property
    def resident_pages(self):
        """Pages the cache itself holds a ref on (nodes + tails)."""
        return len(self)

    def __len__(self):
        return len(self._nodes) + sum(len(t) for t in self._tails.values())
