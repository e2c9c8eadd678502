"""PagedDecodePredictor: page-table cached decoding over a shared pool.

Scope layout:

    base Predictor Scope (weights, device-resident, shared)
        └── this predictor's child Scope (page pools, recurrent state)

Weights are pinned to device ONCE in the parent scope at construction;
every clone() gets a fresh child scope (private cache state, zeroed)
over the same parent, so N serving workers share one copy of the
weights in HBM — the reference PaddlePredictor::Clone contract extended
to runtime state. Per-layer [num_pages, page_tokens, H, dk] pools live
in the child Scope, a host-side PagePool/PrefixCache
(serving/paging.py) decides which physical page every logical position
maps to, and both compiled programs take the page index as a FEED —
admission, copy-on-write and prefix sharing never recompile anything:
each program is static-shape, compiles exactly once through the
executor's whole-block jit cache, and the pools ride the executor's
donation path (in-place update on device). A prefill chunk copies the
page it forks inside its program (a pair of feeds, null in most
calls). The decode program copies none: a decode step in which a page
forks (a stream's first append onto a page it shares: the host's own
table says so before anything is dispatched) runs the pair's page copy
program in front of its own (one kv_page_cow a pool, one dispatch); a
step without a fork, nearly every one, dispatches nothing for it. The
device runs programs in the order they were dispatched, so the append
that follows lands on the copy, also behind a deferred step in flight.
The copy program compiles where the decode program does, in a run over
null pairs in front of the predictor's first decode step (a warm-up
request's, like the other two programs' compiles): no later step's
fork meets its compile.

A stream's life:

    open_stream(slot, prompt)   match the prefix cache, adopt shared
                                pages read-only (zero recompute),
                                allocate nothing yet
    prefill_step(slot)          run ONE prefill_chunk-token chunk;
                                returns the first greedy token once the
                                prompt is complete (None before that);
                                with defer=True the last chunk returns
                                at once, with a handle on that token,
                                which stays on the device
    first_token(handle)         wait for such a token
    decode_step(tokens, pos)    one compiled step over ALL slots; pages
                                are allocated on demand per live stream;
                                with defer=True the step before's ids
                                come back instead of this step's, which
                                stay on the device (a one-deep pipeline);
                                a carried lane takes its token there,
                                from the step before or from its
                                prompt's deferred last chunk
    collect()                   the ids of the deferred step in flight
    block_step(tokens, starts, transfer)
                                in the place of decode_step for a model
                                that generates by diffusion over blocks
                                (`block_tokens` > 0: "Blocks" below):
                                one pass over a block of every lane
    release(slot)               drop the stream's page refs
    save_stream(slot)           copy the stream's pages to host RAM
                                (preempt-first capacity); paired with
    restore_stream(slot, snap)  write them back onto fresh pages and
                                resume bit-exact

Exhaustion is typed: when the pool runs dry (after prefix-cache LRU
eviction) prefill_step/decode_step raise CacheExhaustedError — a
stream never slides silently past its window (COVERAGE divergence 8).
decode_step is transactional: on exhaustion every page
allocated for THAT call is rolled back, so retrying the same feed
after a release is deterministic and bit-exact.

A model with recurrent layers (models/hybrid.py) keeps, beside the
pools, per-slot state that is not pages: `state_names` of the pair,
[slots, ...] arrays in the same child Scope, updated in place by both
programs. A stream's first chunk starts its slot from zero state by a
flag it feeds (no dispatch at open_stream); save_stream / restore_stream
carry the slot's rows with the pages. For such a model a prefix is
pages AND state: its pages without the recurrent state at that boundary
would be a wrong stream, so the prefix cache never hands out the one
without the other. How many boundaries keep their state is a size of
the deployment, `snapshot_rows`, beside `slots` and `kv_pages`. With 0
rows (the default) the cache hands out nothing and registers nothing
for such a model, and nothing below exists: no array, no program, no
dispatch. With rows, beside each state array lies a snapshot array
[snapshot_rows, ...] (38.7 MB a row at the Granite 4.0-H Small widths,
13 MB at the 7B hybrid's, against 128 KB or 1 MB a page): when a
prompt's last chunk is booked, the slot's state is copied to a row on
the device, behind that chunk (the pair's snapshot program), and the
cache's entry for exactly len(prompt) tokens names the row
(PrefixCache.register_state); open_stream matches the longest boundary
that has a snapshot and whose pages are resident (match_state), adopts
its pages as for any model, and the stream's first prefill_step copies
the row into the slot (the adopt program, reset flag 0) in front of its
chunk, which starts at `shared_tokens`, wherever in a page or a chunk
that is; a partly filled last page forks on the first append as pages
do. Rows are bounded and LRU, and a row goes before any other once the
one stream that opened on it has registered a later boundary (a
conversation that moved on; a second reader makes it a shared prefix
and plain LRU again), so a session in progress holds one row and not
the two its last turns touched; a row a stream has matched and not yet
copied is pinned. Both programs compile in front of the predictor's
first prefill chunk, so admission, adoption, snapshot and eviction
recompile nothing. Speculation, export_prefix / install_prefix and
mesh serving refuse recurrent state by name, as before, and the fleet's
prefix directory is told nothing of such a model's pages
(prefix_report, resident_keys).
A model with sliding layers (models/smallthinker.py: spec.window_layers)
keeps a stream's pages in TWO tables over two pools: the full layers'
as above, and the sliding layers' in a table with a window
(serving/paging.py: a sliding list) over a pool sized apart
(`window_pages`, beside `kv_pages`). Every place below that settles a
stream's table settles both: a chunk and a step fork and grow both,
feed both (the window table's rows as they stand, and the positions
counted from its first row), and, once booked, the window table gives up
the pages that lie wholly behind the next position's window; the prefix
cache keeps a window page beside the full one and hands out a boundary
only with its window tail (PrefixCache.match_window). What moves a
stream's pages as one table refuses such a model by name: speculation,
mesh serving, export_prefix / install_prefix, save_stream /
restore_stream (`swappable` is False: the engine re-prefills a stream it
preempts). A model without such layers takes none of this.
A model whose layers keep a latent page (models/axk1.py: one pool a
layer, [pages, page_tokens, row]) keeps nothing else for a stream, so
everything here that moves pages (the prefix cache with copy-on-write,
save_stream / restore_stream, export_prefix / install_prefix) serves
it as it serves K/V pages: each takes its sizes from the pools.
A model whose recurrent layers keep K-1 rows keeps them by the page
(models/lfm2.py: spec.page_state_layers, gated short convolutions whose
whole state is the last K-1 inputs, 16 KB a layer at the published
widths against 64 KB of K/V a page). Beside the K/V pools lies one pool
a such layer, [num_pages, K-1, C], indexed by the SAME page table:
entry p holds the rows at page p's fill point, written by whatever chunk
or step last touched the page (op short_conv's paged forms). A full
page, or one registered as a tail, is frozen, so its entry is the state
at exactly its boundary, and "a stream's state is pages" holds for this
family as for the latent page: the pools are among the pair's
`cache_names`, so allocation, copy-on-write (a chunk's kv_page_cow in
its program, the page copy program in front of a forking step),
save_stream / restore_stream, export_prefix / install_prefix and
eviction move them with the page of the same number, sizes taken from
the pools. Such a model has no `state_names`, so nothing of the
paragraph above exists for it: no per-slot array, no reset flag, no
snapshot rows, no adopt or snapshot program. open_stream asks the cache
the plain `match`: the longest resident chain of whole pages plus a
registered tail, whether or not a prompt ever ended there, which the
snapshot design cannot give. Speculation's verify program and mesh
serving refuse it by name (models/transformer.refuse_page_state: they
know a page as K and V heads alone).

Blocks. A model that generates by diffusion over blocks
(models/sdar_moe.py: spec.block_tokens = B) has no one-token decode
step: the pair's second program is a BLOCK step, B rows of every lane
at the block's positions, each row attending over the lane's pages up
to the end of its block. A lane's block is passed over several times at
the SAME positions (each denoising pass hands some masked rows their
argmax, on the device: op block_unmask) and once more with no row
masked, the commit. Both are the one program with one feed shape. The
cache contract this generalises from speculative.py's parked rows: a
pass writes the block's B K/V rows through the table at the block's
positions, AHEAD of `table.length`, where the next pass overwrites them;
only a commit moves `table.length` on, by B, and so only rows computed
from a block's final tokens ever become visible to later blocks (a
later block's rows attend to 0..its own end). A block's page is grown,
and a shared frontier page forked, ONCE, before the block's first pass
(B divides a page, a block never straddles two), and rolled back as
decode_step rolls back when the pool is dry. A prompt's WHOLE blocks
are prefilled under the same mask (a chunk holds whole blocks); its last
len % B tokens open the first generated block as fixed tokens, so the
last chunk has no first token to hand back: prefill_step ends with a
BlockStart (where the first block starts and what it opens with; at
once, with no program run, where the cache shares every whole block or
the prompt has none), which is not None in either form of the call. The
prefix cache hands out and registers boundaries of whole blocks only
(PrefixCache(block=B)). With the static rules the rows still masked
behind a pass follow from the schedule, so block_step defers like
decode_step (the ids stay on the device, `block_carry` lanes take them
there); with low_confidence_dynamic only the device knows, and the
engine steps such a predictor synchronously (`block_defers`). Such a
model is not swappable (a preempted stream re-prefills: its tokens so
far are whole blocks), and speculation, mesh serving and page shipping
refuse it by name (models/transformer.refuse_blocks).

Telemetry: serving.kv_pages_in_use / serving.kv_pages_free gauges,
serving.prefix_hits / serving.prefix_tokens_reused counters (beside
serving.prompt_tokens_admitted: the prompt tokens of every stream
opened, reused or not),
serving.prefill_chunks histogram (chunks per admitted prompt),
serving.decode_pages_read counter (the pages the decode steps'
attention read; attr `pages_read` of every `paged.decode.tables` span,
beside `overlapped`, 1 if the step was dispatched while the one before was in
flight, `carried`, the lanes whose token it took from that step on
the device, and `carried_prefill`, the lanes whose token it took from
their prompt's last chunk, dispatched in front of it and not waited
for; for a model with sliding layers also `rows_read` and
`window_rows_read`, the K/V rows a full layer's and a sliding layer's
attention has to read in the step);
serving.state_lanes counter (lanes whose recurrent state the decode
steps updated; attr `state_lanes` of the same span),
serving.state_chunk_tokens counter (prompt tokens the prefill chunks
carried through the recurrence's chunk form; attr `state_tokens` of
`paged.prefill.tables`),
serving.recurrent_state_bytes and serving.state_resets gauges (bytes
the recurrent state holds; streams started from zero state so far),
and serving.<family>.state_bytes for a spec that names its state's
family (serving.ssm.state_bytes: models/nemotron_h.py). With snapshot
rows: the counters serving.state.snapshots_taken, _adopted and
_evicted (an eviction for its row or with its pages) and the gauge
serving.state.snapshot_bytes (what the snapshot rows hold on the
device, all rows); serving.prefix_hits / prefix_tokens_reused count
such a model's streams as they count the others'. For a model
whose pages hold latent rows (models/axk1.py): the gauge
serving.latent.cache_bytes (pages in use x the bytes a page's rows
take over all layers) and the counter serving.latent.rows_read (rows
the decode steps' attention read: each live lane's pos + 1, every
layer; attr `latent_rows` of `paged.decode.tables`).
For a model whose recurrent layers keep their rows by the page: the
gauge serving.page_state.bytes (pages in use x the bytes a page's rows
take over all such layers), the counters serving.page_state.streams_adopted
(streams that opened on a cached page and so took their rows from it),
serving.page_state.rows_chunk / .rows_step (rows the chunks and the
steps wrote: page entries touched x K-1 x layers; attr `page_state_rows`
of `paged.prefill.tables` / `paged.decode.tables`). For every model that
asks the cache the plain `match`: the counter
serving.prefix.offprompt_tokens and the attr `offprompt_tokens` of
`paged.open` / `paged.prefix.match`, the reused tokens that came from a
boundary of whole pages where no prompt had ended.
For a model with sliding layers: the counter serving.window_pages_freed
(pages their tables gave up behind the window), the gauges
serving.window_pages_in_use (pages of the window pool handed out: open
streams' and the prefix cache's) and serving.window_pages_live (those
open streams hold), and the counters serving.prefix.window_tail_adopted
/ serving.prefix.window_tail_miss (a stream opened on a boundary with
its window tail; a deeper boundary was resident in the full pool and
passed over because its window tail was not).
Copy-on-write in front of a decode step: serving.cow.dispatches (decode
steps that forked a page and so ran the copy) and serving.cow.pages
(the pages they copied: the pairs that are not null). They count forks
only: a step without one moves neither, nor does the null run in
front of the first decode step, nor a prefill chunk's copy inside its
program.
What a model's expert layers count they count on the device: a
program with such layers returns a third fetch, [4] int32 a call. No
step waits for it: the arrays queue up and moe_counters() (any thread;
a window's edges, not the loop) sums those whose step has ended into
the counters serving.moe.pairs, serving.moe.experts_touched,
serving.moe.pairs_dropped (0: there is no capacity to overflow) and
serving.moe.layer_calls, each also as serving.moe.decode.* for the
decode program's share.

Spans (profiler.RecordEvent), the same four in each step:
`paged.decode.tables` / `paged.prefill.tables` (copy-on-write's
bookkeeping, page allocation, the page-table rows and the other feed
arrays), `paged.cow` (attr `pages`; its child is the copy program's own
`exe.run`) in a decode step that forked and in no other
(`paged.cow.compile` once, around the null run in front of the first
decode step),
the executor's `exe.run` with its children, `paged.*.book` (unref, lengths,
prefix registration, gauges: host work that overlaps the device's),
and `paged.*.fetch` (`np.asarray(ids)`: the wait for the device and
the transfer; in prefill only on a prompt's last chunk, inside
prefill_step, or inside first_token() for a chunk that was deferred).
`paged.carry` (attr `lanes`) in a decode step that takes a token from a
deferred last chunk, and in no other: the dispatch of the one small
executable that writes the chunk's id into the ids the step reads
(compiled inside `paged.cow.compile`, with the copy program). In a deferred
decode step the fetch is that of the step BEFORE, with this one already
queued behind it: the device's lead over the host, not a whole step's
time. collect() leaves a `paged.decode.fetch` alone. The seconds spent
blocked inside the fetch spans (the `np.asarray` of a step's ids, of a
prompt's last chunk's, of collect()'s) add up in the attribute
`fetch_wait_s`, cumulative since construction: the engine's loop reads
it before and after a pass for the pass's wait (`wait_ms` of
`serve.iter`, the counter serving.loop.wait_seconds).
What the prefix cache costs the host, where it happens (the cache
itself, serving/paging.py, imports no telemetry and keeps plain
integers: `hits`, `misses`, `entries_scanned`, `evictions`):
`paged.open` (attrs `prompt_tokens`, `shared_tokens`) around a stream's
opening, under the engine's `serve.admit`, with the child
`paged.prefix.match` (attrs `pages`: the whole pages hashed,
`shared_tokens`) around the one question its family asks the cache
(match / match_window / match_state; none for a model with recurrent
state and no snapshot rows): once a stream. `paged.prefix.register`
(attrs `tokens`, `pages`; `row` 0/1 where the model keeps snapshot
rows: one was handed out) around register / register_state, inside the
`paged.prefill.book` of a prompt's last chunk: once a prompt.
`paged.prefix.evict` (attrs `pool` 'full' | 'window', `scanned`: the
entries the call looked at, `freed` 0/1: it gave a ref up) around every
call a pool that ran dry makes of evict_one / evict_window_one, under
the `paged.prefill.tables` / `paged.decode.tables` that asked for the
page; the same adds up in the counters serving.prefix.evictions,
serving.prefix.entries_scanned and serving.prefix.evict_seconds, for a
deployment that runs longer than the span buffer holds. A step that
meets no arrival, no prompt's end and no dry pool opens none of them.
`paged.state.save` / `paged.state.restore` (attr `nbytes`) inside
save_stream / restore_stream: the recurrent rows' way to the host and
back. `paged.state.snapshot` / `paged.state.adopt` (attrs `nbytes`,
`tokens`: the boundary) around the dispatch of the copy between a slot
and a snapshot row; the copy itself is the device's.
"""
from __future__ import annotations

import collections
import threading
import time

import numpy as np

import jax

from ..executor import Executor, Scope
from ..flags import get_flag
from ..obs import telemetry
from ..profiler import RecordEvent
from .paging import (CacheExhaustedError, PagePool, PageTable, PrefixCache,
                     chain_keys)

__all__ = ['PagedDecodePredictor']

_pages_in_use = telemetry.gauge('serving.kv_pages_in_use')
_pages_free = telemetry.gauge('serving.kv_pages_free')
_prefix_hits = telemetry.counter('serving.prefix_hits')
_prefix_tokens = telemetry.counter('serving.prefix_tokens_reused')
_prompt_tokens = telemetry.counter('serving.prompt_tokens_admitted')
_prefill_chunks = telemetry.histogram('serving.prefill_chunks')
_decode_pages_read = telemetry.counter('serving.decode_pages_read')
_state_lanes = telemetry.counter('serving.state_lanes')
_state_chunk_tokens = telemetry.counter('serving.state_chunk_tokens')
_state_bytes = telemetry.gauge('serving.recurrent_state_bytes')
_state_resets = telemetry.gauge('serving.state_resets')
_latent_bytes = telemetry.gauge('serving.latent.cache_bytes')
_latent_rows = telemetry.counter('serving.latent.rows_read')
_cow_dispatches = telemetry.counter('serving.cow.dispatches')
_cow_pages = telemetry.counter('serving.cow.pages')
_snaps_taken = telemetry.counter('serving.state.snapshots_taken')
_snaps_adopted = telemetry.counter('serving.state.snapshots_adopted')
_snaps_evicted = telemetry.counter('serving.state.snapshots_evicted')
_snap_bytes = telemetry.gauge('serving.state.snapshot_bytes')
_window_freed = telemetry.counter('serving.window_pages_freed')
_window_in_use = telemetry.gauge('serving.window_pages_in_use')
_window_live = telemetry.gauge('serving.window_pages_live')
_window_tail_adopted = telemetry.counter('serving.prefix.window_tail_adopted')
_window_tail_miss = telemetry.counter('serving.prefix.window_tail_miss')
_prefix_evictions = telemetry.counter('serving.prefix.evictions')
_prefix_scanned = telemetry.counter('serving.prefix.entries_scanned')
_prefix_evict_seconds = telemetry.counter('serving.prefix.evict_seconds')
_page_state_bytes = telemetry.gauge('serving.page_state.bytes')
_page_state_adopted = telemetry.counter('serving.page_state.streams_adopted')
_page_state_rows_chunk = telemetry.counter('serving.page_state.rows_chunk')
_page_state_rows_step = telemetry.counter('serving.page_state.rows_step')
_prefix_offprompt = telemetry.counter('serving.prefix.offprompt_tokens')
_block_passes = telemetry.counter('serving.block.passes')
_block_commits = telemetry.counter('serving.block.commits')
_block_masked_rows = telemetry.counter('serving.block.masked_rows')
_block_rows = telemetry.counter('serving.block.rows')
_effective_tokens = telemetry.gauge('serving.effective_tokens_per_step')
_MOE_COUNTS = ('pairs', 'experts_touched', 'pairs_dropped', 'layer_calls')


def _set_row(state, slot, rows):
    """state with state[slot] = rows: functional for a device array (the
    caller reinstalls the result), in place for a host one."""
    if hasattr(state, 'at'):
        return state.at[slot].set(rows)
    state[slot] = rows
    return state


@jax.jit
def _with_first(prev, slot, ids):
    """The ids [slots] a decode step's carried lanes read, with lane
    `slot`'s entry taken from `ids` [1], a prompt's last chunk's: all
    three on the device, so the step can be queued behind the chunk."""
    return prev.at[slot].set(ids[0].astype(prev.dtype))


class _FirstToken(object):
    """A prompt's first greedy token, still on the device: what a
    deferred last chunk hands back (first_token() waits for it)."""
    __slots__ = ('slot', 'ids')

    def __init__(self, slot, ids):
        self.slot, self.ids = slot, ids


class BlockStart(object):
    """What prefill_step hands back for a model that generates by
    diffusion over blocks once a prompt's whole blocks are in: where the
    stream's first generated block starts and the prompt's last tokens,
    which open it as fixed tokens. `chunk_ran`: the call ran a chunk
    (False where nothing was left to prefill)."""
    __slots__ = ('slot', 'start', 'tail', 'chunk_ran')

    def __init__(self, slot, start, tail, chunk_ran):
        self.slot, self.start, self.tail = slot, start, list(tail)
        self.chunk_ran = chunk_ran


class BlockState(object):
    """One lane's block between its passes, as the host knows it: where
    it starts, the ids its first pass is fed (`fixed` tokens, the mask
    id behind them), the rows still masked and the passes dispatched.
    The serving engine keeps one a lane; generate() and a benchmark's
    check drive block_step through the same few lines."""
    __slots__ = ('start', 'ids', 'fixed', 'masked', 'passes')

    def __init__(self, start, tail, block, mask_id):
        self.start, self.fixed = int(start), len(tail)
        self.ids = [int(t) for t in tail] + [mask_id] * (block - len(tail))
        # a prompt token that is the mask id is a masked row like any
        # other: the device counts it so (op block_unmask)
        self.masked, self.passes = self.ids.count(mask_id), 0

    def plan(self, schedule):
        """(transfer, commit) of the block's next pass: the rows it
        unmasks at most, never more than are still masked; a block with
        no row masked is committed."""
        if not self.masked:
            return 0, True
        return min(schedule[min(self.passes, len(schedule) - 1)],
                   self.masked), False

    def passed(self, transfer, masked=None):
        """A denoising pass was dispatched: under the static rules the
        rows still masked follow from `transfer`; `masked` where the
        device's count is known (the synchronous form)."""
        self.passes += 1
        self.masked = self.masked - transfer if masked is None \
            else int(masked)


class _PendingPrefill(object):
    __slots__ = ('prompt', 'chunks', 'snapshot')

    def __init__(self, prompt, snapshot=None):
        self.prompt = prompt
        self.chunks = 0
        self.snapshot = snapshot    # matched, pinned, not yet copied


class PagedDecodePredictor(object):
    """Cached prefill/decode execution over a slot pool and a page
    pool; prefer AnalysisPredictor.prepare_decoding() over calling this
    directly."""

    # decode_step(defer=True) and collect(): a caller may keep one step
    # in flight (the serving engine's pipelined loop looks for this)
    deferred_decode = True

    def __init__(self, predictor, slots=None, page_tokens=None,
                 kv_pages=None, prefill_chunk=None, _clone_of=None,
                 pair=None, mesh=None, snapshot_rows=0, window_pages=None):
        """predictor: a (loaded) Predictor/AnalysisPredictor whose
        program is a decoder-only LM. slots defaults to
        FLAGS_serving_slots, the page geometry to FLAGS_serving_*.
        With `pair` (an already-transpiled PagedDecodePair) the
        transpile is skipped — the speculative path builds its target
        and draft pairs in one transpile_spec and hands them here.
        mesh (None = read FLAGS_serve_mesh_shape; '' = single-chip)
        makes every program ONE GSPMD SPMD program over the mesh — the
        page pool shards its heads axis over tp, weights per
        DecodeSpec.serve_param_specs, greedy decode stays bit-exact vs
        single-chip (serving/mesh.py). snapshot_rows (a model with
        recurrent layers): the prefix boundaries that keep their
        recurrent state on the device (module docstring). window_pages
        (a model with sliding layers): the pages their pools hold."""
        self._base = predictor
        if _clone_of is not None:
            self._pair = _clone_of._pair
            self._weight_scope = _clone_of._weight_scope
            self._mesh = _clone_of._mesh
            self._mesh_shape = _clone_of._mesh_shape
        else:
            from .mesh import serving_mesh
            if pair is not None:
                self._pair = pair
            else:
                from ..transpiler.decode_transpiler import DecodeTranspiler
                slots = int(slots or get_flag('serving_slots'))
                self._pair = DecodeTranspiler().transpile(
                    predictor._program, slots=slots,
                    page_tokens=page_tokens, kv_pages=kv_pages,
                    prefill_chunk=prefill_chunk,
                    snapshot_rows=int(snapshot_rows or 0),
                    window_pages=window_pages)
            self._weight_scope = predictor._scope
            self._mesh, self._mesh_shape = serving_mesh(mesh)
            if self._mesh is not None:
                from ..models.transformer import (refuse_latent_pages,
                                                  refuse_page_state,
                                                  refuse_window)
                what = 'mesh serving (%s)' % self._mesh_shape
                self._refuse_recurrent(what)
                refuse_latent_pages(self._pair.spec, what)
                refuse_window(self._pair.spec, what)
                refuse_page_state(self._pair.spec, what)
                self._refuse_blocks(what)
            self._pair.spec.mesh = self._mesh_shape
        if self.block_tokens and self.prefill_chunk % self.block_tokens:
            raise ValueError('a prefill chunk of %d tokens does not hold '
                             'whole blocks of %d'
                             % (self.prefill_chunk, self.block_tokens))
        self._exe = self._make_executor(predictor._place)
        if _clone_of is None:
            self._pin_weights()
        self._scope = Scope(parent=self._weight_scope)
        self.fetch_wait_s = 0.0       # blocked in a step's fetch, ever
        # what the block steps carried, ever (block_stats())
        self._block_stats = {'passes': 0, 'commits': 0, 'steps': 0,
                             'rows': 0, 'masked_rows': 0, 'live_tokens': 0}
        self.reset()
        # the copy program is compiled by the first decode step, with
        # the decode program (decode_step), the state copy programs by
        # the first prefill chunk, with the prefill program
        self._copy_compiled = False
        self._state_copy_compiled = not self._pair.snapshot_rows

    def _make_executor(self, place):
        if self._mesh is None:
            return Executor(place)
        from .mesh import MeshDecodeExecutor
        return MeshDecodeExecutor(place, self._mesh,
                                  self._cache_shardings())

    def _cache_shardings(self):
        """{pool var name: NamedSharding} — heads axis over tp, adapted
        by fit_spec (heads % tp != 0 falls back to replicated, never
        errors)."""
        from ..parallel.mesh import fit_spec, named_sharding
        pair = self._pair
        spec = fit_spec(pair.spec.pool_spec(), pair.pool_shape, self._mesh)
        sh = named_sharding(self._mesh, spec)
        return {n: sh for n in pair.cache_names}

    def _param_shardings(self):
        """{param name: NamedSharding} for the mesh: column-style specs
        from serve_param_specs, replicated for everything else."""
        from ..parallel.mesh import fit_spec, named_sharding
        serve = self._pair.spec.serve_param_specs()
        out = {}
        for name in self._pair.spec.param_names():
            spec = serve.get(name)
            if spec is not None:
                val = self._weight_scope.find_var(name)
                shape = getattr(val, 'shape', None)
                spec = fit_spec(spec, shape, self._mesh) \
                    if shape is not None else None
            out[name] = named_sharding(self._mesh, spec)
        return out

    # -- mesh introspection ------------------------------------------------
    @property
    def mesh_shape(self):
        """'tp=2'-style axis spec ('' = single-chip) — surfaced through
        ServingEngine.stats() and SRV_HEALTH."""
        return self._mesh_shape

    @property
    def mesh_devices(self):
        return int(self._mesh.devices.size) if self._mesh is not None \
            else 1

    # -- introspection -----------------------------------------------------
    @property
    def slots(self):
        return self._pair.slots

    @property
    def max_len(self):
        return self._pair.spec.max_len

    @property
    def vocab(self):
        return self._pair.spec.vocab

    def jit_cache_stats(self):
        return self._exe.jit_cache_stats()

    @property
    def page_tokens(self):
        return self._pair.page_tokens

    @property
    def num_pages(self):
        return self._pair.num_pages

    @property
    def pages_per_slot(self):
        return self._pair.pages_per_slot

    @property
    def prefill_chunk(self):
        return self._pair.prefill_chunk

    @property
    def window(self):
        """Max tokens (prompt + generated) one stream can hold."""
        return self.pages_per_slot * self.page_tokens

    def slot_tokens(self):
        """{slot: tokens held} for every open stream — the per-slot
        cache pressure LMServer.stats() exposes to the fleet router."""
        return {slot: t.length for slot, t in self._tables.items()}

    @property
    def recurrent(self):
        """True for a model with per-slot recurrent state."""
        return bool(self._pair.state_names)

    @property
    def block_tokens(self):
        """The block length of a model that generates by diffusion over
        blocks (its step is block_step, not decode_step); 0 for every
        other."""
        return self._pair.spec.block_tokens

    @property
    def block_schedule(self):
        """Rows a denoising pass unmasks, pass by pass."""
        return self._pair.spec.block_schedule

    @property
    def block_mask_id(self):
        return self._pair.spec.cfg.mask_id

    @property
    def block_defers(self):
        """Whether block_step can be one stage of a pipeline: under the
        static rules the host knows the rows still masked behind a pass
        without its ids."""
        return self._pair.spec.cfg.remasking != 'low_confidence_dynamic'

    def new_block(self, start, tail=()):
        """The BlockState of a block that starts at `start` and opens
        with the fixed tokens `tail`."""
        return BlockState(start, tail, self.block_tokens, self.block_mask_id)

    @property
    def swappable(self):
        """Whether save_stream / restore_stream can carry a stream of
        this model (not one with a second table, nor one with a block
        between its passes: module docstring)."""
        return not self._pair.window_num_pages and not self.block_tokens

    def _recurrent_state_bytes(self):
        shapes = self._pair.spec.state_shapes(self.slots) \
            if self.recurrent else ()
        return 4 * len(self._pair.spec.recurrent_layers) * int(
            sum(np.prod(s) for s in shapes))

    def _snapshot_row_bytes(self):
        """What one snapshot row holds: one slot's recurrent state."""
        return self._recurrent_state_bytes() // self.slots

    def _copy_state(self, program, at, to):
        """One state copy program: row `at` of every array it reads to
        row `to` of the array beside it, one dispatch."""
        self._exe.run(program, feed=dict(zip(
            self._pair.state_copy_feeds,
            (np.array([at], np.int32), np.array([to], np.int32)))),
            scope=self._scope, return_numpy=False)

    def _fetch(self, value):
        """value on the host: the wait for the device and the transfer,
        its seconds added to `fetch_wait_s`."""
        t0 = time.perf_counter()
        out = np.asarray(value)
        self.fetch_wait_s += time.perf_counter() - t0
        return out

    def _run(self, program, feed, fetches, decode):
        """One run of a program of the pair -> (logits, ids); a third
        fetch, the expert layers' counts, is queued for moe_counters()."""
        out = self._exe.run(program, feed=feed, fetch_list=fetches,
                            scope=self._scope, return_numpy=False)
        if len(out) > 2:
            self._moe_queue.append((decode, out[2]))
            self.moe_counters(leave=64)     # nobody asks: keep it short
        return out[0], out[1]

    def _copy_pages(self, pairs, window_pairs=()):
        """The page copy program over `pairs` of (src, dst) pages, at
        most one a slot: one dispatch copies them in every pool, each
        donated and updated in place. The rest of the feed is the null
        page onto itself. `window_pairs`: the same for the pools of a
        model's sliding layers (its second pair of feeds)."""
        feeds = []
        for some in (pairs, window_pairs)[:len(self._pair.copy_feeds) // 2]:
            src = np.zeros((self.slots,), np.int32)
            dst = np.zeros((self.slots,), np.int32)
            for i, one in enumerate(some):
                src[i], dst[i] = one
            feeds += [src, dst]
        self._exe.run(self._pair.copy_program,
                      feed=dict(zip(self._pair.copy_feeds, feeds)),
                      scope=self._scope, return_numpy=False)

    def _carry_first(self, prev, slot, ids):
        """`prev` (a decode step's ids) with lane `slot`'s entry from a
        last chunk's `ids`, on the device. Both arrays are placed as a
        feed is, so that every call is the one executable that the null
        call beside the copy program's compiled."""
        put = self._exe._put_feed
        return _with_first(put('decode_prev_ids', prev), np.int32(slot),
                           put('decode_prev_ids', ids))

    @staticmethod
    def _slide(wtable, length):
        """A booked chunk or step moved a stream on to `length` tokens:
        its table of the sliding layers gives up what lies behind the
        next position's window."""
        wtable.length = length
        _window_freed.inc(wtable.slide())

    def _fork_pages(self, cows):
        """Copy the pages a decode step forked, in front of its program:
        one dispatch if `cows` (the step's (table, index, (src, dst))
        entries) holds any, nothing otherwise. Called once the
        tables are settled, so a step that raises CacheExhaustedError
        has dispatched nothing."""
        if not cows:
            return
        with RecordEvent('paged.cow', pages=len(cows)):
            self._copy_pages(*(
                [pair for table, _, pair in cows if bool(table.window) == w]
                for w in ((False, True) if self._wpool else (False,))))
        _cow_dispatches.inc()
        _cow_pages.inc(len(cows))

    def moe_counters(self, leave=0):
        """What the expert layers have counted since reset(), over the
        steps that have ended: {'pairs', 'experts_touched',
        'pairs_dropped', 'layer_calls'} over both programs and
        'decode.<same>' for the decode program alone; {} for a model
        without expert layers. Brings the serving.moe.* counters up to
        date. A step still running is left for the next call, as are
        the newest `leave` (a step's own call: one small transfer a
        step, of a step long ended)."""
        if len(self._pair.decode_fetches) - bool(self.block_tokens) < 3:
            return {}
        with self._moe_lock:
            while len(self._moe_queue) > leave \
                    and self._moe_queue[0][1].is_ready():
                decode, counts = self._moe_queue.popleft()
                for what, n in zip(_MOE_COUNTS, np.asarray(counts)):
                    for key in (what, 'decode.' + what) if decode \
                            else (what,):
                        self._moe_totals[key] += int(n)
                        telemetry.counter('serving.moe.' + key).inc(int(n))
            return dict(self._moe_totals)

    def pool_stats(self):
        return {'page_tokens': self.page_tokens,
                'recurrent_state_bytes': self._recurrent_state_bytes(),
                'state_resets': self._resets,
                'num_pages': self.num_pages,
                'pages_in_use': self._pool.pages_in_use,
                'pages_free': self._pool.pages_free,
                'prefix_entries': len(self._prefix),
                'prefix_hits': self._prefix.hits,
                'prefix_misses': self._prefix.misses,
                'prefix_pages': self._prefix.resident_pages,
                'prefix_tokens_reused': self._prefix.tokens_reused,
                'snapshot_rows': self._pair.snapshot_rows,
                'snapshots': self._prefix.snapshots,
                'window_num_pages': self._pair.window_num_pages,
                'window_pages_in_use': self._wpool.pages_in_use
                if self._wpool else 0,
                'window_pages_live': self._window_pages_live()}

    def _window_pages_live(self):
        """Pages of the window pool that open streams' tables hold."""
        return sum(len(t.pages) for t in self._wtables.values())

    def _update_gauges(self):
        _pages_in_use.set(self._pool.pages_in_use)
        _pages_free.set(self._pool.pages_free)
        gone = self._prefix.snapshots_dropped
        if gone > self._snaps_gone:
            _snaps_evicted.inc(gone - self._snaps_gone)
            self._snaps_gone = gone
        if self._pair.spec.page_kind == 'latent':
            _latent_bytes.set(self._pool.pages_in_use * self.page_tokens
                              * self._pair.spec.latent_row_bytes())
        if self._wpool is not None:
            _window_in_use.set(self._wpool.pages_in_use)
            _window_live.set(self._window_pages_live())
        if self._page_state_rows:
            _page_state_bytes.set(self._pool.pages_in_use
                                  * self._page_state_page_bytes)

    # -- lifecycle ---------------------------------------------------------
    def _pin_weights(self):
        """Pin every referenced parameter to device in the PARENT scope
        before any child scope exists — otherwise the executor's lazy
        pin would write per-worker device copies into each child,
        duplicating the model in HBM once per clone.

        On a mesh this also covers already-device-resident arrays (a
        predictor that ran before prepare_decoding leaves params
        committed to one chip): device_put reshards them onto their
        serve NamedSharding, so the executor's single-device lazy-pin
        path never fires for a mesh weight."""
        block = self._pair.decode_program.global_block()
        shardings = self._param_shardings() if self._mesh is not None \
            else None
        for name in self._pair.spec.param_names():
            val = self._weight_scope.find_var(name)
            if val is None:
                raise RuntimeError(
                    'decode transpile references param %r that is not '
                    'in the predictor scope — was the model loaded with '
                    'load_params=True?' % name)
            if isinstance(val, np.ndarray) and \
                    val.dtype in (np.int64, np.uint64, np.float64):
                continue
            var = block.vars.get(name)
            if var is None or not var.persistable:
                continue
            if shardings is not None:
                self._weight_scope.set_var(
                    name, jax.device_put(val, shardings[name]))
            elif isinstance(val, np.ndarray):
                self._weight_scope.set_var(
                    name, jax.device_put(val, self._exe.device))

    def load_sharded(self, ckpt_dir, mesh=None):
        """Replace the weights from a sharded checkpoint root
        (checkpoint/sharded.py two-generation layout): each referenced
        param is assembled from the shard files of the last committed,
        digest-verified generation and resharded onto `mesh` (default:
        this predictor's serving mesh, else pinned whole to its
        device) — serving can roll to a checkpoint saved on ANY
        training topology; train-on-n/serve-on-m is a pure reshard. On
        a mesh the params land under their SERVE specs (column-style
        only; the checkpoint's recorded training spec is deliberately
        overridden — a row-sharded restore would break the bit-exact
        decode contract). The pools are runtime state, never
        checkpointed, never touched here. Raises if no generation is
        loadable or a referenced param is absent."""
        from ..checkpoint import restore as restore_mod
        ckpt = restore_mod.load_checkpoint(ckpt_dir)
        if ckpt is None:
            raise RuntimeError(
                'no committed checkpoint generation under %r' % ckpt_dir)
        if mesh is None:
            mesh = self._mesh
        serve = self._pair.spec.serve_param_specs()
        for name in self._pair.spec.param_names():
            if name not in ckpt:
                raise RuntimeError(
                    'sharded checkpoint %s (generation %d) is missing '
                    'param %r' % (ckpt.dirname, ckpt.generation, name))
            if mesh is not None:
                # spec=() (not None): None would fall back to the spec
                # RECORDED at save — the training layout, not the
                # bit-exact serve layout
                val = ckpt.as_jax(name, mesh,
                                  spec=serve.get(name, ()))
            else:
                val = jax.device_put(ckpt.read(name), self._exe.device)
            self._weight_scope.set_var(name, val)

    def param_names(self):
        """The refreshable weight names: every transpile-referenced
        param (the pools and recurrent state are per-worker runtime
        state, not params: never shipped by a parameter server)."""
        return list(self._pair.spec.param_names())

    def param_digests(self):
        """{name: crc32 of the param's wire payload} over the served
        weights — the same digest a pserver stamps into its manifest,
        so a fleet deploy can prove a replica converged to a published
        version without shipping the bytes again."""
        from ..distributed import wire
        from ..integrity import crc32
        out = {}
        for name in self.param_names():
            val = np.asarray(self._weight_scope.find_var(name))
            out[name] = crc32(wire._payload_of(val)[1])
        return out

    def stage_weights(self, params):
        """Stage a {name: host array} weight update for install: names
        are validated against the decode programs' param set, shapes
        against the currently pinned values, and every array is
        device_put OFF the decode path — the expensive half of a
        refresh. Returns an opaque staged dict for install_weights.
        Raises (installing nothing) on an unknown name or a shape
        mismatch."""
        known = set(self.param_names())
        shardings = self._param_shardings() if self._mesh is not None \
            else None
        staged = {}
        for name, val in params.items():
            if name not in known:
                raise KeyError(
                    'refresh carries unknown param %r (this predictor '
                    'serves %d params)' % (name, len(known)))
            arr = np.ascontiguousarray(val)
            cur = self._weight_scope.find_var(name)
            cur_shape = getattr(cur, 'shape', None)
            if cur_shape is not None and tuple(cur_shape) != arr.shape:
                raise ValueError(
                    'refresh shape mismatch for %r: got %r, serving %r'
                    % (name, arr.shape, tuple(cur_shape)))
            if shardings is not None:
                staged[name] = jax.device_put(arr, shardings[name])
            else:
                staged[name] = jax.device_put(arr, self._exe.device)
        return staged

    def install_weights(self, staged):
        """Swap staged device arrays into the PARENT weight scope — a
        few dict-pointer writes, cheap enough to run under the serving
        engine's step-boundary swap gate. Every clone sees the new
        weights on its next step (shared parent scope); in-flight steps
        already read the old arrays."""
        for name, val in staged.items():
            self._weight_scope.set_var(name, val)

    def reset(self):
        """Zero the page pools and forget every stream and cached
        prefix (fresh allocator state). On a mesh the zeroed pools land
        under the heads-sharded pin up front (steady-state layout from
        step one)."""
        for name, shape in self._pair.cache_shapes():
            self._scope.set_var(name, self._place_cache(
                name, np.zeros(shape, np.float32)))
        spec = self._pair.spec
        for layer in spec.recurrent_layers:
            for name, shape in zip(spec.state_names(layer),
                                   spec.state_shapes(self.slots)):
                self._scope.set_var(name, np.zeros(shape, np.float32))
        rows = self._pair.snapshot_rows
        if rows:
            shapes = spec.state_shapes(rows) * len(spec.recurrent_layers)
            for name, shape in zip(self._pair.snapshot_names, shapes):
                self._scope.set_var(name, np.zeros(shape, np.float32))
            _snap_bytes.set(rows * self._snapshot_row_bytes())
        # a model whose recurrent layers keep their rows by the page:
        # the rows and the bytes a page's entries hold, all such layers
        shapes = [spec.page_state_shape(1) for _ in spec.page_state_layers]
        self._page_state_rows = int(sum(sh[1] for sh in shapes))
        self._page_state_page_bytes = 4 * int(sum(
            np.prod(sh) for sh in shapes))
        self._moe_queue = collections.deque()   # (decode?, counts [4])
        self._moe_lock = threading.Lock()
        self._moe_totals = {p + what: 0 for what in _MOE_COUNTS
                            for p in ('', 'decode.')}
        self._pool = PagePool(self.num_pages, self.page_tokens)
        # a model with sliding layers: their pool and each stream's
        # table of them (None and empty otherwise)
        self._wpool = PagePool(self._pair.window_num_pages,
                               self.page_tokens) \
            if self._pair.window_num_pages else None
        self._wtables = {}            # slot -> PageTable with a window
        self._prefix = PrefixCache(self._pool, snapshot_rows=rows,
                                   window_pool=self._wpool,
                                   window=spec.window,
                                   block=spec.block_tokens)
        # slot -> the start of the block whose page is grown and forked
        self._block_grown = {}
        # the block step before's (ids [slots, B], rows still masked
        # [slots]): on the device once a step ran
        self._last_block = (
            np.zeros((self.slots, max(spec.block_tokens, 1)), np.int64),
            np.zeros((self.slots,), np.int32))
        self._block_masked = {}       # slot -> rows still masked
        self._snaps_gone = 0          # of them, counted so far
        self._pool.set_evict(self._evicting(self._prefix.evict_one, 'full'))
        if self._wpool is not None:
            self._wpool.set_evict(self._evicting(
                self._prefix.evict_window_one, 'window'))
        self._tables = {}             # slot -> PageTable
        self._pending = {}            # slot -> _PendingPrefill
        self._resets = 0              # streams started from zero state
        # the newest decode step's greedy ids, on the device once a step
        # ran: what a carried lane of the next step is fed from
        self._last_ids = np.zeros((self.slots,), np.int64)
        self._in_flight = False       # a deferred step awaits its fetch
        # slot -> the _FirstToken of a deferred last chunk that no decode
        # step has taken and no first_token() has fetched yet
        self._first = {}
        _state_bytes.set(self._recurrent_state_bytes())
        if spec.state_family:
            telemetry.gauge('serving.%s.state_bytes' % spec.state_family) \
                .set(self._recurrent_state_bytes())
        self._update_gauges()

    def _evicting(self, evict, pool):
        """`evict` (the prefix cache's, for the pool named) as the pool
        calls it, in a `paged.prefix.evict` span under the `*.tables`
        span that asked for the page; what the scan cost also adds up in
        the serving.prefix.* counters."""
        prefix = self._prefix

        def run():
            with RecordEvent('paged.prefix.evict', pool=pool) as ev:
                t0, scanned = time.perf_counter(), prefix.entries_scanned
                freed = evict()
                scanned = ev.attrs['scanned'] = \
                    prefix.entries_scanned - scanned
                ev.attrs['freed'] = int(freed)
                _prefix_evictions.inc(int(freed))
                _prefix_scanned.inc(scanned)
                _prefix_evict_seconds.inc(time.perf_counter() - t0)
            return freed
        return run

    def _place_cache(self, name, value):
        """Host K/V state -> the executor's pinned device layout (the
        identity off-mesh: the executor lazy-pins on first run)."""
        if self._mesh is None:
            return value
        return self._exe.place_state(name, value)

    def clone(self):
        """A worker sharing this one's weights and compiled-program
        identity (same Program objects -> same jit cache keys) with a
        PRIVATE cache scope + executor — concurrent decode streams
        can't cross-talk."""
        return PagedDecodePredictor(self._base, _clone_of=self)

    # -- streams -----------------------------------------------------------
    def open_stream(self, slot, prompt):
        """Begin a stream on `slot`: match the prefix cache and adopt
        any shared pages (read-only, zero recompute). Allocates no new
        pages, so admission itself can never exhaust the pool. Returns
        {'shared_tokens', 'chunks'} — the suffix prefill plan — and
        'offprompt_tokens': of the shared tokens, those that came from a
        boundary of whole pages where no prompt had ended (0 for a
        family that does not ask the cache the plain match)."""
        slot = int(slot)
        if not 0 <= slot < self.slots:
            raise ValueError('slot %r outside [0, %d)' % (slot, self.slots))
        if slot in self._tables:
            raise RuntimeError('slot %d already holds a stream — '
                               'release() it first' % slot)
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not 1 <= len(prompt) <= self.max_len:
            raise ValueError('prompt length %d outside [1, %d] (max_len)'
                             % (len(prompt), self.max_len))
        with RecordEvent('paged.open', prompt_tokens=len(prompt)) as ev:
            shared, off = self._open(slot, prompt)
            ev.attrs['shared_tokens'] = shared
            if off is not None:
                ev.attrs['offprompt_tokens'] = off
        chunk = self.prefill_chunk
        return {'slot': slot, 'prompt_tokens': len(prompt),
                'shared_tokens': shared, 'offprompt_tokens': off or 0,
                'chunks': -(-(self._prefilled(len(prompt)) - shared)
                            // chunk)}

    def _prefilled(self, n):
        """Of a prompt of `n` tokens, those the prefill program takes:
        all of them, or its whole blocks."""
        return n - n % self.block_tokens if self.block_tokens else n

    def _open(self, slot, prompt):
        """open_stream's body: the stream's tables, the one question to
        the prefix cache its family asks, the adoption. Returns the
        tokens it shares and, where the question was the plain match,
        those of them from a boundary where no prompt had ended (None
        otherwise)."""
        table = PageTable(self._pool, self.pages_per_slot)
        # never pages without their state: a stream with recurrent
        # state opens on a boundary that has a snapshot (which comes
        # pinned until its first chunk has copied it), or on nothing
        # (see the module docstring)
        if self._wpool is not None:
            match = self._prefix.match_window
        elif not self.recurrent:
            match = self._prefix.match
        elif self._pair.snapshot_rows:
            match = self._prefix.match_state
        else:
            match = None
        # a prompt's last token is always computed (its logits make the
        # first token), except where whole blocks are prefilled and the
        # first block's pass makes the stream's first logits
        limit = len(prompt) - (0 if self.block_tokens else 1)
        missed = self._prefix.window_tail_misses
        before, off = self._prefix.offprompt_tokens, None
        got = [], 0
        if match is not None:
            with RecordEvent('paged.prefix.match',
                             pages=limit // self.page_tokens) as ev:
                got = match(prompt, limit=limit)
                ev.attrs['shared_tokens'] = got[1]
                if match == self._prefix.match:
                    # of them, those from a boundary no prompt ended at
                    off = self._prefix.offprompt_tokens - before
                    ev.attrs['offprompt_tokens'] = off
                    _prefix_offprompt.inc(off)
        pages, shared, *rest = got
        snapshot = None
        if self._wpool is not None:
            wpages, wfirst = rest
            wtable = self._wtables[slot] = PageTable(
                self._wpool, self._pair.window_pages_per_slot,
                window=self._pair.spec.window)
            if shared:
                wtable.adopt_shared(wpages, shared, first=wfirst)
                _window_tail_adopted.inc()
            if self._prefix.window_tail_misses > missed:
                _window_tail_miss.inc()
        elif rest:
            snapshot, = rest
        if shared:
            table.adopt_shared(pages, shared)
            _prefix_hits.inc()
            _prefix_tokens.inc(shared)
            if self._page_state_rows:
                # its recurrent rows came with the page it opened on
                _page_state_adopted.inc()
        _prompt_tokens.inc(len(prompt))
        self._tables[slot] = table
        self._pending[slot] = _PendingPrefill(prompt, snapshot)
        self._update_gauges()
        return shared, off

    def release(self, slot):
        """Drop a stream's page refs (cache-registered prefix pages
        stay resident for future hits). With a deferred decode step in
        flight that still writes this stream's last page, the release
        is safe as it is: that write is already queued, and a program
        that could own the page again is dispatched after it, so the
        device runs it after it."""
        slot = int(slot)
        table = self._tables.pop(slot, None)
        wtable = self._wtables.pop(slot, None)
        st = self._pending.pop(slot, None)
        self._first.pop(slot, None)
        self._block_grown.pop(slot, None)
        self._block_masked.pop(slot, None)
        if st is not None and st.snapshot is not None:
            self._prefix.unpin(st.snapshot)
        if wtable is not None:
            wtable.release()
        if table is not None:
            table.release()
            self._update_gauges()

    # -- preempt / resume (serving/preempt.py) -----------------------------
    def save_stream(self, slot):
        """Snapshot one open, fully prefilled stream's page contents
        device -> host (preempt-first capacity, serving/preempt.py).
        Returns {'length', 'pages', 'data', 'nbytes'}; the stream
        itself is untouched — the caller release()s the slot only after
        the copy succeeded, so a failed gather never loses pages."""
        slot = int(slot)
        self._refuse_window('save_stream')
        self._refuse_blocks('save_stream')
        if slot in self._pending:
            raise RuntimeError('slot %d is still prefilling — requeue '
                               'it, there is nothing worth swapping'
                               % slot)
        table = self._tables[slot]
        pools = [self._scope.find_var(name)
                 for name in self._pair.cache_names]
        data = self._pool.save_pages(pools, table.pages)
        snap = {'length': table.length, 'pages': len(table.pages),
                'data': data,
                'nbytes': int(sum(d.nbytes for d in data))}
        if self.recurrent:
            with RecordEvent('paged.state.save') as ev:
                snap['state'] = [
                    np.asarray(self._scope.find_var(name)[slot])
                    for name in self._pair.state_names]
                ev.attrs['nbytes'] = nbytes = \
                    int(sum(a.nbytes for a in snap['state']))
            snap['nbytes'] += nbytes
        return snap

    def restore_stream(self, slot, snapshot, prompt=None):
        """Re-seat a save_stream() snapshot on `slot`: allocate fresh
        pages (all-or-nothing — CacheExhaustedError with nothing taken
        when the pool is still too tight, so the resuming stream just
        stays queued), write the host copies back, and rebuild the page
        table at the saved length. Every restored page is private (the
        stream owns the fresh copies), so later appends never fork.
        `prompt` (the committed token sequence) is unused here; the
        speculative override re-prefills its draft from it."""
        slot = int(slot)
        self._refuse_window('restore_stream')
        self._refuse_blocks('restore_stream')
        if slot in self._tables:
            raise RuntimeError('slot %d already holds a stream — '
                               'release() it first' % slot)
        names = self._pair.cache_names
        pools = [self._scope.find_var(name) for name in names]
        ids, pools = self._pool.restore_pages(pools, snapshot['data'])
        for name, pool in zip(names, pools):
            # _place_cache: on a mesh the .at[].set result re-pins to the
            # heads-sharded layout so the donated pool never flips
            # sharding (which would recompile the decode step)
            self._scope.set_var(name, self._place_cache(name, pool))
        if self.recurrent:
            with RecordEvent('paged.state.restore') as ev:
                for name, rows in zip(self._pair.state_names,
                                      snapshot['state']):
                    self._scope.set_var(name, _set_row(
                        self._scope.find_var(name), slot, rows))
                ev.attrs['nbytes'] = \
                    int(sum(a.nbytes for a in snapshot['state']))
        table = PageTable(self._pool, self.pages_per_slot)
        table.pages = list(ids)
        table.length = int(snapshot['length'])
        self._tables[slot] = table
        self._update_gauges()

    # -- disaggregated page shipping (serving/disagg.py) -------------------
    def export_prefix(self, prompt):
        """Gather the full-page hash chain this cache holds for
        `prompt` (capped at prompt[:-1], the sharing limit) into host
        float32 copies — the prefill tier's half of a page ship.
        Returns None when nothing is resident, else {'keys' (hex, in
        chain order), 'tokens', 'data' (one [n, page_tokens, ...] array
        per layer pool), 'nbytes'}. A pure read: refcounts, tables and
        LRU stamps are untouched."""
        self._refuse_recurrent('page shipping (export_prefix)')
        self._refuse_window('page shipping (export_prefix)')
        self._refuse_blocks('page shipping (export_prefix)')
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        digests, pages = self._prefix.chain(prompt,
                                            limit=len(prompt) - 1)
        if not digests:
            return None
        pools = [self._scope.find_var(name)
                 for name in self._pair.cache_names]
        data = self._pool.save_pages(pools, pages)
        return {'keys': [d.hex() for d in digests],
                'tokens': len(digests) * self.page_tokens,
                'data': data,
                'nbytes': int(sum(d.nbytes for d in data))}

    def resident_keys(self, prompt):
        """Hex keys of the leading full-page chain run this cache holds
        for `prompt` — the 'have' list a page fetch sends so the sender
        skips pages already here. Advisory (no quiesce, no LRU touch):
        install_prefix re-checks residency under the swap gate, so a
        racing eviction only costs wire bytes, never correctness."""
        if self.recurrent or self._wpool is not None or self.block_tokens:
            return []           # such pages are worth nothing elsewhere
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        digests, _ = self._prefix.chain(prompt, limit=len(prompt) - 1)
        return [d.hex() for d in digests]

    def install_prefix(self, prompt, keys, data, skip=0):
        """Install a shipped page run into the local pool + prefix
        cache (the decode tier's half). `keys` is the FULL leading run
        of the prompt's hash chain the sender holds; `data` carries
        rows for keys[skip:] only (the sender omitted pages the
        receiver reported having). The chain is recomputed here, so a
        shipment with foreign pages, corrupt keys, or a different
        page_tokens is refused with ValueError and the caller
        re-prefills locally — as is a shipment whose skipped prefix is
        no longer resident (evicted between report and install: the
        graft would dangle). Rows already resident (a racing install)
        are deduped without allocation. Returns (installed, deduped)
        page counts; raises the retryable CacheExhaustedError with
        nothing taken when the pool cannot fit the fresh rows."""
        self._refuse_recurrent('page shipping (install_prefix)')
        self._refuse_window('page shipping (install_prefix)')
        self._refuse_blocks('page shipping (install_prefix)')
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        keys = list(keys)
        skip = int(skip)
        n = len(keys)
        if n == 0:
            return 0, 0
        expected = chain_keys(prompt, self.page_tokens,
                              limit=len(prompt) - 1)
        if keys != expected[:n]:
            raise ValueError(
                'shipped keys are not a leading run of the prompt '
                'hash chain (%d keys, page_tokens=%d)'
                % (n, self.page_tokens))
        resident, _ = self._prefix.chain(prompt, limit=len(prompt) - 1)
        have = min(len(resident), n)
        if have >= n:
            return 0, n
        if have < skip:
            raise ValueError(
                'shipment skipped %d pages but only %d are still '
                'resident — the graft parent was evicted' % (skip, have))
        names = self._pair.cache_names
        pools = [self._scope.find_var(name) for name in names]
        ids, pools = self._pool.restore_pages(
            pools, [rows[have - skip:n - skip] for rows in data])
        for name, pool in zip(names, pools):
            self._scope.set_var(name, self._place_cache(name, pool))
        parent = resident[have - 1] if have else b''
        self._prefix.extend_chain(
            parent, [bytes.fromhex(k) for k in keys[have:n]], ids)
        self._update_gauges()
        return n - have, have

    def _refuse_recurrent(self, what):
        from ..models.transformer import refuse_recurrent
        refuse_recurrent(self._pair.spec, what)

    def _refuse_window(self, what):
        from ..models.transformer import refuse_window
        refuse_window(self._pair.spec, what)

    def _refuse_blocks(self, what):
        from ..models.transformer import refuse_blocks
        refuse_blocks(self._pair.spec, what)

    def prefix_report(self):
        """Drain the prefix cache's registered/evicted delta (the
        SRV_HEALTH payload feeding the fleet prefix directory). The
        directory is told nothing of pages that are a prefix only with
        a snapshot row of this predictor."""
        events = self._prefix.drain_events()
        return {'new': [], 'evicted': []} if self.recurrent \
            or self._wpool is not None or self.block_tokens else events

    @staticmethod
    def _rollback(cows, grows):
        """Undo page mutations from a failed (never-run) step: COW
        sources were NOT unref'd yet, so restoring them is pure
        bookkeeping and the device state is untouched."""
        for table, before in reversed(grows):
            while len(table.pages) > before:
                table.pool.unref(table.pages.pop())
        for table, idx, (src, dst) in reversed(cows):
            table.pages[idx] = src
            table.shared.add(idx)
            table.pool.unref(dst)

    # -- execution ---------------------------------------------------------
    def _registering(self, prompt):
        """The span around a prompt's registration in the prefix cache
        (once a prompt, inside the `paged.prefill.book` of its last
        chunk); `pages`: the whole pages hashed."""
        return RecordEvent('paged.prefix.register', tokens=len(prompt),
                           pages=len(prompt) // self.page_tokens)

    def prefill_step(self, slot, return_logits=False, defer=False):
        """Advance one stream's prefill by ONE chunk. Returns None
        while more chunks remain; on the final chunk, registers the
        prompt with the prefix cache and returns the first greedy
        token (with return_logits: (token, logits [vocab])). Raises
        CacheExhaustedError — with this call's allocations rolled
        back — when the pool cannot cover the chunk.

        Called so, the final chunk is synchronous: the call waits for
        its token. With `defer=True` the final chunk is dispatched and
        booked (the adoption in front of it and the snapshot behind it
        as in the other form, in the same order) and the call returns
        at once, with a handle on the token (never None: the prompt is
        in), which stays on the device. A decode_step that names the
        slot in `carry` feeds the lane that token there, so it can be
        dispatched behind the chunk with nothing fetched in between;
        first_token(handle) waits for it. A chunk that is not the last
        returns None in both forms.

        For a model that generates by diffusion over blocks the chunks
        hold the prompt's whole blocks and the last has no token to hand
        back: the call that ends the prefill (at once and with no
        program run, where no whole block is left to compute) returns a
        BlockStart in both forms (with return_logits: (BlockStart,
        logits [vocab] of the chunk's last row, None where no chunk
        ran))."""
        if defer and return_logits:
            raise ValueError('a deferred chunk hands back its token only')
        slot = int(slot)
        st = self._pending[slot]
        table = self._tables[slot]
        prompt, start = st.prompt, table.length
        whole = self._prefilled(len(prompt))
        C, P, pt = self.prefill_chunk, self.pages_per_slot, self.page_tokens
        if start >= whole:
            # whole blocks alone are prefilled, and the cache shared them
            # all (or the prompt is shorter than a block)
            del self._pending[slot]
            _prefill_chunks.observe(st.chunks)
            out = BlockStart(slot, whole, prompt[whole:], False)
            return (out, None) if return_logits else out
        n = min(C, whole - start)
        cows, grows = [], []
        wtable = self._wtables.get(slot)
        with RecordEvent('paged.prefill.tables') as ev:
            try:
                for one in (table, wtable) if wtable else (table,):
                    before = len(one.pages)
                    pair = one.cow_for_append(start)
                    if pair is not None:
                        cows.append((one, one.index(start), pair))
                    one.ensure(start + n)
                    if len(one.pages) > before:
                        grows.append((one, before))
            except CacheExhaustedError as e:
                self._rollback(cows, grows)
                raise CacheExhaustedError(str(e), slots=[slot])
            tokens = np.zeros((1, C, 1), np.int64)
            tokens[0, :n, 0] = prompt[start:start + n]
            positions = (start + np.arange(C, dtype=np.int32))
            table_feed = np.zeros((1, P), np.int32)
            table.row(table_feed[0])
            # a chunk forks at most one page a table: (0, 0) where none
            forked = {bool(one.window): pair for one, _idx, pair in cows}
            cow_src, cow_dst = (np.array([p], np.int32)
                                for p in forked.get(False, (0, 0)))
            feed = {'prefill_tokens': tokens,
                    'prefill_positions': positions,
                    'prefill_len': np.array([n], np.int32),
                    'prefill_last': np.array([n - 1], np.int32),
                    'prefill_page_table': table_feed,
                    'prefill_cow_src': cow_src,
                    'prefill_cow_dst': cow_dst}
            if 'prefill_state_slot' in self._pair.prefill_feeds:
                # a stream's first chunk starts its slot from zero state
                feed['prefill_state_slot'] = np.array([slot], np.int32)
                feed['prefill_state_reset'] = \
                    np.array([start == 0], np.int32)
                if start == 0:
                    self._resets += 1
                    _state_resets.set(self._resets)
                # the rows the recurrence's chunk form really carries
                ev.attrs['state_tokens'] = n
                _state_chunk_tokens.inc(n)
            if self._page_state_rows:
                # every page the chunk touches takes its layers' rows
                ev.attrs['page_state_rows'] = rows = self._page_state_rows \
                    * ((start + n - 1) // pt - start // pt + 1)
                _page_state_rows_chunk.inc(rows)
            if wtable is not None:
                wfeed = np.zeros((1, wtable.width), np.int32)
                wtable.row(wfeed[0])
                wsrc, wdst = (np.array([p], np.int32)
                              for p in forked.get(True, (0, 0)))
                feed.update(prefill_window_table=wfeed,
                            prefill_window_positions=positions - np.int32(
                                wtable.base),
                            prefill_window_cow_src=wsrc,
                            prefill_window_cow_dst=wdst)
        if not self._state_copy_compiled:
            # the two state copy programs compile where the prefill
            # program does: a copy of this slot's rows, which the chunk
            # is about to reset, to a row that holds no snapshot yet,
            # and back
            with RecordEvent('paged.state.compile'):
                self._copy_state(self._pair.snapshot_program, slot, 0)
                self._copy_state(self._pair.adopt_program, 0, slot)
            self._state_copy_compiled = True
        if st.snapshot is not None:
            # the stream opened on a snapshot: its row into the slot, on
            # the device, in front of the chunk (the tables are settled:
            # a chunk that raised has copied nothing and keeps its pin)
            with RecordEvent('paged.state.adopt', tokens=start,
                             nbytes=self._snapshot_row_bytes()):
                self._copy_state(self._pair.adopt_program,
                                 st.snapshot.row, slot)
            self._prefix.unpin(st.snapshot)
            st.snapshot = None
            _snaps_adopted.inc()
        logits, ids = self._run(self._pair.prefill_program, feed,
                                self._pair.prefill_fetches, False)
        with RecordEvent('paged.prefill.book'):
            for table_, _idx, (src, _dst) in cows:
                table_.pool.unref(src)
            table.length = start + n
            st.chunks += 1
            if wtable is not None:
                if table.length == len(prompt):
                    # both tables, before the second gives up what the
                    # prompt's end no longer reads: a boundary a little
                    # short of it finds its window tail too
                    with self._registering(prompt):
                        self._prefix.register(prompt, table, wtable)
                self._slide(wtable, table.length)
            self._update_gauges()
            if table.length < whole:
                return None
            if wtable is None and not self.recurrent:
                with self._registering(prompt):
                    self._prefix.register(prompt, table)
            elif self._pair.snapshot_rows:
                with self._registering(prompt) as ev:
                    row = self._prefix.register_state(prompt, table)
                    ev.attrs['row'] = int(row is not None)
                if row is not None:
                    # behind the chunk just dispatched, in front of any
                    # step that moves the slot's state on
                    with RecordEvent('paged.state.snapshot',
                                     tokens=len(prompt),
                                     nbytes=self._snapshot_row_bytes()):
                        self._copy_state(self._pair.snapshot_program,
                                         slot, row)
                    _snaps_taken.inc()
                self._update_gauges()
            del self._pending[slot]
            _prefill_chunks.observe(st.chunks)
        if self.block_tokens:
            out = BlockStart(slot, whole, prompt[whole:], True)
            if return_logits:
                with RecordEvent('paged.prefill.fetch'):
                    return out, self._fetch(logits)[0]
            return out
        if defer:
            first = self._first[slot] = _FirstToken(slot, ids)
            return first
        with RecordEvent('paged.prefill.fetch'):
            tok = int(self._fetch(ids)[0])
            if return_logits:
                return tok, self._fetch(logits)[0]
        return tok

    def first_token(self, first):
        """The token of a deferred last chunk (the handle its
        prefill_step returned): the wait for the chunk and the
        transfer, in a `paged.prefill.fetch` span as the synchronous
        form's, its seconds in `fetch_wait_s`. A decode step that took
        the token on the device may have been dispatched meanwhile, or
        not: a lane fed from the host afterwards is fed this."""
        if self._first.get(first.slot) is first:
            del self._first[first.slot]
        with RecordEvent('paged.prefill.fetch'):
            return int(self._fetch(first.ids)[0])

    def decode_step(self, tokens, positions, return_logits=False,
                    lanes=None, carry=(), defer=False):
        """One step for the WHOLE pool:
        tokens [slots], positions [slots] (each stream's next append
        position, which must be its current length). Only open,
        fully-prefilled streams take part (`lanes`, an iterable of
        slots, names fewer: a stream left out keeps its pages and its
        recurrent state as they are); every other lane is fed the
        null-page table row, so its mandatory write is dead
        weight. New pages are
        allocated on demand, and a lane whose append would land on a
        page it shares forks it: the step then runs the page copy
        program in front of its own (_fork_pages), as no other step
        does. If ANY stream cannot grow, the step runs
        nothing, this call's allocations and forks are rolled back, and
        CacheExhaustedError(slots=[...]) names the victims — the
        caller releases or evicts them and retries the same feed.

        Called so, the step is synchronous: it returns this step's
        greedy ids [slots] (and logits with return_logits). With
        `defer=True` it is one stage of a one-deep pipeline: the step
        is dispatched, and what comes back is the ids of the deferred
        step BEFORE it (None if none was in flight), fetched only now,
        behind this step's dispatch, so the device has its next program
        while the host waits for the last. This step's ids stay on the
        device until the next deferred call or collect(). A lane in
        `carry` is fed its token on the device, by a select inside the
        program: its `tokens` entry is not read. The token is the id
        the step in flight made for it (a slot that took part in that
        step), or, for a slot whose prompt's last chunk was deferred
        and has not been fetched, that chunk's id: written into the
        ids the select reads by one small executable in front of the
        step (`paged.carry`), so the step is queued behind the chunk
        with no fetch in between. The ids the caller gets back are the
        step before's as that step left them. CacheExhaustedError
        keeps its contract in both forms: nothing of this step ran, the
        step in flight is as it was, still to be collected, and a
        deferred chunk's token is still to be taken or fetched."""
        S, P, pt = self.slots, self.pages_per_slot, self.page_tokens
        self._refuse_blocks('decode_step')
        overlapped = self._in_flight
        if overlapped and not defer:
            raise RuntimeError('a deferred decode step is in flight — '
                               'collect() it before a synchronous one')
        if defer and return_logits:
            raise ValueError('a deferred step hands back ids only')
        carry = [int(s) for s in carry]
        fresh = [s for s in carry if s in self._first]
        if len(carry) > len(fresh) and not overlapped:
            raise ValueError('carry names slot(s) %s but no step is in '
                             'flight to take their tokens from'
                             % [s for s in carry if s not in fresh])
        with RecordEvent('paged.decode.tables') as ev:
            tokens = np.asarray(tokens, np.int64).reshape(S, 1, 1)
            positions = np.asarray(positions, np.int32).reshape(S)
            table_feed = np.zeros((S, P), np.int32)
            pos_feed = np.zeros((S,), np.int32)
            carry_feed = np.zeros((S,), np.int32)
            carry_feed[carry] = 1
            if self._wpool is not None:
                wtable_feed = np.zeros(
                    (S, self._pair.window_pages_per_slot), np.int32)
                wpos_feed = np.zeros((S,), np.int32)
            cows, grows, failed, live = [], [], [], []
            for slot in sorted(self._tables if lanes is None
                               else map(int, lanes)):
                if slot in self._pending:
                    continue          # mid-prefill: stays on null pages
                table = self._tables[slot]
                wtable = self._wtables.get(slot)
                pos = int(positions[slot])
                try:
                    for one in (table, wtable) if wtable else (table,):
                        before = len(one.pages)
                        pair = one.cow_for_append(pos)
                        if pair is not None:
                            cows.append((one, one.index(pos), pair))
                        one.ensure(pos + 1)
                        if len(one.pages) > before:
                            grows.append((one, before))
                except CacheExhaustedError:
                    failed.append(slot)
                    continue
                table.row(table_feed[slot])
                pos_feed[slot] = pos
                if wtable is not None:
                    wtable.row(wtable_feed[slot])
                    wpos_feed[slot] = pos - wtable.base
                live.append(slot)
            if failed:
                self._rollback(cows, grows)
                self._update_gauges()
                raise CacheExhaustedError(
                    'KV page pool exhausted for slot(s) %s'
                    % ','.join(map(str, failed)), slots=failed)
            # what the step's attention has to read against what a
            # gather of every slot's window would: the lanes that take
            # part hold ceil((pos + 1) / pt) pages each
            ev.attrs['pages_read'] = pages_read = \
                sum(int(pos_feed[slot]) // pt + 1 for slot in live)
            ev.attrs['overlapped'] = int(overlapped)
            ev.attrs['carried'] = len(carry) - len(fresh)
            ev.attrs['carried_prefill'] = len(fresh)
            _decode_pages_read.inc(pages_read)
            feed = {'decode_tokens': tokens,
                    'decode_prev_ids': self._last_ids,
                    'decode_carry': carry_feed,
                    'decode_step_idx': pos_feed,
                    'decode_page_table': table_feed}
            live_feed = np.zeros((S,), np.int32)
            live_feed[live] = 1
            if 'decode_live' in self._pair.decode_feeds:
                feed['decode_live'] = live_feed
            if self._wpool is not None:
                feed['decode_window_table'] = wtable_feed
                feed['decode_window_step_idx'] = wpos_feed
                # the rows the step's attention has to read: in a full
                # layer a lane's tokens so far and the one it appends,
                # in a sliding layer the last `window` of them
                ev.attrs['rows_read'] = sum(
                    int(pos_feed[slot]) + 1 for slot in live)
                ev.attrs['window_rows_read'] = sum(
                    min(int(pos_feed[slot]) + 1, self._pair.spec.window)
                    for slot in live)
            if self._pair.spec.page_kind == 'latent':
                # the rows the step's attention reads: a lane's tokens
                # so far and the one it appends, in every layer
                ev.attrs['latent_rows'] = rows = \
                    len(self._pair.spec.kv_layers) \
                    * sum(int(pos_feed[slot]) + 1 for slot in live)
                _latent_rows.inc(rows)
            if 'decode_state_live' in self._pair.decode_feeds:
                feed['decode_state_live'] = live_feed
                ev.attrs['state_lanes'] = len(live)
                _state_lanes.inc(len(live))
            if self._page_state_rows:
                ev.attrs['page_state_rows'] = rows = \
                    self._page_state_rows * len(live)
                _page_state_rows_step.inc(rows)
        if not self._copy_compiled:
            # a run over null pairs in front of the first decode step:
            # the copy program compiles where the decode program does
            # (weights and pools are on the device by now: beside
            # their transfer, when the predictor is built, 48 pools'
            # copies took 4.7 s to compile, after it 0.5), and no later
            # step's fork meets its compile
            with RecordEvent('paged.cow.compile'):
                self._copy_pages(())
                self._carry_first(self._last_ids, 0,
                                  np.zeros((1,), np.int32))
            self._copy_compiled = True
        self._fork_pages(cows)
        if fresh:
            with RecordEvent('paged.carry', lanes=len(fresh)):
                for slot in fresh:
                    feed['decode_prev_ids'] = self._carry_first(
                        feed['decode_prev_ids'], slot,
                        self._first.pop(slot).ids)
        logits, ids = self._run(self._pair.decode_program, feed,
                                self._pair.decode_fetches, True)
        with RecordEvent('paged.decode.book'):
            for table, _idx, (src, _dst) in cows:
                table.pool.unref(src)
            for slot in live:
                table = self._tables[slot]
                table.length = max(table.length, int(positions[slot]) + 1)
                if slot in self._wtables:
                    self._slide(self._wtables[slot], table.length)
            self._update_gauges()
        prev, self._last_ids, self._in_flight = self._last_ids, ids, defer
        with RecordEvent('paged.decode.fetch'):
            if defer:
                # the wait for the step BEFORE this one, with this one
                # already queued on the device behind it
                return self._fetch(prev) if overlapped else None
            if return_logits:
                return self._fetch(ids), self._fetch(logits)
            return self._fetch(ids)

    def block_step(self, tokens, starts, transfer, lanes, carry=(),
                   commit=(), return_logits=False, defer=False):
        """One pass over a block of every lane in `lanes`, for a model
        that generates by diffusion over blocks (module docstring,
        "Blocks"): tokens [slots, B] (each lane's block as the host
        holds it: fixed tokens, the mask id elsewhere; not read for a
        lane in `carry`, which takes the ids the step before left on the
        device behind its unmasking), starts [slots] (where each lane's
        block starts: its stream's committed length, a multiple of B),
        transfer [slots] (the masked rows this pass unmasks, at most).
        A lane in `commit` has no row masked: its pass's K/V rows are
        the ones that count, and its stream's length grows by B; every
        other lane's rows are overwritten by its next pass. A lane
        left out of `lanes` keeps its pages as they are.

        A lane's first pass over a block grows its table to the block's
        end and forks the page the block lands on if the stream shares
        it (the page copy program, in front of this step's: once a
        block). If ANY lane cannot grow, the step runs nothing, this
        call's allocations and forks are rolled back, and
        CacheExhaustedError(slots=[...]) names the victims, as
        decode_step does.

        Returns (ids [slots, B] behind this pass's unmasking, masked
        [slots]: rows still masked), with return_logits also logits
        [slots, B, vocab] (row i scores the token at position i of the
        block). With `defer=True` (not under low_confidence_dynamic:
        `block_defers`) the step is dispatched and what comes back is
        the pair of the deferred step BEFORE it, or None: a one-deep
        pipeline, as decode_step's."""
        S, P, pt = self.slots, self.pages_per_slot, self.page_tokens
        B = self.block_tokens
        if not B:
            raise RuntimeError('block_step on a model that decodes one '
                               'token a lane a step')
        overlapped = self._in_flight
        if overlapped and not defer:
            raise RuntimeError('a deferred block step is in flight — '
                               'collect() it before a synchronous one')
        if defer and (return_logits or not self.block_defers):
            raise ValueError('a deferred block step hands back ids only, '
                             'and not under low_confidence_dynamic')
        carry = [int(s) for s in carry]
        commit = [int(s) for s in commit]
        with RecordEvent('paged.decode.tables') as ev:
            # copies: the caller packs its next pass into the same
            # arrays while this one may still be on its way to the device
            tokens = np.array(tokens, np.int64).reshape(S, B, 1)
            starts = np.asarray(starts, np.int32).reshape(S)
            table_feed = np.zeros((S, P), np.int32)
            pos_feed = np.zeros((S, B), np.int32)
            end_feed = np.zeros((S,), np.int32)
            live_feed = np.zeros((S,), np.int32)
            # a lane that sits the pass out keeps the ids it left on the
            # device: it is carried through, with nothing to unmask
            carry_feed = np.ones((S,), np.int32)
            transfer_feed = np.array(transfer, np.int32).reshape(S)
            cows, grows, failed, live, grown = [], [], [], [], []
            for slot in sorted(map(int, lanes)):
                if slot in self._pending:
                    continue          # mid-prefill: stays on null pages
                table = self._tables[slot]
                start = int(starts[slot])
                if start % B or start != table.length:
                    raise ValueError(
                        'slot %d: a block at %d over %d committed tokens'
                        % (slot, start, table.length))
                if self._block_grown.get(slot) != start:
                    try:
                        before = len(table.pages)
                        pair = table.cow_for_append(start)
                        if pair is not None:
                            cows.append((table, table.index(start), pair))
                        table.ensure(start + B)
                        if len(table.pages) > before:
                            grows.append((table, before))
                    except CacheExhaustedError:
                        failed.append(slot)
                        continue
                    grown.append((slot, start))
                table.row(table_feed[slot])
                pos_feed[slot] = start + np.arange(B, dtype=np.int32)
                end_feed[slot] = start + B - 1
                live.append(slot)
            if failed:
                self._rollback(cows, grows)
                self._update_gauges()
                raise CacheExhaustedError(
                    'KV page pool exhausted for slot(s) %s'
                    % ','.join(map(str, failed)), slots=failed)
            self._block_grown.update(grown)
            live_feed[live] = 1
            carry_feed[[s for s in live if s not in carry]] = 0
            # rows still masked going in, as the host knows them: a
            # fresh block's by its ids, a carried one's by the schedule
            # (or, behind a synchronous step, by the device's count)
            going_in = {
                slot: self._block_masked.get(slot, 0) if slot in carry
                else int(np.sum(tokens[slot] == self.block_mask_id))
                for slot in live}
            masked_in = sum(going_in.values())
            commit = [slot for slot in commit if slot in live]
            ev.attrs['pages_read'] = pages_read = \
                sum(int(end_feed[slot]) // pt + 1 for slot in live)
            ev.attrs['overlapped'] = int(overlapped)
            ev.attrs['carried'] = len(carry)
            ev.attrs['block_rows'] = len(live) * B
            ev.attrs['live_tokens'] = live_tokens = int(sum(
                int(end_feed[slot]) + 1 for slot in live))
            ev.attrs['masked_rows'] = masked_in
            ev.attrs['commit_lanes'] = len(commit)
            _decode_pages_read.inc(pages_read)
            feed = {'block_tokens': tokens,
                    'block_prev_ids': self._last_block[0],
                    'block_carry': carry_feed,
                    'block_positions': pos_feed,
                    'block_ends': end_feed,
                    'block_page_table': table_feed,
                    'block_live': live_feed,
                    'block_transfer': transfer_feed}
        if not self._copy_compiled:
            # as in front of the first decode step: the copy program
            # compiles where the step's program does
            with RecordEvent('paged.cow.compile'):
                self._copy_pages(())
            self._copy_compiled = True
        self._fork_pages(cows)
        out = self._exe.run(self._pair.decode_program, feed=feed,
                            fetch_list=self._pair.decode_fetches,
                            scope=self._scope, return_numpy=False)
        logits, ids, left = out[0], out[1], out[-1]
        if len(out) > 3:
            self._moe_queue.append((True, out[2]))
            self.moe_counters(leave=64)
        with RecordEvent('paged.decode.book'):
            for table, _idx, (src, _dst) in cows:
                table.pool.unref(src)
            for slot in live:
                # the rows still masked behind this pass, by the schedule
                # (exact under the static rules)
                self._block_masked[slot] = max(
                    0, going_in[slot] - int(transfer_feed[slot]))
            for slot in commit:
                self._tables[slot].length = int(starts[slot]) + B
                del self._block_grown[slot], self._block_masked[slot]
            st = self._block_stats
            st['steps'] += 1
            st['passes'] += len(live)
            st['commits'] += len(commit)
            st['rows'] += len(live) * B
            st['masked_rows'] += masked_in
            st['live_tokens'] += live_tokens
            _block_passes.inc(len(live))
            _block_commits.inc(len(commit))
            _block_rows.inc(len(live) * B)
            _block_masked_rows.inc(masked_in)
            _effective_tokens.set(B * st['commits'] / st['steps'])
            self._update_gauges()
        prev, self._last_block, self._in_flight = \
            self._last_block, (ids, left), defer
        with RecordEvent('paged.decode.fetch'):
            if defer:
                return (self._fetch(prev[0]), self._fetch(prev[1])) \
                    if overlapped else None
            ids, left = self._fetch(ids), self._fetch(left)
            for slot in live:
                if slot not in commit:
                    self._block_masked[slot] = int(left[slot])
            if return_logits:
                return ids, left, self._fetch(logits).reshape(
                    S, B, self.vocab)
            return ids, left

    def block_stats(self):
        """Lane-passes, commits, steps, rows carried, rows that went in
        masked and the tokens the passes' lanes held (committed and the
        block's own), since construction (cumulative, like
        `fetch_wait_s`: a reset() leaves them)."""
        return dict(self._block_stats)

    @property
    def in_flight(self):
        """True while a deferred step's ids have not been fetched."""
        return self._in_flight

    def collect(self):
        """Fetch the ids [slots] of the deferred step in flight without
        dispatching another (the last step of a burst, a drain, an
        error path); None if none is. Not a step: no tables, no run.
        A deferred block step's (ids [slots, B], masked [slots])."""
        if not self._in_flight:
            return None
        self._in_flight = False
        with RecordEvent('paged.decode.fetch'):
            if self.block_tokens:
                return tuple(self._fetch(a) for a in self._last_block)
            return self._fetch(self._last_ids)

    def prefill(self, prompts, slot_ids, return_logits=False):
        """Whole-prompt prefill (the parity / generate() path): each
        prompt is streamed chunk by chunk to completion; a slot that
        already holds a stream is released first. Returns first greedy ids
        [len(prompts)] (+ last-position logits with return_logits)."""
        if not prompts or len(prompts) != len(slot_ids):
            raise ValueError('%d prompts for %d slots'
                             % (len(prompts), len(slot_ids)))
        self._refuse_blocks('prefill() (a first token a prompt)')
        out_ids = np.zeros((len(prompts),), np.int64)
        out_logits = []
        for i, (prompt, slot) in enumerate(zip(prompts, slot_ids)):
            slot = int(slot)
            if slot in self._tables:
                self.release(slot)
            self.open_stream(slot, prompt)
            result = None
            while result is None:
                result = self.prefill_step(slot,
                                           return_logits=return_logits)
            if return_logits:
                out_ids[i], logits = result
                out_logits.append(logits)
            else:
                out_ids[i] = result
        if return_logits:
            return out_ids, np.stack(out_logits)
        return out_ids

    def generate(self, prompt, max_new_tokens, eos_id=None, slot=0):
        """Solo greedy generation on one slot (the benchmark / parity
        path; real traffic goes through ServingEngine)."""
        if self.block_tokens:
            return self._generate_blocks(prompt, max_new_tokens, eos_id,
                                         int(slot))
        ids = self.prefill([prompt], [slot])
        tok = int(ids[0])
        out = [tok]
        pos = len(np.asarray(prompt).reshape(-1))
        toks = np.zeros((self.slots,), np.int64)
        poss = np.zeros((self.slots,), np.int32)
        while len(out) < max_new_tokens and tok != eos_id:
            toks[slot] = tok
            poss[slot] = pos
            tok = int(self.decode_step(toks, poss)[slot])
            out.append(tok)
            pos += 1
        return out

    def _generate_blocks(self, prompt, max_new_tokens, eos_id, slot):
        """generate() for a model that generates by diffusion over
        blocks: block after block, each passed over until no row is
        masked and then committed; the tokens past eos_id or the budget
        are dropped."""
        if slot in self._tables:
            self.release(slot)
        self.open_stream(slot, prompt)
        start = None
        while start is None:
            start = self.prefill_step(slot)
        B, S = self.block_tokens, self.slots
        tokens = np.zeros((S, B), np.int64)
        starts = np.zeros((S,), np.int32)
        transfer = np.zeros((S,), np.int32)
        blk = self.new_block(start.start, start.tail)
        out = []
        while True:
            n, commit = blk.plan(self.block_schedule)
            tokens[slot], starts[slot], transfer[slot] = blk.ids, blk.start, n
            ids, left = self.block_step(tokens, starts, transfer, [slot],
                                        commit=[slot] if commit else ())
            blk.ids = [int(t) for t in ids[slot]]
            if not commit:
                blk.passed(n, left[slot])
                continue
            for tok in blk.ids[blk.fixed:]:
                out.append(tok)
                if len(out) >= max_new_tokens or tok == eos_id:
                    return out
            blk = self.new_block(blk.start + B)
