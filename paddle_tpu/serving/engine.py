"""ServingEngine: continuous batching over a fixed slot pool.

Orca-style iteration-level scheduling, TPU-flavored: the decode step is
ONE compiled program over all `slots` lanes, so admission/eviction
never changes a shape — a request joining the running batch is a
stream opened on a free slot and prefilled in chunks between decode
steps, a finished/cancelled request is a lane the scheduler stops
feeding (its pages released; a lane left out of a step writes to the
null page). Worker threads each own a PagedDecodePredictor clone —
private cache scope + executor, weights shared through the parent
Scope — and pull from one shared queue.

A worker's loop keeps ONE decode step in flight (a predictor that
offers `deferred_decode`; the speculative predictor keeps the serial
loop: fetch, accept, pack, dispatch). An iteration admits, advances
one prefill chunk, packs step
n from what the host knows without step n-1's tokens (positions,
budgets, page tables; a lane that carries on is fed its token on the
device), dispatches it, and only then fetches and accepts step n-1. So
the host's work between two programs runs beside the one in flight,
and the device goes from one decode program to the next. A lane that
ends on what only the token or the clock can tell (an eos_id, a
cancel, a deadline) has been fed to one step too many: that lane
result is dropped (serving.decode_lanes_dropped), its stream is what
the serial loop gives. Whatever needs the lanes whole on the host
collects the step in flight first: a preemption, the handling of
CacheExhaustedError, a weight swap, an iteration with no lane ready,
the worker's exit.

A prompt's first token stays on the device too. The pass that dispatches
a prompt's last chunk does not wait for it: the predictor hands back a
handle on the chunk's id, the lane is ready with its token pending, and
the same pass's decode step takes the slot among its carried lanes (the
predictor writes the chunk's id into the ids that step reads, on the
device). The device then runs the step in flight, the chunk, the next
step, with nothing of the host between them. The host waits for the step
in flight first, as the call always does, and for the chunk's token
directly behind it, in the same pass: the first token is accepted, and
`first_token_at` taken, when the chunk is done, not a step later. What
the first token alone can tell is handled like a deferred step's: a
first token that is the eos_id, a cancel or a deadline ends the lane
after it was fed to that step, whose result for it is dropped; a
request of one token is known from its budget and sits the step out.
Whatever collects the step in flight (above) collects a pending first
token with it, the step first. A resumed request that re-prefills takes
the same path: its last chunk's token is its next one.

What stood in front of a token. Every decode step carries a record made
at its DISPATCH, `(chunks, lanes, sync)`: the prefill chunks this worker
dispatched since it dispatched the step before (0, 1, rarely more: the
device runs them in front of this step), the lanes the step was packed
with (`len(ready)`), and 1 if no decode step was in flight at the
dispatch (a burst's first, the step behind a collect, every step of a
predictor that does not defer), else 0. The step behind a prompt's last
chunk is dispatched behind the chunk with the step before still in
flight: (1, lanes, 0), a `chunk` gap like the one behind any other
chunk. Behind a lone stream's last chunk no step is in flight, only the
chunk: that step records (1, 1, 1) and its gap reads `sync`, as it did,
although the device now runs it straight behind the chunk (it is a
request's own first gap and no other's). The count is taken at
dispatch, not at accept: in the pipelined loop a step's tokens are
accepted after the NEXT pass's chunk has gone out, and that chunk runs
on the device behind the step. The record travels with the step's
tokens and each token that ends a gap (every one but a request's
first) leaves it on its request: `Request.gap_chunks`, `gap_lanes`,
`gap_sync`, entry i for the gap between tokens i and i + 1. A gap's
KIND (`gap_kind`) is `sync` if its step was dispatched with nothing in
flight; else `chunk` if at least one chunk stood in front; else
`plain`. A speculative step that yields several tokens for a lane gives
its record to the first and (0, lanes, 1) to the others; a resumed
request's first gap after a preemption is `sync` (a token that a
re-prefill's last chunk made reads (0, 1, 1)). All of it is recorded
while the registry is on and costs one boolean read while it is off.

A lane that is not one token a step. A predictor of a model that
generates by diffusion over blocks (`block_tokens` = B > 0,
serving/paged.py "Blocks") is asked once a pass, not once a lane, and
its lanes ride the same loop: admission, at most one chunk, ONE step
over the ready lanes (`block_step`). A lane carries its block's state
(`_Lane.block`: where the block starts, the rows still masked, the
passes so far); lanes at different passes of their blocks, and lanes
whose pass is their block's commit, ride in the same step. Most steps
yield a lane nothing; the commit yields the block's tokens at once.
They are accepted together, in order, cut at the eos_id or the budget:
`first_token_at` is the first block's commit, every token of a block
gets that commit's `token_at`, and a "gap" is the time between two
DELIVERIES: `_gap` records one entry a delivery (every one but a
request's first), with the chunks that stood in front of ANY of the
block's passes, the lanes of its commit's step and `sync` if any of its
passes was dispatched with nothing in flight (`Request.delivered_at`
holds the deliveries' times, and the `serve.decode` span's `gaps_ms` the
differences between them, so the lists stay one entry a gap). Under the
static unmasking rules the loop is pipelined like the one-token loop: a
pass is packed from the schedule while the one before runs, the block's
ids stay on the device between passes, and a commit's tokens are
accepted one fetch late (a lane that ended on an eos_id, a cancel or a
deadline was fed to one pass too many, whose result is dropped); under
low_confidence_dynamic only the device knows how many rows a pass
unmasked, and the loop is the serial one. A preempted stream
re-prefills (its tokens so far are whole blocks).

Requests carry a PRIORITY tier (submit(priority=), higher = more
important, 0 = the default lowest tier): one queue per tier, popped
highest-tier first, and the queue-full admission bound applies only to
the lowest tier. On paged-cache exhaustion the engine preempts the
lowest-tier longest-idle stream (serving/preempt.py — swap its pages
to host RAM or drop them for re-prefill) instead of shedding it; the
victim re-enters the FRONT of its own tier and resumes bit-exact.

Telemetry (paddle_tpu/obs/, exported when FLAGS_obs_dir is set):
  serving.requests.{submitted,admitted,completed,cancelled,rejected,
  failed}  counters; serving.tokens_generated / serving.decode_steps /
  serving.decode_steps_overlapped (steps dispatched while the one
  before was in flight) / serving.decode_lanes_dropped /
  serving.first_tokens_carried (prompts whose first token was fed to
  the next decode step on the device: over requests.admitted, the share
  of prompts that did not empty the pipeline) /
  serving.prefills / serving.tokens_behind_prefill /
  serving.tokens_behind_sync (tokens whose gap was of kind `chunk` /
  `sync`: over tokens_generated, the share of generation that another
  request's prefill and the empty pipeline delay) /
  serving.loop.seconds / serving.loop.wait_seconds (the workers' passes,
  and the part of them spent blocked in a step's fetch, the predictor's
  `fetch_wait_s`: 1 - wait / seconds is how far the host is from
  setting the pace) / serving.loop.idle_seconds (a worker's stays with
  no lane and an empty queue, outside any pass: 1 - idle / (idle +
  seconds) is the engine's utilisation)  counters; serving.queue_depth /
  serving.slot_occupancy  gauges; serving.ttft /
  serving.token_latency / serving.decode_batch  histograms (seconds /
  seconds / active lanes per step; the last two take one observation
  a dispatched step, token_latency the host time of its call); plus
  the preemption set from serving/preempt.py (serving.preemptions /
  serving.swapped_pages / serving.swap_bytes / serving.resume_latency /
  serving.preempted_streams).

Spans (profiler.RecordEvent; recorded while the registry is on, and on
the device trace's clock while a profile runs): `serve.iter` is one
pass of a worker's loop (attrs lanes, ready, prefilling, queued; chunk
and step, 0/1: the pass dispatched a prefill chunk / a decode step;
wait_ms, the part of it spent blocked in a fetch, so the span's length
less wait_ms is the host's own section of the pass) with
children `serve.admit` (attr admitted: the streams the pass opened or
resumed, 0 in nearly every pass; a stream's opening is the decoder's
`paged.open` under it), `serve.prefill_tick`, `serve.pack` and
`serve.accept`; the decoder's own spans (serving/paged.py) nest under
it. `serve.idle` is one stay of a worker that has no lane and finds the
queue empty, from the moment it waits to the moment it stops waiting
(ONE span a stay, however often `idle_wait` wakes it; top level on the
worker's thread, like `serve.iter`, and never inside one): the traffic's
idle time, which the loop's own spans must not be charged with. In the pipelined loop `serve.accept` follows the call and holds
the tokens of the step BEFORE the one the call dispatched (none behind
a burst's first step); a step collected without a call leaves a
`paged.decode.fetch` and a `serve.accept` of its own in the iteration
that collected it. The wait for a prompt's first token is a
`paged.prefill.fetch` of the pass that dispatched its last chunk,
behind that pass's decode call. A request that reaches a terminal state leaves
three spans of kind 'request' that share sid = its id: `serve.queue`
(submitted_at -> admitted_at), `serve.prefill` (admitted_at ->
first_token_at) and
`serve.decode` (first_token_at -> done_at; attrs n_prompt,
max_new_tokens, n_tokens, state, prefill_chunks, preemptions,
gaps_ms, the times between its tokens, and beside it gap_chunks,
gap_lanes and gap_sync, what stood in front of each). A preempted
request adds one
`serve.requeue` (preempted -> slot taken again) per resumption.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time

import numpy as np

from ..flags import get_flag
from ..obs import telemetry
from ..obs import trace as _trace
from ..profiler import RecordEvent
from . import preempt as _preempt
from .paging import CacheExhaustedError
from .preempt import HostSwapBudget, pick_victim, preempt_policy

__all__ = ['Request', 'ServingEngine', 'DeadlineExceededError']

QUEUED, RUNNING, DONE, CANCELLED, FAILED = \
    'QUEUED', 'RUNNING', 'DONE', 'CANCELLED', 'FAILED'


class DeadlineExceededError(RuntimeError):
    """The request's end-to-end deadline_ms budget expired before it
    finished. Typed and NON-retryable (serving/replica.py special-cases
    it): retrying elsewhere can only spend more of a budget that is
    already gone. As a lane/queue failure it crosses poll() as a FAILED
    state whose error string leads with this class name — the fleet
    router string-matches it the same way it matches CacheExhausted."""

_submitted = telemetry.counter('serving.requests.submitted')
_admitted = telemetry.counter('serving.requests.admitted')
_completed = telemetry.counter('serving.requests.completed')
_cancelled = telemetry.counter('serving.requests.cancelled')
_rejected = telemetry.counter('serving.requests.rejected')
_failed = telemetry.counter('serving.requests.failed')
_tokens_out = telemetry.counter('serving.tokens_generated')
_decode_steps = telemetry.counter('serving.decode_steps')
_decode_steps_overlapped = telemetry.counter(
    'serving.decode_steps_overlapped')
_decode_lanes_dropped = telemetry.counter('serving.decode_lanes_dropped')
_first_tokens_carried = telemetry.counter('serving.first_tokens_carried')
_prefills = telemetry.counter('serving.prefills')
_tokens_behind_prefill = telemetry.counter('serving.tokens_behind_prefill')
_tokens_behind_sync = telemetry.counter('serving.tokens_behind_sync')
_loop_seconds = telemetry.counter('serving.loop.seconds')
_loop_wait_seconds = telemetry.counter('serving.loop.wait_seconds')
_loop_idle_seconds = telemetry.counter('serving.loop.idle_seconds')
_queue_depth = telemetry.gauge('serving.queue_depth')
_occupancy = telemetry.gauge('serving.slot_occupancy')
_ttft = telemetry.histogram('serving.ttft')
_token_latency = telemetry.histogram('serving.token_latency')
_decode_batch = telemetry.histogram('serving.decode_batch')
_weight_swaps = telemetry.counter('serving.weight_swaps')
_swap_wait = telemetry.histogram('serving.swap_wait')
_cache_exhausted = telemetry.counter('serving.cache_exhausted')
_deadline_expired = telemetry.counter('serving.deadline_expired')
_block_tokens = telemetry.counter('serving.block.tokens')
_passes_per_block = telemetry.histogram('serving.block.passes_per_block')


def gap_kind(chunks, sync):
    """The kind of a token's gap from its step's record (the module's
    docstring): 'sync', 'chunk' or 'plain'."""
    return 'sync' if sync else 'chunk' if chunks else 'plain'


_TOKENS_BEHIND = {'chunk': _tokens_behind_prefill,
                  'sync': _tokens_behind_sync}


class _StepGate(object):
    """Writer-preferring read/write gate around engine steps.

    Every worker iteration (admission prefills + the decode step) runs
    as a READER; a weight install (ParamSubscriber via request_swap)
    runs as the SOLE WRITER. A waiting writer blocks new iterations
    from starting, drains the in-flight ones, runs between two steps,
    and releases — the ISSUE's step-boundary swap contract: in-flight
    decode steps finish on the old weights, the next step reads the
    new ones, and the writer's critical section is only the staged
    pointer swap (never a network pull).

    A worker that keeps one decode step in flight from one iteration to
    the next (the paged engine's pipeline) stays a reader across them
    and leaves only with nothing in flight: it looks at
    `writer_waiting` after every iteration, collects its step and
    leaves. So the writer still runs between two steps, and no step is
    in flight while it does."""

    def __init__(self):
        self._mu = threading.Condition()
        self._readers = 0
        self._writers_waiting = 0
        self._writing = False

    def acquire_read(self):
        with self._mu:
            while self._writing or self._writers_waiting:
                self._mu.wait()
            self._readers += 1

    def release_read(self):
        with self._mu:
            self._readers -= 1
            if not self._readers:
                self._mu.notify_all()

    @property
    def writer_waiting(self):
        return self._writers_waiting > 0

    @contextlib.contextmanager
    def read(self):
        self.acquire_read()
        try:
            yield
        finally:
            self.release_read()

    @contextlib.contextmanager
    def exclusive(self):
        with self._mu:
            self._writers_waiting += 1
            while self._writing or self._readers:
                self._mu.wait()
            self._writers_waiting -= 1
            self._writing = True
        try:
            yield
        finally:
            with self._mu:
                self._writing = False
                self._mu.notify_all()


class Request(object):
    """One generation request. tokens grows as the stream decodes;
    wait() blocks until a terminal state (DONE/CANCELLED/FAILED).
    priority is the SLO tier (higher = more important, 0 = the default
    lowest tier — the only tier queue-full admission rejects)."""

    _ids = itertools.count()

    def __init__(self, prompt, max_new_tokens, eos_id, priority=0,
                 deadline_ms=None):
        self.id = next(Request._ids)
        self.prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.priority = int(priority)
        self.state = QUEUED
        self.tokens = []
        self.error = None
        self.snapshot = None          # swapped pages while preempted
        self.preempted_at = None      # set while waiting to resume
        self.submitted_at = time.perf_counter()
        # end-to-end budget, absolute against THIS process's clock from
        # arrival — None (the old-peer / no-key path) means no deadline
        self.deadline_at = None if deadline_ms is None \
            else self.submitted_at + float(deadline_ms) / 1000.0
        # all on perf_counter(), like submitted_at: admitted_at is when
        # a slot was FIRST taken (before open_stream), token_at one
        # reading per accepted token (token_at[0] is first_token_at)
        self.admitted_at = None
        self.first_token_at = None
        self.token_at = []
        # a lane that delivers a block at a time: one reading a delivery
        # (None for a token a step, where token_at says the same)
        self.delivered_at = None
        # what stood in front of the step that made token i + 1 (the
        # module's docstring), one entry a gap while the registry is on
        self.gap_chunks, self.gap_lanes, self.gap_sync = [], [], []
        self._resumed = False         # its next gap spans a preemption
        self.prefill_chunks = 0
        self.preemptions = 0
        self.done_at = None
        self._done = threading.Event()

    def _admitted(self):
        """A slot was taken for this request (again, if it was
        preempted): the queue wait ends at the first."""
        if self.admitted_at is None:
            self.admitted_at = time.perf_counter()

    def _gap(self, rec):
        """One more gap between two tokens, marked by the record of the
        step that made the later one; None for a token that a
        re-prefill's last chunk made."""
        chunks, n_lanes, sync = rec or (0, 1, 1)
        if self._resumed:
            sync, self._resumed = 1, False
        self.gap_chunks.append(chunks)
        self.gap_lanes.append(n_lanes)
        self.gap_sync.append(sync)
        behind = _TOKENS_BEHIND.get(gap_kind(chunks, sync))
        if behind is not None:
            behind.inc()

    def _finish(self, state, error=None):
        self.state = state
        self.error = error
        self.done_at = time.perf_counter()
        if telemetry._enabled:
            self._record_spans()
        self._done.set()

    def _record_spans(self):
        """The request's life as three spans that share sid = id, from
        its own timestamps: a phase it never reached is left out, and
        the one it ended in runs to done_at."""
        marks = [('serve.queue', self.submitted_at),
                 ('serve.prefill', self.admitted_at),
                 ('serve.decode', self.first_token_at)]
        marks = [(n, t) for n, t in marks if t is not None]
        ends = [t for _, t in marks[1:]] + [self.done_at]
        attrs = dict(n_prompt=len(self.prompt),
                     max_new_tokens=self.max_new_tokens,
                     n_tokens=len(self.tokens), state=self.state,
                     prefill_chunks=self.prefill_chunks,
                     preemptions=self.preemptions)
        for (name, t0), t1 in zip(marks, ends):
            if name == 'serve.decode':
                at = self.token_at if self.delivered_at is None \
                    else self.delivered_at
                attrs.update(gaps_ms=[1e3 * (b - a)
                                      for a, b in zip(at, at[1:])],
                             gap_chunks=self.gap_chunks,
                             gap_lanes=self.gap_lanes,
                             gap_sync=self.gap_sync)
            _trace.record_span(name, 'request', self.id, t0, t1, **attrs)

    def wait(self, timeout=None):
        return self._done.wait(timeout)

    def result(self, timeout=None):
        """Block for the generated tokens; raises on FAILED, returns
        the partial stream on CANCELLED."""
        if not self.wait(timeout):
            raise TimeoutError('request %d still %s after %rs'
                               % (self.id, self.state, timeout))
        if self.state == FAILED:
            raise RuntimeError('request %d failed: %s'
                               % (self.id, self.error))
        return list(self.tokens)


class _Lane(object):
    """One occupied slot: the request plus the position its NEXT token
    will be appended at (== absolute position of the token being fed).
    `ready` is False while a paged stream is still prefilling in
    chunks — the lane occupies its slot but sits out decode steps.
    `last_active` (last accepted-token time) is the idleness key the
    preemption policy sorts victims by within a tier. In the pipelined
    loop a lane fed to the decode step in flight has `pos` already at
    the position after that step, and its next token still on the
    device (`tok` is then the last one accepted; none yet for a lane
    whose first token is pending behind its prompt's last chunk).
    A lane of a model that generates by diffusion over blocks: `pos` is
    its committed length (where its block starts), `block` the block's
    state between its passes (serving/paged.BlockState; None until the
    prompt is in), `carry` whether the block's ids are on the device
    (a pass over it was dispatched), `pending` the tokens of commits
    dispatched and not yet accepted, and `front` what stood in front of
    the passes since the last delivery ([chunks, sync])."""
    __slots__ = ('req', 'pos', 'tok', 'ready', 'last_active', 'block',
                 'carry', 'pending', 'front')

    def __init__(self, req, pos, tok, ready=True):
        self.req, self.pos, self.tok = req, pos, tok
        self.ready = ready
        self.last_active = time.perf_counter()
        self.block, self.carry, self.pending = None, False, 0
        self.front = [0, 0]


class ServingEngine(object):
    def __init__(self, predictor, workers=1, max_queue=None,
                 idle_wait=None):
        """predictor: a PagedDecodePredictor (AnalysisPredictor
        .prepare_decoding()); workers > 1 adds clone()-shared-weight
        worker threads, each with its own slot pool."""
        self._predictors = [predictor]
        for _ in range(1, int(workers)):
            self._predictors.append(predictor.clone())
        self._max_queue = int(max_queue
                              or get_flag('serving_max_queue'))
        self._idle_wait = float(idle_wait
                                if idle_wait is not None
                                else get_flag('serving_idle_wait'))
        self._queues = {}             # priority tier -> deque
        self._cond = threading.Condition()
        self._running = False
        self._threads = []
        self._active_total = 0
        self._inflight = {}           # req.id -> RUNNING Request
        self._accepting = True        # False once a drain/stop began
        self._slo = None
        self._gate = _StepGate()
        self._swaps = 0
        self._slot_tokens = {}        # worker idx -> {slot: tokens held}
        self._swap_budget = HostSwapBudget()
        self._preempted = 0           # streams waiting to resume
        self._preemptions_n = 0
        self._resumes_n = 0

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._running:
            return self
        self._running = True
        self._accepting = True
        # serving SLOs (obs/slo.py): when FLAGS_slo_rules is set, a
        # watchdog re-checks TTFT/token-latency percentiles and token
        # rates against the declared thresholds for the engine's
        # lifetime, emitting slo.breach events
        from ..obs import slo as _slo
        self._slo = _slo.watchdog_from_flags()
        if self._slo is not None:
            self._slo.start()
        self._threads = [
            threading.Thread(target=self._worker_loop, args=(i, p),
                             name='serving-worker-%d' % i, daemon=True)
            for i, p in enumerate(self._predictors)]
        for t in self._threads:
            t.start()
        return self

    def drain(self, timeout=None):
        """Block until no queued or running work remains, leaving the
        engine serving. Returns True once idle, False if `timeout`
        expired first (nothing is cancelled — the caller decides
        whether to escalate). On a never-started engine the queue has
        no one to drain it: returns immediately."""
        if not self._threads:
            return not self._qsize_locked() and not self._inflight
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        while True:
            with self._cond:
                if not self._qsize_locked() and not self._inflight:
                    return True
            if deadline is not None and time.monotonic() >= deadline:
                return False
            time.sleep(0.005)

    def stop(self, drain=True, timeout=None):
        """drain=True finishes queued + running requests first;
        drain=False cancels everything still queued. A `timeout` bounds
        the drain: past it the stop ESCALATES — every still-queued and
        still-running request is cancelled (partial tokens stay
        readable) and the workers are joined with a bound instead of
        hanging forever on a stuck stream. Returns True for a clean
        drain, False when the escalation fired."""
        self._accepting = False
        clean = True
        if drain and timeout is not None:
            clean = self.drain(timeout)
        with self._cond:
            if not drain or not clean:
                for q in self._queues.values():
                    while q:
                        req = q.popleft()
                        self._forget_preempted(req)
                        req._finish(CANCELLED)
                        _cancelled.inc()
                if not clean:
                    # running lanes notice the CANCELLED state at the
                    # next step boundary and evict (cancel() semantics)
                    for req in list(self._inflight.values()):
                        if req.state == RUNNING:
                            req.state = CANCELLED
            self._running = False
            self._cond.notify_all()
        join_deadline = None if timeout is None \
            else time.monotonic() + max(5.0, timeout)
        for t in self._threads:
            t.join(None if join_deadline is None
                   else max(0.1, join_deadline - time.monotonic()))
            if t.is_alive():
                # a wedged decode step: the daemon thread dies with the
                # process — surfacing a False beats hanging the caller
                clean = False
        self._threads = []
        if self._slo is not None:
            # final check covers the tail between the last periodic
            # evaluation and drain
            self._slo.stop(final_check=True)
            self._slo = None
        return clean

    close = stop

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop(drain=not any(exc))

    # -- client surface ----------------------------------------------------
    def submit(self, prompt, max_new_tokens=16, eos_id=None,
               priority=0, deadline_ms=None):
        """priority: SLO tier, higher = more important (default 0 =
        the lowest tier). Tiers dequeue highest-first, and the
        queue-full rejection applies only to the lowest tier — shed
        rules cost low-tier latency, never high-tier admission.

        deadline_ms: optional end-to-end budget. An expired request is
        rejected at dequeue (before its prefill is wasted) and an
        expired lane is cancelled between decode steps with its pages
        freed — both FAILED with a typed, non-retryable
        DeadlineExceededError. None = no deadline."""
        prompt = np.asarray(prompt).reshape(-1)
        max_len = self._predictors[0].max_len
        if not 1 <= prompt.size <= max_len:
            _rejected.inc()
            raise ValueError('prompt length %d outside [1, %d] '
                             '(max_len)' % (prompt.size, max_len))
        if max_new_tokens < 1:
            _rejected.inc()
            raise ValueError('max_new_tokens must be >= 1')
        if deadline_ms is not None and float(deadline_ms) <= 0:
            _rejected.inc()
            _deadline_expired.inc()
            raise DeadlineExceededError(
                'deadline_ms %r already spent at submit'
                % (deadline_ms,))
        req = Request(prompt, max_new_tokens, eos_id,
                      priority=priority, deadline_ms=deadline_ms)
        with self._cond:
            if self._running and not self._accepting:
                _rejected.inc()
                raise RuntimeError(
                    'serving engine is draining — submission rejected')
            if req.priority <= 0 and \
                    self._qsize_locked() >= self._max_queue:
                _rejected.inc()
                raise RuntimeError('serving queue full (%d)'
                                   % self._max_queue)
            self._push_locked(req)
        _submitted.inc()
        return req

    def generate(self, prompt, max_new_tokens=16, eos_id=None,
                 timeout=None):
        return self.submit(prompt, max_new_tokens,
                           eos_id=eos_id).result(timeout)

    def cancel(self, req):
        """Mark a request cancelled; a queued one never runs, a running
        one is evicted at the next step boundary (its partial tokens
        remain readable)."""
        if req.state in (QUEUED, RUNNING):
            req.state = CANCELLED
        return req

    def request_swap(self, fn, label='weights'):
        """Run fn() with every worker quiesced at a step boundary and
        return its result. fn must be CHEAP (staged-pointer installs,
        not pulls): it holds up every decode lane while it runs. With
        the engine stopped there are no steps in flight and fn runs
        inline. The wait-for-boundary time lands in serving.swap_wait;
        serving.weight_swaps counts completed swaps."""
        t0 = time.perf_counter()
        if not self._threads:
            out = fn()
            self._swaps += 1
            _weight_swaps.inc()
            return out
        with self._gate.exclusive():
            _swap_wait.observe(time.perf_counter() - t0)
            out = fn()
            self._swaps += 1
            _weight_swaps.inc()
            return out

    # -- disaggregated page shipping (serving/disagg.py) -------------------
    def export_prefix(self, prompt):
        """Gather the longest resident full-page chain for `prompt`
        across workers into host copies (quiesced at a step boundary —
        save_pages reads device pools). None on a cold cache."""
        def _gather():
            best = None
            for p in self._predictors:
                got = p.export_prefix(prompt)
                if got and (best is None
                            or len(got['keys']) > len(best['keys'])):
                    best = got
            return best
        return self.request_swap(_gather, label='export_prefix')

    def install_prefix(self, prompt, keys, data, skip=0):
        """Install shipped pages into worker 0's pool + prefix cache
        (quiesced — restore_pages functionally rewrites device pools).
        Streams admitted by other workers simply re-prefill locally;
        correctness never depends on the install. Returns (installed,
        deduped)."""
        return self.request_swap(
            lambda: self._predictors[0].install_prefix(prompt, keys,
                                                       data, skip=skip),
            label='install_prefix')

    def resident_keys(self, prompt):
        """Worker 0's resident leading chain run for `prompt` (hex) —
        advisory, lock-free (see PagedDecodePredictor.resident_keys)."""
        return self._predictors[0].resident_keys(prompt)

    def prefix_report(self):
        """Drain registered/evicted prefix-chain deltas from every
        worker (merged) — the replica's SRV_HEALTH contribution to the
        fleet prefix directory."""
        new, gone = [], []
        for p in self._predictors:
            got = p.prefix_report()
            new.extend(got['new'])
            gone.extend(got['evicted'])
        return {'new': new, 'evicted': gone}

    def stats(self):
        with self._cond:
            depth = self._qsize_locked()
            preempted = self._preempted
        p0 = self._predictors[0]
        slot_tokens = [dict(self._slot_tokens.get(i, {}))
                       for i in range(len(self._predictors))]
        out = {'queue_depth': depth, 'active': self._active_total,
               'workers': len(self._predictors),
               'slots_per_worker': p0.slots,
               'weight_swaps': self._swaps,
               # preempt-first capacity (serving/preempt.py): lifetime
               # preemptions/resumes, streams currently swapped out or
               # waiting to re-prefill, and host RAM held by swaps
               'preemptions': self._preemptions_n,
               'resumes': self._resumes_n,
               'preempted_streams': preempted,
               'swap_host_bytes': self._swap_budget.used_bytes,
               # mesh-sharded serving (serving/mesh.py): '' and 1 on
               # the single-chip path
               'mesh_shape': getattr(p0, 'mesh_shape', ''),
               'mesh_devices': getattr(p0, 'mesh_devices', 1),
               # per-worker {slot: tokens held} — actual cache pressure,
               # so the fleet router's least-loaded dispatch can weigh
               # a worker near its token capacity over one holding the
               # same lane count of short streams
               'slot_tokens': slot_tokens,
               'cache_tokens': sum(sum(d.values()) for d in slot_tokens),
               'jit': p0.jit_cache_stats()}
        kv = {'pages_in_use': 0, 'pages_free': 0, 'prefix_hits': 0,
              'prefix_misses': 0, 'prefix_pages': 0,
              'prefix_tokens_reused': 0, 'prefix_entries': 0}
        for p in self._predictors:
            for key in kv:
                kv[key] += p.pool_stats()[key]
        kv['page_tokens'] = p0.page_tokens
        kv['num_pages'] = p0.num_pages
        out['kv'] = kv
        out['cache_capacity'] = (len(self._predictors)
                                 * (p0.num_pages - 1) * p0.page_tokens)
        if getattr(p0, 'speculative', False):
            sp = [p.spec_stats() for p in self._predictors]
            drafted = sum(s['draft_tokens'] for s in sp)
            accepted = sum(s['accepted_tokens'] for s in sp)
            steps = sum(s['steps'] for s in sp)
            emitted = sum(s['effective_tokens_per_step'] * s['steps']
                          for s in sp)
            out['spec'] = {
                'spec_k': sp[0]['spec_k'],
                'k_live': sp[0]['k_live'],
                'steps': steps,
                'draft_tokens': drafted,
                'accepted_tokens': accepted,
                'rejected_tokens': drafted - accepted,
                'fallback_steps': sum(s['fallback_steps'] for s in sp),
                'accept_rate': (accepted / drafted if drafted else 0.0)}
            # tokens emitted per verify iteration — the fleet router's
            # effective-throughput weight (1.0 would be plain decode)
            out['effective_tokens_per_step'] = (emitted / steps
                                                if steps else 0.0)
            out['spec']['effective_tokens_per_step'] = \
                out['effective_tokens_per_step']
        return out

    # -- scheduler ---------------------------------------------------------
    def _qsize_locked(self):
        return sum(len(q) for q in self._queues.values())

    def _push_locked(self, req, front=False):
        """Enqueue into the request's own tier (front=True: a
        requeued exhaustion victim or preempted stream resumes ahead
        of its tier's waiting admissions — but never jumps a higher
        tier, which is always drained first)."""
        q = self._queues.get(req.priority)
        if q is None:
            q = self._queues[req.priority] = collections.deque()
        if front:
            q.appendleft(req)
        else:
            q.append(req)
        _queue_depth.set(self._qsize_locked())
        self._cond.notify_all()

    def _pop_next(self):
        with self._cond:
            for prio in sorted(self._queues, reverse=True):
                q = self._queues[prio]
                while q:
                    req = q.popleft()
                    _queue_depth.set(self._qsize_locked())
                    if req.state == CANCELLED:
                        self._forget_preempted(req)
                        req._finish(CANCELLED)
                        _cancelled.inc()
                        continue
                    if req.deadline_at is not None and \
                            time.perf_counter() > req.deadline_at:
                        # expired while queued: reject BEFORE wasting a
                        # prefill on tokens nobody is waiting for
                        self._forget_preempted(req)
                        req._finish(FAILED,
                                    error='DeadlineExceededError: '
                                          'expired in queue')
                        _failed.inc()
                        _deadline_expired.inc()
                        continue
                    return req
        return None

    # -- preemption (serving/preempt.py) -----------------------------------
    def _forget_preempted(self, req):
        """A preempted request leaving the queue for a terminal state:
        give back its host budget and the preempted-streams gauge."""
        snap, req.snapshot = req.snapshot, None
        if snap is not None:
            self._swap_budget.release(snap['nbytes'])
        if req.preempted_at is not None:
            req.preempted_at = None
            with self._cond:
                self._preempted -= 1
            _preempt.preempted_streams.set(self._preempted)

    def _resume(self, req):
        """Preempt -> back-in-a-slot accounting (the request is being
        re-admitted; its snapshot, if any, was already restored)."""
        if req.preempted_at is None:
            return
        now = time.perf_counter()
        _preempt.resume_latency.observe(now - req.preempted_at)
        _trace.record_span('serve.requeue', 'request', req.id,
                           req.preempted_at, now)
        req.preempted_at = None
        req._resumed = True
        with self._cond:
            self._preempted -= 1
            self._resumes_n += 1
        _preempt.preempted_streams.set(self._preempted)

    def _preempt_lane(self, pred, lanes, slot, wstate, policy):
        """Preempt one READY lane: swap its pages to pinned host memory
        (budget permitting) or drop them for re-prefill, release the
        slot, and requeue the request at the FRONT of its own tier.
        Admission then waits (cache_wait) until a live stream releases
        pages, so the victim cannot immediately steal back what it
        just gave up."""
        lane = lanes.pop(slot)
        req = lane.req
        snap = None
        if policy == 'swap' and getattr(pred, 'swappable', True):
            snap = pred.save_stream(slot)
            if self._swap_budget.reserve(snap['nbytes']):
                _preempt.swapped_pages.inc(snap['pages'])
                _preempt.swap_bytes.inc(snap['nbytes'])
            else:
                snap = None       # host budget dry: re-prefill instead
        pred.release(slot)
        self._inflight.pop(req.id, None)
        self._active_total -= 1
        with self._cond:
            self._preempted += 1
            self._preemptions_n += 1
            req.preemptions += 1
            req.state = QUEUED
            req.snapshot = snap
            req.preempted_at = time.perf_counter()
            self._push_locked(req, front=True)
        _preempt.preemptions.inc()
        _preempt.preempted_streams.set(self._preempted)
        wstate['cache_wait'] = True

    def _finish_lane(self, lanes, slot, state, error=None, *, pred,
                     wstate):
        lane = lanes.pop(slot)
        self._inflight.pop(lane.req.id, None)
        lane.req._finish(state, error)
        self._active_total -= 1
        # freed pages un-stick any admission waiting on the pool
        pred.release(slot)
        wstate['cache_wait'] = False
        if state == DONE:
            _completed.inc()
        elif state == CANCELLED:
            _cancelled.inc()
        else:
            _failed.inc()

    def _lane_accept(self, lanes, slot, tok, *, pred, wstate, rec=None):
        """Record one generated token; returns False if the lane is
        done (eos / budget / cancelled) and was evicted. `rec` is the
        record of the decode step that made the token (None: a prefill
        chunk made it)."""
        lane = lanes[slot]
        req = lane.req
        if req.state == CANCELLED:
            self._finish_lane(lanes, slot, CANCELLED, pred=pred,
                              wstate=wstate)
            return False
        now = time.perf_counter()
        req.tokens.append(int(tok))
        req.token_at.append(now)
        _tokens_out.inc()
        if req.first_token_at is None:
            req.first_token_at = now
            _ttft.observe(now - req.submitted_at)
        elif telemetry._enabled:
            req._gap(rec)
        if len(req.tokens) >= req.max_new_tokens or \
                (req.eos_id is not None and int(tok) == req.eos_id):
            self._finish_lane(lanes, slot, DONE, pred=pred,
                              wstate=wstate)
            return False
        lane.tok = int(tok)
        lane.last_active = now
        return True

    def _admit(self, pred, lanes, prefilling, wstate):
        """Admission: open a stream per free slot (a prefix-cache
        match + read-only page adoption — allocates nothing, so
        admission itself can never exhaust the pool) and queue it for
        chunked prefill. While cache_wait is set, a requeued
        exhaustion victim is waiting for a live stream to release
        pages — admitting more streams would only deepen the hole.

        A resuming PREEMPTED stream takes one of two paths: a swap
        snapshot restores its pages device-side before the next decode
        step it joins (bit-exact — float32 bytes round-trip exactly);
        without one, the stream re-prefills (prompt + tokens so far),
        and the final chunk's output token IS its next stream token —
        the fleet-failover contract, equally bit-exact by greedy
        determinism. Returns the streams it opened or resumed (the
        `admitted` of the pass's `serve.admit` span; 0 in nearly every
        pass)."""
        if wstate['cache_wait'] and lanes:
            return 0
        wstate['cache_wait'] = False
        admitted = 0
        free = [s for s in range(pred.slots) if s not in lanes]
        while free:
            req = self._pop_next()
            if req is None:
                break
            slot = free.pop(0)
            # a resumed stream continues from its accumulated tokens;
            # a fresh one has none and seq is just its prompt
            seq = req.prompt + req.tokens
            if req.snapshot is not None:
                try:
                    pred.restore_stream(slot, req.snapshot, prompt=seq)
                except CacheExhaustedError:
                    if lanes:
                        # pool still too tight: back to the tier front
                        # until a live stream releases
                        with self._cond:
                            self._push_locked(req, front=True)
                        wstate['cache_wait'] = True
                        return admitted
                    # nothing live will ever free pages for this
                    # snapshot: drop it and re-prefill instead (the
                    # pool may fit a chunked prefill it cannot fit
                    # whole)
                    self._swap_budget.release(req.snapshot['nbytes'])
                    req.snapshot = None
                except Exception as e:  # noqa: BLE001 — lane-fatal
                    self._forget_preempted(req)
                    req._finish(FAILED, error=repr(e))
                    _failed.inc()
                    continue
                else:
                    self._swap_budget.release(req.snapshot['nbytes'])
                    req.snapshot = None
                    req._admitted()
                    self._resume(req)
                    req.state = RUNNING
                    self._inflight[req.id] = req
                    self._active_total += 1
                    lanes[slot] = _Lane(req, pos=len(seq) - 1,
                                        tok=req.tokens[-1])
                    _admitted.inc()
                    admitted += 1
                    continue
            req.state = RUNNING
            req._admitted()
            self._inflight[req.id] = req
            self._active_total += 1
            try:
                pred.open_stream(slot, seq)
            except Exception as e:  # noqa: BLE001 — lane-fatal only
                self._forget_preempted(req)
                self._inflight.pop(req.id, None)
                req._finish(FAILED, error=repr(e))
                self._active_total -= 1
                _failed.inc()
                continue
            self._resume(req)
            lanes[slot] = _Lane(req, pos=len(seq), tok=0,
                                ready=False)
            prefilling.append(slot)
            _admitted.inc()
            admitted += 1
        return admitted

    def _prefill_tick(self, pred, lanes, prefilling, wstate):
        """Advance chunked prefill by AT MOST one chunk per engine
        iteration — the head-of-line bound: a 4k-token prompt costs
        the live decode lanes one chunk's latency per step, never a
        whole-prompt stall. Pool exhaustion mid-prefill first tries to
        PREEMPT a strictly lower-tier ready lane (the prefilling
        stream keeps its slot and retries the same chunk next
        iteration); with no lower-tier victim, it requeues at the
        front of its OWN tier — never jumping a higher tier's waiting
        admissions — and admission pauses until a live stream releases
        (with no live stream left to wait on, the request can never
        fit and fails with the typed error)."""
        while prefilling:
            slot = prefilling[0]
            lane = lanes.get(slot)
            if lane is None:
                prefilling.popleft()
                continue
            req = lane.req
            if req.state == CANCELLED:
                prefilling.popleft()
                self._finish_lane(lanes, slot, CANCELLED, pred=pred,
                                  wstate=wstate)
                continue
            if req.deadline_at is not None and \
                    time.perf_counter() > req.deadline_at:
                prefilling.popleft()
                self._finish_lane(lanes, slot, FAILED,
                                  error='DeadlineExceededError: '
                                        'expired mid-prefill',
                                  pred=pred, wstate=wstate)
                _deadline_expired.inc()
                continue
            # a deferring predictor does not wait for a prompt's last
            # chunk: it hands back a handle on the token, and this
            # pass's decode step is dispatched behind the chunk
            deferred = getattr(pred, 'deferred_decode', False)
            try:
                out = pred.prefill_step(slot, defer=True) if deferred \
                    else pred.prefill_step(slot)
            except CacheExhaustedError as e:
                # a victim's tokens and pages must agree: the decode
                # step in flight is accepted before one is picked
                self._collect(pred, lanes, wstate)
                _cache_exhausted.inc()
                policy = preempt_policy()
                if policy != 'off':
                    victim = pick_victim(lanes, below=req.priority)
                    if victim is not None:
                        # a lower-tier stream gives way; this prefill
                        # keeps its slot and retries the same chunk
                        # next iteration
                        self._preempt_lane(pred, lanes, victim,
                                           wstate, policy)
                        return
                prefilling.popleft()
                lanes.pop(slot)
                pred.release(slot)
                self._inflight.pop(req.id, None)
                self._active_total -= 1
                if lanes:
                    req.state = QUEUED
                    with self._cond:
                        self._push_locked(req, front=True)
                    wstate['cache_wait'] = True
                else:
                    req._finish(FAILED,
                                error='CacheExhaustedError: %s' % e)
                    _failed.inc()
                return
            except Exception as e:  # noqa: BLE001 — lane-fatal only
                prefilling.popleft()
                self._finish_lane(lanes, slot, FAILED, error=repr(e),
                                  pred=pred, wstate=wstate)
                return
            if getattr(out, 'chunk_ran', True):
                _prefills.inc()
                wstate['chunks'] += 1
                req.prefill_chunks += 1
            if out is None:
                return               # more chunks remain — next iteration
            prefilling.popleft()
            lane.ready = True
            if getattr(pred, 'block_tokens', 0):
                # whole blocks are in: the prompt's last tokens open the
                # first generated block, whose passes make the stream's
                # first logits
                lane.pos = out.start
                lane.block = pred.new_block(out.start, out.tail)
            elif deferred:
                wstate['first'] = (slot, lane, out)
            else:
                self._lane_accept(lanes, slot, int(out), pred=pred,
                                  wstate=wstate)
            return

    def _worker_loop(self, wid, pred):
        lanes = {}                       # slot -> _Lane
        prefilling = collections.deque()  # slots mid-prefill
        # flight: the (slot, lane) pairs fed to the decode step that is
        # dispatched and not yet accepted (the pipelined loop), or None,
        # and rec, that step's record of what stood in front of it.
        # chunks / steps: the prefill chunks and decode steps this
        # worker has dispatched; chunks_seen: chunks at the last step.
        # first: (slot, lane, handle) of the prompt whose last chunk
        # this pass dispatched and whose first token is not fetched yet
        wstate = {'cache_wait': False, 'flight': None, 'rec': None,
                  'first': None, 'wid': wid, 'chunks': 0,
                  'chunks_seen': 0, 'steps': 0}
        tokens = np.zeros((pred.slots,), np.int64)
        positions = np.zeros((pred.slots,), np.int32)
        reading = False
        try:
            while True:
                with self._cond:
                    if self._running and not self._qsize_locked() \
                            and not lanes:
                        # the traffic's idle time, not the loop's: ONE
                        # span a stay, however often _idle_wait wakes it
                        with RecordEvent('serve.idle'):
                            t0 = time.perf_counter()
                            while self._running and not lanes \
                                    and not self._qsize_locked():
                                self._cond.wait(self._idle_wait)
                            _loop_idle_seconds.inc(time.perf_counter() - t0)
                    if not self._running and not self._qsize_locked() \
                            and not lanes:
                        return
                # one gate-read section per iteration: a waiting weight
                # swap (request_swap) runs between iterations — i.e. at
                # a step boundary — never under a prefill or decode
                # step. With a decode step in flight the section runs on
                # into the next iteration; a swap that waits has the
                # step collected and accepted first, so it sees none.
                if not reading:
                    self._gate.acquire_read()
                    reading = True
                with RecordEvent('serve.iter') as it:
                    timed = telemetry._enabled
                    if timed:
                        t0, wait0 = time.perf_counter(), pred.fetch_wait_s
                        chunks0, steps0 = wstate['chunks'], wstate['steps']
                    self._iterate(it, wid, pred, lanes, prefilling, wstate,
                                  tokens, positions)
                    if self._gate.writer_waiting:
                        self._collect(pred, lanes, wstate)
                    if timed:
                        wait = pred.fetch_wait_s - wait0
                        _loop_seconds.inc(time.perf_counter() - t0)
                        _loop_wait_seconds.inc(wait)
                        it.attrs.update(wait_ms=1e3 * wait,
                                        chunk=wstate['chunks'] - chunks0,
                                        step=wstate['steps'] - steps0)
                if wstate['flight'] is None:
                    self._gate.release_read()
                    reading = False
        finally:
            # on any way out, nothing stays in flight on the predictor
            # and no first token unfetched
            self._collect(pred, lanes, wstate)
            if reading:
                self._gate.release_read()

    def _collect(self, pred, lanes, wstate):
        """Fetch and accept the decode step in flight, if there is one,
        without dispatching another, and behind it a prompt's pending
        first token: before anything that needs the lanes' state whole
        on the host (a preemption's save_stream, the handling of
        CacheExhaustedError, a weight swap), and when no lane is ready
        for a next step."""
        flight, wstate['flight'] = wstate['flight'], None
        if flight is None and wstate['first'] is None:
            return
        if flight is not None:
            try:
                ids = pred.collect()
            except Exception as e:   # noqa: BLE001 — engine survives
                for slot, lane, *_ in flight:
                    if lanes.get(slot) is lane:
                        self._finish_lane(lanes, slot, FAILED,
                                          error=repr(e), pred=pred,
                                          wstate=wstate)
            else:
                with RecordEvent('serve.accept') as ev:
                    if getattr(pred, 'block_tokens', 0):
                        ev.attrs['blocks'] = self._accept_blocks(
                            flight, ids, pred, lanes, wstate)
                    else:
                        self._accept_flight(flight, wstate['rec'], ids,
                                            pred, lanes, wstate)
        self._accept_first(pred, lanes, wstate)
        self._report(wstate['wid'], lanes)

    def _accept_first(self, pred, lanes, wstate):
        """Wait for the first token of the prompt whose last chunk this
        pass dispatched, if one is pending, and accept it:
        `first_token_at` is the end of that wait. The device ran the
        step in flight before the chunk, so the caller has fetched that
        step first."""
        first, wstate['first'] = wstate['first'], None
        if first is None:
            return
        slot, lane, handle = first
        try:
            tok = pred.first_token(handle)
        except Exception as e:   # noqa: BLE001 — lane-fatal only
            if lanes.get(slot) is lane:
                self._finish_lane(lanes, slot, FAILED, error=repr(e),
                                  pred=pred, wstate=wstate)
            return
        if lanes.get(slot) is lane:
            self._lane_accept(lanes, slot, tok, pred=pred, wstate=wstate)

    def _report(self, wid, lanes):
        """The occupancy gauge and this worker's {slot: tokens held},
        again after every round of evictions, so that an idle worker
        reports zero held tokens and not its last busy state."""
        _occupancy.set(self._active_total)
        self._slot_tokens[wid] = {s: ln.pos for s, ln in lanes.items()}

    def _accept_flight(self, flight, rec, ids, pred, lanes, wstate):
        """The tokens of a deferred step, one fetch late, each with the
        step's record `rec`. A lane that
        ended meanwhile on what only the token or the clock could tell
        (an eos_id, a cancel, a deadline) was fed to this step all the
        same: its result is dropped here. Its pages were released with
        the step's write into them still queued; the device executes
        that write before any later program that could own them
        (serving/paged.py release())."""
        for slot, lane in flight:
            if lanes.get(slot) is not lane:
                _decode_lanes_dropped.inc()
                continue
            self._lane_accept(lanes, slot, int(ids[slot]), pred=pred,
                              wstate=wstate, rec=rec)

    def _step_exhausted(self, e, pred, lanes, wstate):
        """A step raised CacheExhaustedError: nothing of it ran; the one
        in flight is accepted first, so that a victim's tokens and pages
        agree."""
        self._collect(pred, lanes, wstate)
        # preempt-first (serving/preempt.py): instead of
        # failing the named victims, the lowest-tier
        # longest-idle stream gives its pages back (swap or
        # drop) and every survivor retries the IDENTICAL
        # step next iteration — the transactional rollback
        # already undid this call's allocations, so the
        # retry is bit-exact. policy 'off' restores the
        # legacy typed shed (the fleet router retries it
        # cross-replica).
        _cache_exhausted.inc()
        policy = preempt_policy()
        preempted = False
        if policy != 'off':
            for slot in list(e.slots):
                lane = lanes.get(slot)
                if lane is not None and lane.pos + max(
                        1, getattr(pred, 'block_tokens', 0)) > pred.window:
                    # outgrew its own page window: no
                    # preemption can ever make it fit
                    self._finish_lane(
                        lanes, slot, FAILED,
                        error='CacheExhaustedError: %s' % e,
                        pred=pred, wstate=wstate)
            victim = pick_victim(lanes)
            if victim is not None:
                self._preempt_lane(pred, lanes, victim,
                                   wstate, policy)
                preempted = True
        if not preempted:
            for slot in e.slots:
                if slot in lanes:
                    self._finish_lane(
                        lanes, slot, FAILED,
                        error='CacheExhaustedError: %s' % e,
                        pred=pred, wstate=wstate)

    def _step_failed(self, e, ready, pred, lanes, wstate):
        """A step raised something else: the one in flight is accepted,
        the lanes this one was packed with fail."""
        self._collect(pred, lanes, wstate)
        for slot in ready:
            if slot in lanes:
                self._finish_lane(lanes, slot, FAILED, error=repr(e),
                                  pred=pred, wstate=wstate)

    def _block_pass(self, wid, pred, lanes, ready, wstate):
        """The step of a pass for a predictor whose lanes hold blocks
        (the module's docstring, "A lane that is not one token a step"):
        one block_step over the ready lanes, each at its own pass of its
        block, and the acceptance of what the step (or, pipelined, the
        step before) handed back."""
        B, S = pred.block_tokens, pred.slots
        deferred = pred.block_defers and \
            getattr(pred, 'deferred_decode', False)
        buf = wstate.get('block_feed')
        if buf is None:
            buf = wstate['block_feed'] = (np.zeros((S, B), np.int64),
                                          np.zeros((S,), np.int32),
                                          np.zeros((S,), np.int32))
        tokens, starts, transfer = buf
        plan = []
        with RecordEvent('serve.pack'):
            for slot in ready:
                lane = lanes[slot]
                if lane.block is None:
                    lane.block, lane.carry = pred.new_block(lane.pos), False
                blk = lane.block
                n, commit = blk.plan(pred.block_schedule)
                tokens[slot], starts[slot] = blk.ids, blk.start
                transfer[slot] = n
                plan.append((slot, lane, blk, n, commit))
        t0 = time.perf_counter()
        try:
            out = pred.block_step(
                tokens, starts, transfer, ready,
                carry=[s for s, ln, *_ in plan if ln.carry],
                commit=[s for s, *_, commit in plan if commit],
                defer=deferred)
        except CacheExhaustedError as e:
            self._step_exhausted(e, pred, lanes, wstate)
            return
        except Exception as e:   # noqa: BLE001 — engine survives
            self._step_failed(e, ready, pred, lanes, wstate)
            return
        _decode_steps.inc()
        _token_latency.observe(time.perf_counter() - t0)
        _decode_batch.observe(len(ready))
        wstate['steps'] += 1
        chunks = wstate['chunks'] - wstate['chunks_seen']
        sync = int(not deferred or wstate['flight'] is None)
        wstate['chunks_seen'] = wstate['chunks']
        fed = []
        for slot, lane, blk, n, commit in plan:
            lane.front[0] += chunks
            lane.front[1] |= sync
            rec = None
            if commit:
                # the tokens are accepted when the ids arrive; the next
                # block opens behind this one, all of it masked
                _passes_per_block.observe(blk.passes + 1)
                lane.pending += B - blk.fixed
                lane.pos = blk.start + B
                lane.block, lane.carry = None, False
                rec, lane.front = (lane.front[0], len(ready),
                                   lane.front[1]), [0, 0]
            else:
                blk.passed(n)
                lane.carry = True
            fed.append((slot, lane, blk, commit, rec))
        if deferred:
            flight, wstate['flight'] = wstate['flight'], fed
            if flight is not None:
                _decode_steps_overlapped.inc()
                with RecordEvent('serve.accept') as ev:
                    ev.attrs['blocks'] = self._accept_blocks(
                        flight, out, pred, lanes, wstate)
            if not any(lanes.get(s) is ln for s, ln, *_ in fed):
                self._collect(pred, lanes, wstate)
        else:
            with RecordEvent('serve.accept') as ev:
                ev.attrs['blocks'] = self._accept_blocks(
                    fed, out, pred, lanes, wstate)
        self._report(wid, lanes)

    def _accept_blocks(self, flight, out, pred, lanes, wstate):
        """What a block step handed back, (ids [slots, B], masked
        [slots]), for the lanes it was fed (`flight`): a lane that ended
        meanwhile is passed over (its result is dropped, as a deferred
        decode step's is), a cancelled one ends here, a commit's tokens
        are delivered, and a synchronous pass's count of rows still
        masked corrects the schedule's (they differ under
        low_confidence_dynamic alone). Returns the blocks delivered."""
        ids, masked = out
        delivered = 0
        for slot, lane, blk, commit, rec in flight:
            if lanes.get(slot) is not lane:
                _decode_lanes_dropped.inc()
                continue
            if lane.req.state == CANCELLED:
                self._finish_lane(lanes, slot, CANCELLED, pred=pred,
                                  wstate=wstate)
                continue
            if not commit:
                if lane.block is blk and wstate['flight'] is None:
                    # the newest step's own result: the device's count,
                    # and ids the host can feed again
                    blk.masked = int(masked[slot])
                    blk.ids = [int(t) for t in ids[slot]]
                    lane.carry = False
                continue
            delivered += 1
            lane.pending -= pred.block_tokens - blk.fixed
            self._deliver(lanes, slot, [int(t) for t in ids[slot]][blk.fixed:],
                          rec, pred=pred, wstate=wstate)
        return delivered

    def _deliver(self, lanes, slot, toks, rec, *, pred, wstate):
        """Record one block's tokens, accepted together at its commit,
        in order, cut at the budget or behind an eos_id; False if the
        lane is done and was evicted."""
        lane = lanes[slot]
        req = lane.req
        toks = toks[:req.max_new_tokens - len(req.tokens)]
        if req.eos_id is not None and req.eos_id in toks:
            toks = toks[:toks.index(req.eos_id) + 1]
        now = time.perf_counter()
        req.tokens.extend(toks)
        req.token_at.extend([now] * len(toks))
        _tokens_out.inc(len(toks))
        _block_tokens.inc(len(toks))
        if req.first_token_at is None:
            req.first_token_at = now
            req.delivered_at = [now]
            _ttft.observe(now - req.submitted_at)
        else:
            req.delivered_at.append(now)
            if telemetry._enabled:
                req._gap(rec)
        if len(req.tokens) >= req.max_new_tokens or \
                (req.eos_id is not None and toks[-1] == req.eos_id):
            self._finish_lane(lanes, slot, DONE, pred=pred, wstate=wstate)
            return False
        lane.tok = toks[-1]
        lane.last_active = now
        return True

    def _iterate(self, it, wid, pred, lanes, prefilling, wstate, tokens,
                 positions):
        """One pass of a worker's loop, inside its `serve.iter` span
        `it`: admission, at most one prefill chunk, then one decode
        step over the ready lanes and the acceptance of its tokens.

        With a predictor that defers (`deferred_decode`: not the
        speculative one) the pass is one stage of a one-deep pipeline: it
        packs step n from what the host knows without step n-1's
        tokens (positions, budgets; a lane that carries on takes its
        token on the device, from step n-1 or from its prompt's last
        chunk, which this pass dispatched), dispatches it, accepts the
        tokens of step n-1, which the same call hands back, and then
        waits for that chunk's token. The host's work between two
        programs then runs beside the one in flight."""
        # a speculative predictor's step is one draft->verify iteration
        # (serving/speculative.py): same feed ABI, but each live lane
        # gets 1..k+1 tokens back instead of exactly one — and where a
        # lane stands after a step depends on how many, so it cannot be
        # packed ahead
        speculative = getattr(pred, 'speculative', False)
        deferred = getattr(pred, 'deferred_decode', False)
        with RecordEvent('serve.admit') as ev:
            ev.attrs['admitted'] = self._admit(pred, lanes, prefilling,
                                               wstate)
        with RecordEvent('serve.prefill_tick'):
            self._prefill_tick(pred, lanes, prefilling, wstate)
        self._report(wid, lanes)
        # deadline check at the step boundary: an expired ready
        # lane is evicted (pages freed) before it buys another
        # decode step. Prefilling lanes are checked at the
        # prefill-queue head (_prefill_tick), matching how
        # cancellation reaches them. A lane whose first token is
        # pending is looked at a pass later, with the token its prefill
        # earned.
        first = None if wstate['first'] is None else wstate['first'][0]
        now = time.perf_counter()
        for slot, ln in list(lanes.items()):
            if ln.ready and ln.req.deadline_at is not None \
                    and now > ln.req.deadline_at and slot != first:
                self._finish_lane(
                    lanes, slot, FAILED,
                    error='DeadlineExceededError: expired '
                          'mid-decode',
                    pred=pred, wstate=wstate)
                _deadline_expired.inc()
        # the lanes whose next token is still on the device, in the step
        # in flight or in the last chunk just dispatched (the pipelined
        # loop alone has any); one whose budget ends with that token
        # sits this step out
        block = getattr(pred, 'block_tokens', 0)
        carried = {s for s, ln, *_ in wstate['flight'] or ()
                   if lanes.get(s) is ln}
        if first is not None:
            carried.add(first)
        ready = [s for s, ln in lanes.items() if ln.ready and
                 len(ln.req.tokens) + (ln.pending if block else s in carried)
                 < ln.req.max_new_tokens]
        if telemetry._enabled:
            with self._cond:
                queued = self._qsize_locked()
            it.attrs.update(lanes=len(lanes), ready=len(ready),
                            prefilling=len(prefilling), queued=queued)
        if not ready:
            self._collect(pred, lanes, wstate)
            return
        if block:
            self._block_pass(wid, pred, lanes, ready, wstate)
            return
        with RecordEvent('serve.pack'):
            for slot in ready:
                tokens[slot] = lanes[slot].tok
                positions[slot] = lanes[slot].pos
        t0 = time.perf_counter()
        try:
            if speculative:
                emitted = pred.spec_step(tokens, positions)
            elif deferred:
                ids = pred.decode_step(
                    tokens, positions, lanes=ready, defer=True,
                    carry=[s for s in ready if s in carried])
            else:
                ids = pred.decode_step(tokens, positions)
        except CacheExhaustedError as e:
            self._step_exhausted(e, pred, lanes, wstate)
            return
        except Exception as e:   # noqa: BLE001 — engine survives
            self._step_failed(e, ready, pred, lanes, wstate)
            return
        dt = time.perf_counter() - t0
        _decode_steps.inc()
        _token_latency.observe(dt)
        _decode_batch.observe(len(ready))
        # what stood in front of the step just dispatched (the module's
        # docstring); its tokens take it with them
        wstate['steps'] += 1
        rec = None
        if telemetry._enabled:
            rec = (wstate['chunks'] - wstate['chunks_seen'], len(ready),
                   int(not deferred or wstate['flight'] is None))
        wstate['chunks_seen'] = wstate['chunks']
        if deferred:
            flight, rec_before = wstate['flight'], wstate['rec']
            wstate['flight'] = fed = [(s, lanes[s]) for s in ready]
            wstate['rec'] = rec
            for _slot, lane in fed:
                lane.pos += 1
            if flight is not None:
                _decode_steps_overlapped.inc()
                with RecordEvent('serve.accept'):
                    self._accept_flight(flight, rec_before, ids, pred,
                                        lanes, wstate)
            # the chunk's token, behind the step in flight's: the
            # device ran them in this order
            if first in ready:
                _first_tokens_carried.inc()
            self._accept_first(pred, lanes, wstate)
            if not any(lanes.get(s) is ln for s, ln in fed):
                # every lane of the step just dispatched has ended:
                # nothing waits for it, and the worker may go idle
                self._collect(pred, lanes, wstate)
        else:
            with RecordEvent('serve.accept'):
                if speculative:
                    # per-slot mixed accept lengths in the SAME
                    # iteration: each lane consumes its own emitted
                    # prefix, stopping early on eos/budget/cancel
                    more = rec and (0, len(ready), 1)
                    for slot in ready:
                        for i, tok in enumerate(emitted.get(slot, ())):
                            lanes[slot].pos += 1
                            if not self._lane_accept(
                                    lanes, slot, int(tok), pred=pred,
                                    wstate=wstate,
                                    rec=more if i else rec):
                                break
                else:
                    for slot in ready:
                        lanes[slot].pos += 1
                        self._lane_accept(lanes, slot, int(ids[slot]),
                                          pred=pred, wstate=wstate,
                                          rec=rec)
        self._report(wid, lanes)
