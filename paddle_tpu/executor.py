"""Executor: jit-compiles whole program blocks to XLA.

TPU-native re-design of the reference C++ Executor
(paddle/fluid/framework/executor.cc: Prepare :294, hot loop :332-339). The
reference interprets a block op-by-op, dispatching each op to a per-device
kernel -- per-op host overhead the TPU cannot tolerate. Here `Prepare`
partitions a block into maximal *device segments* separated by host ops
(save/load/print/feed/fetch), composes each segment's op emitters into one
Python function over traced JAX values, and `jax.jit`s it with persistable
state donated -- so a whole training step (forward + backward + optimizer
update) is ONE XLA executable with in-place parameter buffers in HBM. This is
exactly the BASELINE.json north star: "Executor jit-compiles ProgramDesc
blocks to XLA HLO instead of dispatching per-op CUDA kernels".

Compile cache: keyed on (program identity, mutation version, block, feed
shape/dtype signature, fetch names) -- the analog of the reference Python
Executor's program cache (executor.py:374) plus XLA's own executable cache.
"""
from __future__ import annotations

import contextlib
import hashlib
import time

import numpy as np

import jax
import jax.numpy as jnp

from . import registry
from .framework import default_main_program, Program, Variable

__all__ = ['Executor', 'Scope', 'global_scope', 'scope_guard',
           '_switch_scope', 'CPUPlace', 'TPUPlace', 'XLAPlace',
           'CUDAPlace', 'fetch_var', 'OpExecutionError']


class OpExecutionError(RuntimeError):
    """An op failed during lowering/execution, annotated with the op's
    identity and its declared I/O (the PADDLE_ENFORCE-style context of
    reference platform/enforce.h:253 + operator.cc error wrapping — a
    user with a shape bug in a 200-op program gets the offending op
    named, not a bare JAX traceback)."""


def _describe_op(op, block, pos=None):
    def slot_str(mapping):
        parts = []
        for slot, names in mapping.items():
            descs = []
            for n in names:
                try:
                    v = block.var_recursive(n)
                    descs.append('%s%s' % (n, list(v.shape)
                                           if v.shape is not None else ''))
                except KeyError:
                    descs.append(n)
            parts.append('%s=[%s]' % (slot, ', '.join(descs)))
        return '; '.join(parts)
    where = ('op #%d ' % pos) if pos is not None else 'op '
    return ('%s%r in block %d\n  inputs:  %s\n  outputs: %s'
            % (where, op.type, block.idx, slot_str(op.inputs),
               slot_str(op.outputs)))


def _passthrough_exception(e):
    """Exceptions that are control flow, not op failures — never wrap."""
    from .reader.pipeline import EOFException
    return isinstance(e, (OpExecutionError, EOFException))


def _wrap_op_error(e, op, block, pos=None):
    return OpExecutionError(
        'Error running %s\n  cause: %s: %s'
        % (_describe_op(op, block, pos), type(e).__name__, e))


# ---------------------------------------------------------------------------
# Places (reference paddle/fluid/platform/place.h:78 boost::variant<...>)
# ---------------------------------------------------------------------------

class Place(object):
    platform = None

    def __init__(self, device_id=0):
        self.device_id = device_id

    def jax_device(self):
        devs = (jax.devices(self.platform) if self.platform
                else jax.devices())
        if not 0 <= self.device_id < len(devs):
            raise IndexError(
                '%r: JAX sees %d %s device(s)'
                % (self, len(devs), devs[0].platform))
        return devs[self.device_id]

    def __repr__(self):
        return '%s(%d)' % (type(self).__name__, self.device_id)

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))


class CPUPlace(Place):
    platform = 'cpu'


class TPUPlace(Place):
    """A device of JAX's default backend -- the analog of fluid.CUDAPlace
    and the north star's fluid.XLAPlace. JAX itself falls back to the CPU
    when it finds no accelerator, so this place does not prove a chip:
    entry points that measure one call obs.perf.require_tpu() first."""
    platform = None


# reference-compatible aliases: scripts say fluid.CUDAPlace(0) / XLAPlace(0)
XLAPlace = TPUPlace
CUDAPlace = TPUPlace
CUDAPinnedPlace = CPUPlace    # pinned host staging is PJRT's job here


# ---------------------------------------------------------------------------
# Scope (reference paddle/fluid/framework/scope.h:39): name -> runtime value.
# Values are jax.Arrays (device-resident) or host numpy for host-only vars.
# ---------------------------------------------------------------------------

class Scope(object):
    def __init__(self, parent=None):
        self._vars = {}
        self.parent = parent
        self._kids = []

    def var(self, name):
        """Find-or-create (reference Scope::Var)."""
        if name not in self._vars:
            self._vars[name] = None
        return self._vars.get(name)

    def find_var(self, name):
        s = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has_var(self, name):
        s = self
        while s is not None:
            if name in s._vars:
                return True
            s = s.parent
        return False

    def set_var(self, name, value):
        self._vars[name] = value

    def erase(self, name):
        self._vars.pop(name, None)

    def new_scope(self):
        kid = Scope(parent=self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids = []

    def local_var_names(self):
        return list(self._vars)


_global_scope = Scope()


def global_scope():
    return _global_scope


def _switch_scope(scope):
    """Swap the global scope, returning the previous one (reference
    executor.py:39 — scripts use it for manual scope juggling where
    scope_guard's context shape does not fit)."""
    global _global_scope
    prev, _global_scope = _global_scope, scope
    return prev


@contextlib.contextmanager
def scope_guard(scope):
    prev = _switch_scope(scope)
    try:
        yield
    finally:
        _switch_scope(prev)


def fetch_var(name, scope=None, return_numpy=True):
    scope = scope or global_scope()
    val = scope.find_var(name)
    if val is None:
        raise KeyError('var %r not found in scope' % name)
    return np.asarray(val) if return_numpy else val


def pad_lod_to_batch(flat, lod_level0_offsets):
    """Flat LoD rows [N, ...] + level-0 offsets -> (padded [B, T, ...],
    lengths [B] int32). The padded-batch lowering of the reference's
    no-padding LoD batching (lod_tensor.h:58); masks/lengths carry the
    raggedness instead of ragged shapes (XLA needs static shapes)."""
    offs = list(lod_level0_offsets)
    lens = np.diff(offs).astype('int32')
    B, T = len(lens), (int(lens.max()) if len(lens) else 0)
    padded = np.zeros((B, max(T, 1)) + flat.shape[1:], dtype=flat.dtype)
    for b in range(B):
        padded[b, :lens[b]] = flat[offs[b]:offs[b + 1]]
    return padded, lens


def _expand_sequence_feeds(program, feed):
    """Expand LoD feeds into the padded + '@SEQ_LEN' companion pair."""
    from .lod_tensor import LoDTensor
    out = {}
    for name, value in feed.items():
        var = program.global_block().vars.get(name)
        if var is None or var.lod_level == 0:
            out[name] = value
            continue
        lens_name = name + '@SEQ_LEN'
        if isinstance(value, LoDTensor) and value.lod():
            lod = value.lod()
            if len(lod) != 1:
                raise NotImplementedError(
                    'only lod_level=1 feeds are supported on TPU '
                    '(got %d levels for %r)' % (len(lod), name))
            padded, lens = pad_lod_to_batch(value.numpy(), lod[0])
            out[name] = padded
            out.setdefault(lens_name, lens)
        elif isinstance(value, tuple) and len(value) == 2:
            padded, lens = value
            out[name] = np.asarray(padded)
            out.setdefault(lens_name, np.asarray(lens, dtype='int32'))
        else:
            arr = np.asarray(value)
            declared = len(var.shape or ())
            if arr.ndim != declared + 1:
                raise ValueError(
                    'feed %r is a lod_level=%d var: feed a LoDTensor, a '
                    '(padded, lengths) tuple, or a padded array of rank %d '
                    '(got rank %d)' % (name, var.lod_level, declared + 1,
                                       arr.ndim))
            out[name] = arr
            out.setdefault(lens_name,
                           np.full((arr.shape[0],), arr.shape[1], 'int32'))
    return out


# ---------------------------------------------------------------------------
# Emit contexts
# ---------------------------------------------------------------------------

class EmitContext(object):
    """Traced-value environment handed to op emitters during lowering.

    _op_index: globally-unique index for RNG folding (synthetic inside
    sub-blocks). _block_pos: the op's position within ctx.block.ops (used
    for IR-level constant folding, e.g. tensor-array indices)."""

    __slots__ = ('env', 'block', 'rng_key', 'is_test', '_op_index',
                 '_block_pos', '_fold_limits', 'mesh', 'amp',
                 'bn_local_stats')

    def __init__(self, env, block, rng_key, is_test, amp=False):
        self.env = env
        self.block = block
        self.rng_key = rng_key
        self.is_test = is_test
        self.amp = amp
        self._op_index = 0
        self._block_pos = 0
        # block idx -> op-position limit for IR constant folding: inside a
        # sub-block, ancestor blocks may only be scanned up to the
        # enclosing control-flow op's position (ops after it haven't
        # "happened" yet)
        self._fold_limits = {}
        # device mesh for sharding_constraint emitters; None on a plain
        # single-device Executor (ParallelExecutor sets its Mesh)
        self.mesh = None
        # per-executor BuildStrategy.bn_local_stats override (None =
        # follow the global flag); see ops/nn_ops.py _bn_local_mode
        self.bn_local_stats = None

    def get(self, name):
        try:
            return self.env[name]
        except KeyError:
            raise KeyError(
                'var %r is not available on device; produced ops must come '
                'before consumers in the block' % name)

    def set(self, name, value):
        self.env[name] = value

    def var(self, name):
        return self.block.var_recursive(name)

    def rng(self, op):
        if self.rng_key is None:
            raise RuntimeError('op %s needs RNG but none was threaded'
                               % op.type)
        return jax.random.fold_in(self.rng_key, self._op_index)


def host_value(value):
    """`value` whole on the host. An array sharded over several
    processes (the state of a multi-trainer mesh) is gathered first: a
    collective, so every trainer reads it (a fetch, a save)."""
    if isinstance(value, jax.Array) and not value.is_fully_addressable:
        if value.is_fully_replicated:
            return np.asarray(value.addressable_data(0))
        from jax.experimental import multihost_utils
        return np.asarray(
            multihost_utils.process_allgather(value, tiled=True))
    return np.asarray(value)


class HostContext(object):
    """Host-side environment for host ops (print/save/load/...)."""

    def __init__(self, scope, block):
        self.scope = scope
        self.block = block
        self.is_test = False

    def get(self, name):
        val = self.scope.find_var(name)
        if val is None:
            raise KeyError('host op input %r not found in scope' % name)
        return host_value(val)

    def get_raw(self, name):
        """Like get() but without numpy coercion — for host ops consuming
        structured values (SelectedRows gradients in the send op)."""
        val = self.scope.find_var(name)
        if val is None:
            raise KeyError('host op input %r not found in scope' % name)
        return val

    def set(self, name, value):
        self.scope.set_var(name, np.asarray(value))

    def set_raw(self, name, value):
        self.scope.set_var(name, value)

    def delete(self, name):
        self.scope.erase(name)

    def var(self, name):
        return self.block.var_recursive(name)

    def rng(self, op):
        raise RuntimeError('host ops have no device RNG')


# ---------------------------------------------------------------------------
# Prepared program: segments + metadata
# ---------------------------------------------------------------------------

class _DeviceSegment(object):
    __slots__ = ('ops', 'op_offsets', 'in_names', 'out_names', 'jitted',
                 'needs_rng', '_arg_struct')

    def __init__(self, ops, op_offsets):
        self.ops = ops
        self.op_offsets = op_offsets  # global op indices (stable rng folding)
        self.in_names = []
        self.out_names = []
        self.jitted = None
        self.needs_rng = False
        self._arg_struct = None   # set on first run; see _run_prepared


class _HostStep(object):
    __slots__ = ('op',)

    def __init__(self, op):
        self.op = op


class PreparedProgram(object):
    """Analog of reference ExecutorPrepareContext (executor.h:28)."""

    def __init__(self, program, block_id, feed_names, fetch_names,
                 donate=True):
        self.program = program
        self.block = program.blocks[block_id]
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        # donate=False for pserver optimize blocks: the RPC threads may
        # serve a parameter concurrently with the next async update, so
        # buffers must not be invalidated in place
        self.donate = donate
        # perf observatory (obs/perf.py): fingerprint tags this
        # prepared program's exe.run and xla.compile spans
        self.fingerprint = None
        self.steps = []          # list of _DeviceSegment | _HostStep
        self._build_segments()
        self._analyze_dataflow()

    def _build_segments(self):
        cur_ops, cur_offsets = [], []
        for idx, op in enumerate(self.block.ops):
            if op.type in ('feed', 'fetch'):
                continue
            opdef = registry._REGISTRY.get(op.type)
            if opdef is None or opdef.emit is None:
                raise KeyError('op %r has no emitter registered' % op.type)
            if opdef.host:
                if cur_ops:
                    self.steps.append(_DeviceSegment(cur_ops, cur_offsets))
                    cur_ops, cur_offsets = [], []
                self.steps.append(_HostStep(op))
            else:
                cur_ops.append(op)
                cur_offsets.append(idx)
        if cur_ops:
            self.steps.append(_DeviceSegment(cur_ops, cur_offsets))

    def _analyze_dataflow(self):
        """Per-segment inputs (read-before-write) and live outputs (written
        and needed by later steps / fetches / persistable state)."""
        persistable = {name for name, var in self.block.vars.items()
                       if var.persistable}
        # also persistables from the global block (sub-block case)
        b = self.block
        while b.parent_block is not None:
            b = b.parent_block
            persistable |= {n for n, v in b.vars.items() if v.persistable}

        step_reads, step_writes = [], []
        for step in self.steps:
            if isinstance(step, _DeviceSegment):
                reads, writes = set(), set()
                for op in step.ops:
                    for n in op.input_arg_names():
                        if n not in writes:
                            reads.add(n)
                    writes.update(op.output_arg_names())
                step_reads.append(reads)
                step_writes.append(writes)
            else:
                step_reads.append(set(step.op.input_arg_names()))
                step_writes.append(set(step.op.output_arg_names()))

        fetch_set = set(self.fetch_names)
        for i, step in enumerate(self.steps):
            if not isinstance(step, _DeviceSegment):
                continue
            later_reads = set()
            for j in range(i + 1, len(self.steps)):
                later_reads |= step_reads[j]
            writes = step_writes[i]
            step.in_names = sorted(step_reads[i])
            step.out_names = sorted(
                (writes & (later_reads | fetch_set | persistable)))
            step.needs_rng = any(
                self._op_is_stateful(op) for op in step.ops)

    def _op_is_stateful(self, op):
        """stateful (RNG-using) check, recursing into control-flow
        sub-blocks (dropout inside an RNN step still needs the key)."""
        if registry._REGISTRY[op.type].stateful:
            return True
        sub_idx = op.attr('sub_block', None) if op.attrs else None
        if sub_idx is not None:
            sub = self.program.blocks[sub_idx]
            return any(self._op_is_stateful(sop) for sop in sub.ops)
        return False


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

import weakref

_LIVE_EXECUTORS = weakref.WeakSet()


def all_compiled_hlo_texts():
    """Compiled HLO of every device segment run so far by any live
    Executor — the instruction→op_name metadata source the profiler
    joins against xplane device events (profiler.py op attribution;
    reference analog: device_tracer.cc correlating CUPTI records to op
    annotations)."""
    texts = []
    for exe in list(_LIVE_EXECUTORS):
        texts.extend(exe.compiled_hlo_texts())
    return texts


class Executor(object):
    def __init__(self, place=None):
        self.place = place if place is not None else TPUPlace()
        # device resolved lazily: constructing an Executor must not touch
        # the JAX backend (a later ParallelExecutor(num_trainers>1) in the
        # same script still needs to run jax.distributed.initialize first)
        self._device = None
        self._prepared_cache = {}
        self._step = 0
        self._base_key = None
        # device segments jit-compiled by this executor (monotonic):
        # serving asserts the decode program compiles exactly once
        # across a generation loop (jit_cache_stats)
        self._compile_count = 0
        # per-executor mirror of the xla.jit_cache.{hit,miss} telemetry
        # counters: one hit/miss per device-segment dispatch (misses ==
        # compiled_segments outside check_nan_inf mode)
        self._segment_hits = 0
        self._segment_misses = 0
        _LIVE_EXECUTORS.add(self)

    def jit_cache_stats(self):
        """{'prepared_programs', 'compiled_segments', 'segment_hits',
        'segment_misses'} — compiled_segments is monotonic, so a
        steady-state serving loop proves jit-cache hits by observing it
        stay constant across N decode steps; hits/misses count every
        device-segment dispatch (ParallelExecutor inherits all four —
        SPMD and pipeline paths feed the same counters)."""
        return {'prepared_programs': len(self._prepared_cache),
                'compiled_segments': self._compile_count,
                'segment_hits': self._segment_hits,
                'segment_misses': self._segment_misses}

    def compiled_hlo_texts(self):
        """Optimized-HLO text of each compiled device segment, re-lowered
        from the stashed abstract arg signature. A segment that fails to
        re-lower raises: callers assert on what the text contains, and a
        dropped segment would read as a pass."""
        texts = []
        for prepared in self._prepared_cache.values():
            for step in prepared.steps:
                if isinstance(step, _DeviceSegment) \
                        and step.jitted is not None \
                        and step._arg_struct is not None:
                    texts.append(step.jitted.lower(*step._arg_struct)
                                 .compile().as_text())
        return texts

    @property
    def device(self):
        if self._device is None:
            self._device = self.place.jax_device()
        return self._device

    # -- rng ---------------------------------------------------------------
    def _rng_key(self, program):
        seed = program.random_seed
        if self._base_key is None or seed != getattr(self, '_seed_used', None):
            if seed == 0:
                seed = np.random.randint(0, 2**31 - 1)
            self._base_key = jax.random.PRNGKey(seed)
            self._realized_seed = int(seed)   # checkpointable (Trainer)
            self._seed_used = program.random_seed
        return jax.random.fold_in(self._base_key, self._step)

    # -- public API (reference python executor.py:374 Executor.run) --------
    def run(self, program=None, feed=None, fetch_list=None,
            feed_var_name='feed', fetch_var_name='fetch', scope=None,
            return_numpy=True, use_program_cache=True):
        from .obs import perf as _perf
        from .profiler import RecordEvent
        t0_perf = _perf.step_begin()
        program = program or default_main_program()
        if not isinstance(program, Program):
            raise TypeError('Executor.run expects a Program')
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or global_scope()

        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in fetch_list]

        with RecordEvent('exe.run', n_feeds=len(feed)) as run_ev:
            with RecordEvent('exe.feed'):
                feed_arrays = self._place_feeds(program, feed)
            with RecordEvent('exe.prepare'):
                feed_sig = tuple(sorted(
                    (n, a.shape, str(a.dtype))
                    for n, a in feed_arrays.items()))
                cache_key = (program._uid, program._version, 0, feed_sig,
                             tuple(fetch_names))
                prepared = self._prepared_cache.get(cache_key) \
                    if use_program_cache else None
                if prepared is None:
                    prepared = PreparedProgram(program, 0,
                                               feed_arrays.keys(),
                                               fetch_names)
                    if use_program_cache:
                        self._prepared_cache[cache_key] = prepared
                if prepared.fingerprint is None:
                    prepared.fingerprint = hashlib.md5(
                        repr(cache_key).encode()).hexdigest()[:12]
            run_ev.attrs['fingerprint'] = prepared.fingerprint

            result = self._run_prepared(prepared, feed_arrays, fetch_names,
                                        scope, program)
            self._step += 1
            if return_numpy and result:
                # the host fetch is the wait for the device: only a run
                # that fetched something has a latency worth the name
                with RecordEvent('exe.fetch'):
                    result = [self._to_numpy(r) for r in result]
        if t0_perf is not None:
            _perf.step_end(t0_perf, device=self.device, scope=scope,
                           fetched=return_numpy and bool(result))
        return result

    def _place_feeds(self, program, feed):
        """name -> placed array for every fed value (converted to the
        declared dtype; a device-resident value stays on the device)."""
        feed_arrays = {}
        feed = _expand_sequence_feeds(program, feed)
        for name, value in feed.items():
            from .lod_tensor import LoDTensor
            if isinstance(value, LoDTensor):
                value = value.numpy()
            if isinstance(value, jax.Array):
                # already device-resident (e.g. a pre-placed benchmark batch
                # or double-buffered reader output): hand it to the feed
                # placer without a host round-trip, casting on device if the
                # declared var dtype differs (canonicalized: x64 is off).
                var = program.global_block().vars.get(name)
                if var is not None and var.dtype is not None and \
                        var.dtype != 'bfloat16':
                    want = jax.dtypes.canonicalize_dtype(np.dtype(var.dtype))
                    if value.dtype != want:
                        value = value.astype(want)
                feed_arrays[name] = self._put_feed(name, value)
                continue
            arr = np.asarray(value)
            var = program.global_block().vars.get(name)
            if var is not None and var.dtype is not None and \
                    arr.dtype != np.dtype(var.dtype) and \
                    var.dtype != 'bfloat16':
                arr = arr.astype(var.dtype)
            feed_arrays[name] = self._put_feed(name, arr)
        return feed_arrays

    def _to_numpy(self, value):
        """One fetched result on the host, whole (host_value gathers what
        a multi-trainer mesh holds as shards across processes)."""
        return host_value(value)

    # -- internals ---------------------------------------------------------
    def _run_prepared(self, prepared, feed_arrays, fetch_names, scope,
                      program):
        block = prepared.block
        rng_key = None
        temp_names = set()
        # run-local view: feeds + scope
        local = dict(feed_arrays)

        def read_var(name):
            if name in local:
                return local[name]
            val = scope.find_var(name)
            if val is None:
                raise RuntimeError(
                    'var %r used before initialization -- did you run the '
                    'startup program?' % name)
            # Pin host-resident persistables to the device ONCE: values
            # written by host ops (load_inference_model's load ops, set
            # vars) arrive as numpy; without this, every run() of a
            # program that only READS them (inference!) re-uploads all
            # parameters host-to-device (reference analog: parameters
            # live on-device in the Scope, framework/tensor.h holder
            # semantics).
            # (64-bit dtypes excluded: with x64 off, device_put would
            # narrow them and the narrowed array would leak back into
            # host-side save paths)
            if isinstance(val, np.ndarray) and \
                    val.dtype not in (np.int64, np.uint64, np.float64):
                var = block.vars.get(name)
                if var is not None and var.persistable:
                    val = jax.device_put(val, self.device)
                    scope.set_var(name, val)
            return val

        from . import flags as flags_mod
        from . import profiler as _prof
        check_nan_inf = flags_mod.get_flag('check_nan_inf')

        for step_idx, step in enumerate(prepared.steps):
            if isinstance(step, _HostStep):
                # sync host-visible values then run on host
                hctx = _RunHostContext(scope, local, block)
                try:
                    with _prof.RecordEvent('host_op:%s' % step.op.type):
                        registry._REGISTRY[step.op.type].emit(hctx,
                                                              step.op)
                except Exception as e:
                    if _passthrough_exception(e):
                        raise
                    raise _wrap_op_error(e, step.op, block) from e
                if step.op.type == 'read':
                    # a py_reader batch is a feed: the reader's placer
                    # thread put it on one device, the executor places
                    # it where its step wants it (sharded over dp under
                    # a ParallelExecutor mesh; already there otherwise)
                    for name in step.op.output('Out'):
                        local[name] = self._put_feed(name, local[name])
                continue

            donated = {}
            const = {}
            out_set = set(step.out_names)
            for name in step.in_names:
                val = read_var(name)
                if name in out_set and name not in feed_arrays \
                        and not check_nan_inf:
                    donated[name] = val
                else:
                    const[name] = val
            if step.needs_rng and rng_key is None:
                rng_key = self._rng_key(program)
            key_arg = rng_key if step.needs_rng \
                else jnp.zeros((2,), dtype=jnp.uint32)
            if check_nan_inf:
                # debug mode: ops run eagerly one by one, every output
                # scanned for NaN/Inf (reference operator.cc:749
                # FLAGS_check_nan_inf semantics; unfused and slow).
                # Nothing is donated: buffers stay valid for inspection.
                outs = self._run_segment_checked(step, block, program,
                                                 const, key_arg)
            else:
                from .obs import perf as _perf
                fresh_compile = step.jitted is None
                if fresh_compile:
                    self._segment_misses += 1
                    _perf.jit_cache_miss()
                    step.jitted = self._compile_segment(
                        step, block, program,
                        feed_names=tuple(feed_arrays.keys()),
                        donate=prepared.donate)
                else:
                    self._segment_hits += 1
                    _perf.jit_cache_hit()
                if getattr(step, '_arg_struct', None) is None:
                    # abstract arg signature (with each array's
                    # sharding, so a mesh step re-lowers to the program
                    # that ran) kept so compiled_hlo_texts and the
                    # profiler can read the compiled HLO
                    step._arg_struct = jax.tree.map(
                        lambda a: jax.ShapeDtypeStruct(
                            np.shape(a), getattr(a, 'dtype', None)
                            or np.asarray(a).dtype,
                            sharding=getattr(a, 'sharding', None)),
                        (donated, const, key_arg))
                seg_event = _prof.RecordEvent(
                    'device_segment:%d(%d ops)' % (step_idx, len(step.ops)))
                if fresh_compile and _perf.enabled():
                    # time the FIRST call: trace+lower+XLA-compile (or
                    # the persistent cache's retrieval) all happen
                    # inside it, so this span is the user-visible
                    # compile stall
                    t0c = time.perf_counter()
                    with _perf.compile_span(prepared.fingerprint,
                                            step_idx, len(step.ops)):
                        with seg_event:
                            outs = step.jitted(donated, const, key_arg)
                    _perf.record_compile(time.perf_counter() - t0c)
                else:
                    with seg_event:
                        outs = step.jitted(donated, const, key_arg)
            for name, val in zip(step.out_names, outs):
                local[name] = val
                var = block.vars.get(name)
                if var is None and block.parent_block is not None:
                    # sub-block execution (pserver optimize blocks): the
                    # written var usually lives in an ancestor block
                    try:
                        var = block.var_recursive(name)
                    except KeyError:
                        var = None
                if var is not None and var.persistable:
                    scope.set_var(name, val)
                else:
                    temp_names.add(name)

        results = []
        for name in fetch_names:
            if name in local:
                results.append(local[name])
            else:
                val = scope.find_var(name)
                if val is None:
                    raise KeyError('fetch var %r was not produced' % name)
                results.append(val)
        return results

    def _run_segment_checked(self, segment, block, program, env_in,
                             rng_key):
        """check_nan_inf mode: emit ops eagerly, scan every op's outputs
        for non-finite values, and name the offending op+var."""
        from .selected_rows import SelectedRows
        env = dict(env_in)
        ctx = EmitContext(env, block, rng_key, program._is_test,
                          amp=getattr(program, '_use_bf16', False))
        ctx.mesh = self._emit_mesh()
        ctx.bn_local_stats = getattr(self, '_bn_local_stats', None)
        for op, off in zip(segment.ops, segment.op_offsets):
            ctx._op_index = off
            ctx._block_pos = off
            try:
                registry._REGISTRY[op.type].emit(ctx, op)
            except Exception as e:
                if _passthrough_exception(e):
                    raise
                raise _wrap_op_error(e, op, block, pos=off) from e
            for name in op.output_arg_names():
                val = env.get(name)
                if val is None:
                    continue
                if isinstance(val, SelectedRows):
                    val = val.values
                # jnp.issubdtype, not np: bfloat16 (the AMP activation
                # dtype) is not a subtype of np.floating and would be
                # silently skipped
                dt = getattr(val, 'dtype', None) or np.asarray(val).dtype
                if jnp.issubdtype(dt, jnp.floating) and \
                        not bool(jnp.isfinite(jnp.asarray(val)).all()):
                    raise OpExecutionError(
                        'NaN/Inf detected in output %r of %s'
                        % (name, _describe_op(op, block, pos=off)))
        return tuple(env[n] for n in segment.out_names)

    def _put_feed(self, name, arr):
        """Hook: place one feed array; ParallelExecutor overrides this to
        shard the global batch across the mesh."""
        return jax.device_put(arr, self.device)

    def _jit_options(self, segment, feed_names):
        """Hook: extra jax.jit kwargs (in_shardings for the SPMD path)."""
        return {}

    def _emit_mesh(self):
        """Hook: mesh visible to emitters (sharding constraints)."""
        return None

    def run_block(self, program, block_id, scope, fetch_names=()):
        """Run one block (no feeds) against `scope` — the nested-executor
        entry used by host ops that interpret sub-blocks on the host
        (listen_and_serv optimize blocks; reference
        listen_and_serv_op.cc:148 ParallelExecuteBlocks). Buffers are NOT
        donated: RPC threads may read a parameter concurrently."""
        # 'block_run' tag: run() caches donate=True entries for block 0
        # under a colliding signature — never share them
        cache_key = ('block_run', program._uid, program._version, block_id,
                     tuple(fetch_names))
        prepared = self._prepared_cache.get(cache_key)
        if prepared is None:
            prepared = PreparedProgram(program, block_id, (),
                                       list(fetch_names), donate=False)
            prepared.fingerprint = hashlib.md5(
                repr(cache_key).encode()).hexdigest()[:12]
            self._prepared_cache[cache_key] = prepared
        return self._run_prepared(prepared, {}, list(fetch_names), scope,
                                  program)

    def close(self):
        """Notify pservers this trainer is done (reference
        executor.cc:48 Executor::Close -> SendComplete)."""
        from .distributed.rpc import close_all_clients
        close_all_clients(send_complete=True)

    def _compile_segment(self, segment, block, program, feed_names=(),
                         donate=True):
        is_test = program._is_test
        ops = segment.ops
        offsets = segment.op_offsets
        out_names = segment.out_names

        amp = getattr(program, '_use_bf16', False)

        def seg_fn(donated, const, rng_key):
            env = {}
            env.update(const)
            env.update(donated)
            ctx = EmitContext(env, block, rng_key, is_test, amp=amp)
            ctx.mesh = self._emit_mesh()
            ctx.bn_local_stats = getattr(self, '_bn_local_stats', None)
            for op, off in zip(ops, offsets):
                ctx._op_index = off
                ctx._block_pos = off
                try:
                    # named_scope stamps the IR op identity into XLA
                    # metadata, so xplane device events carry
                    # "<type>.<index>/..." — the per-op device-time
                    # attribution the reference gets from correlating
                    # CUPTI records to op annotations
                    # (platform/device_tracer.cc); consumed by
                    # profiler.py + tools/timeline.py
                    with jax.named_scope('%s.%d' % (op.type, off)):
                        registry._REGISTRY[op.type].emit(ctx, op)
                except Exception as e:
                    if _passthrough_exception(e):
                        raise
                    raise _wrap_op_error(e, op, block, pos=off) from e
            return tuple(env[n] for n in out_names)

        self._compile_count += 1
        return jax.jit(seg_fn, donate_argnums=(0,) if donate else (),
                       **self._jit_options(segment, feed_names))


class _RunHostContext(HostContext):
    """Host context that also sees the run-local (non-persistable) values."""

    def __init__(self, scope, local, block):
        super(_RunHostContext, self).__init__(scope, block)
        self.local = local

    def get(self, name):
        if name in self.local:
            return np.asarray(self.local[name])
        return super(_RunHostContext, self).get(name)

    def get_raw(self, name):
        if name in self.local:
            return self.local[name]
        return super(_RunHostContext, self).get_raw(name)

    def set(self, name, value):
        self.local[name] = np.asarray(value)
        if self.scope.has_var(name) or \
                (name in self.block.vars and self.block.vars[name].persistable):
            self.scope.set_var(name, np.asarray(value))

    def set_raw(self, name, value):
        self.local[name] = value
        if self.scope.has_var(name) or \
                (name in self.block.vars and self.block.vars[name].persistable):
            self.scope.set_var(name, value)
