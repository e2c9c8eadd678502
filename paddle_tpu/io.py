"""Model persistence: save/load vars + inference model packaging
(reference python/paddle/fluid/io.py: save_vars:89, save_persistables:252,
load_vars:295, save_inference_model:561, load_inference_model:677).

Like the reference, persistence is expressed as save/load *ops* executed by
the Executor (host ops here), so distributed/sharded variants can rewrite
them; the tensor file format lives in ops/io_ops.py.
"""
from __future__ import annotations

import os
import sys
import time

import jax

from .executor import global_scope
from .framework import (Program, Parameter, Variable, default_main_program,
                        program_guard)
from .flags import get_flag
from .obs import telemetry
from .ops import io_ops
from .profiler import RecordEvent

__all__ = ['save_vars', 'save_params', 'save_persistables', 'load_vars',
           'load_params', 'load_persistables', 'save_inference_model',
           'load_inference_model', 'get_inference_program']

_MODEL_FILENAME = '__model__'

# one save_vars / load_vars call each: bytes and files on disk, wall
# seconds from entry to return, and the seconds the save's ops stood
# waiting for a device copy (near 0: the files set the pace; near
# io.save.seconds: the device-to-host link does)
_SAVE_BYTES = telemetry.counter('io.save.bytes')
_SAVE_FILES = telemetry.counter('io.save.files')
_SAVE_SECONDS = telemetry.counter('io.save.seconds')
_SAVE_COPY_WAIT = telemetry.counter('io.save.copy_wait_seconds')
_LOAD_BYTES = telemetry.counter('io.load.bytes')
_LOAD_FILES = telemetry.counter('io.load.files')
_LOAD_SECONDS = telemetry.counter('io.load.seconds')


def is_persistable(var):
    # cache vars (serving KV rings) are persistable for the executor's
    # scope write-back but are runtime state, not weights: a saved
    # decode program must not try to serialize (or later load) them
    return var.persistable and not getattr(var, 'is_cache', False)


def is_parameter(var):
    return isinstance(var, Parameter)


def _build_io_program(main_program, vars, dirname, filename, op_type):
    prog = Program()
    block = prog.global_block()
    names = []
    for var in vars:
        v = block.create_var(name=var.name, shape=var.shape, dtype=var.dtype,
                             persistable=True)
        names.append(v.name)
        if filename is None:
            block.append_op(
                type=op_type,
                inputs={'X': [v.name]} if op_type == 'save' else {},
                outputs={} if op_type == 'save' else {'Out': [v.name]},
                attrs={'file_path': os.path.join(dirname, v.name)})
    if filename is not None:
        block.append_op(
            type=op_type + '_combine',
            inputs={'X': names} if op_type == 'save' else {},
            outputs={} if op_type == 'save' else {'Out': names},
            attrs={'file_path': os.path.join(dirname, filename)})
    return prog


def _select_vars(main_program, vars, predicate, filter_fn):
    """predicate picks the base var set (persistables, params, ...);
    filter_fn composes on top — the caller's hook to exclude (or keep
    only) some of them without re-stating the base rule."""
    if vars is None:
        vars = [v for v in main_program.list_vars()
                if predicate is None or predicate(v)]
    else:
        vars = [main_program.global_block().var(v) if isinstance(v, str)
                else v for v in vars]
    if filter_fn is not None:
        vars = [v for v in vars if filter_fn(v)]
    return vars


def _io_files(vars, filename):
    return [filename] if filename is not None else [v.name for v in vars]


def save_vars(executor, dirname, main_program=None, vars=None,
              predicate=None, filename=None, filter_fn=None):
    main_program = main_program or default_main_program()
    vars = _select_vars(main_program, vars, predicate, filter_fn)
    prog = _build_io_program(main_program, vars, dirname, filename, 'save')
    t0 = time.perf_counter()
    with RecordEvent('io.save') as ev:
        # the ops of this one program share a pipeline (ops/io_ops.py):
        # the device copies run ahead of the op that writes
        scope = global_scope()
        stream = prog._io_stream = io_ops.SaveStream(
            [(v.name, scope.find_var(v.name)) for v in vars])
        executor.run(prog)
        ev.attrs.update(bytes=stream.bytes, files=stream.files,
                        held_max=stream.held_max)
    _SAVE_BYTES.inc(stream.bytes)
    _SAVE_FILES.inc(stream.files)
    _SAVE_COPY_WAIT.inc(stream.copy_wait)
    _SAVE_SECONDS.inc(time.perf_counter() - t0)
    if get_flag('ckpt_verify', False):
        # record the just-written files in the dir's CHECKPOINT_DIGESTS
        # (merging: __model__ from save_inference_model and a later
        # save_persistables into the same dir share one manifest) —
        # the same verification story as the mesh path
        from .checkpoint import manifest
        manifest.write_digests(dirname, files=_io_files(vars, filename),
                               merge=True)


def save_params(executor, dirname, main_program=None, filename=None,
                filter_fn=None):
    save_vars(executor, dirname, main_program, predicate=is_parameter,
              filename=filename, filter_fn=filter_fn)


def save_persistables(executor, dirname, main_program=None, filename=None,
                      filter_fn=None):
    save_vars(executor, dirname, main_program, predicate=is_persistable,
              filename=filename, filter_fn=filter_fn)


def load_vars(executor, dirname, main_program=None, vars=None,
              predicate=None, filename=None, filter_fn=None):
    main_program = main_program or default_main_program()
    vars = _select_vars(main_program, vars, predicate, filter_fn)
    if get_flag('ckpt_verify', False):
        # verify exactly the files this load is about to read BEFORE
        # any of them reaches the scope; a mismatch raises
        # CheckpointCorruptError naming the var + file
        from .checkpoint import manifest
        names = {v.name for v in vars}
        if manifest.read_digests(dirname) is None:
            sys.stderr.write(
                'WARNING: FLAGS_ckpt_verify set but %s has no %s '
                'manifest (pre-digest save?); loading unverified\n'
                % (dirname, manifest.DIGESTS_FILE))
        else:
            manifest.verify_or_raise(
                dirname, files=_io_files(vars, filename),
                var_of=lambda rel: rel if rel in names else None)
    prog = _build_io_program(main_program, vars, dirname, filename, 'load')
    t0 = time.perf_counter()
    with RecordEvent('io.load') as ev:
        # the mirror of save_vars' pipeline: in a process with one device
        # each variable's copy to the executor's device is started as its
        # file arrives (with more devices a mesh may want it elsewhere:
        # it stays on the host, as an op alone leaves it)
        stream = prog._io_stream = io_ops.LoadStream(
            executor.device if jax.device_count() == 1 else None)
        executor.run(prog)
        ev.attrs.update(bytes=stream.bytes, files=stream.files)
    _LOAD_BYTES.inc(stream.bytes)
    _LOAD_FILES.inc(stream.files)
    _LOAD_SECONDS.inc(time.perf_counter() - t0)


def load_params(executor, dirname, main_program=None, filename=None,
                filter_fn=None):
    load_vars(executor, dirname, main_program, predicate=is_parameter,
              filename=filename, filter_fn=filter_fn)


def load_persistables(executor, dirname, main_program=None, filename=None,
                      filter_fn=None):
    load_vars(executor, dirname, main_program, predicate=is_persistable,
              filename=filename, filter_fn=filter_fn)


def save_inference_model(dirname, feeded_var_names, target_vars, executor,
                         main_program=None, model_filename=None,
                         params_filename=None,
                         export_for_deployment=True):
    """Prune to the inference subgraph + save params (reference io.py:561)."""
    main_program = main_program or default_main_program()
    if isinstance(feeded_var_names, str):
        feeded_var_names = [feeded_var_names]
    if isinstance(target_vars, Variable):
        target_vars = [target_vars]
    os.makedirs(dirname, exist_ok=True)

    pruned = main_program.clone(for_test=True)
    pruned = pruned._prune(target_vars, feeds=feeded_var_names)

    model_path = os.path.join(dirname,
                              model_filename or _MODEL_FILENAME)
    with open(model_path, 'w') as f:
        import json
        f.write(json.dumps({
            'program': pruned.to_json(),
            'feed_names': list(feeded_var_names),
            'fetch_names': [v.name for v in target_vars],
        }))
    save_persistables(executor, dirname, pruned, params_filename)
    return [v.name for v in target_vars]


def load_inference_model(dirname, executor, model_filename=None,
                         params_filename=None, load_params=True):
    """Returns (program, feed_names, fetch_vars) (reference io.py:677).
    load_params=False skips reading weights — for Predictor.clone(),
    whose shared scope already holds them on device."""
    import json
    model_path = os.path.join(dirname, model_filename or _MODEL_FILENAME)
    with open(model_path) as f:
        d = json.loads(f.read())
    program = Program.from_json(d['program'])
    if load_params:
        load_persistables(executor, dirname, program, params_filename)
    fetch_vars = [program.global_block().var(n) for n in d['fetch_names']]
    return program, d['feed_names'], fetch_vars


def get_inference_program(target_vars, main_program=None):
    main_program = main_program or default_main_program()
    pruned = main_program.clone(for_test=True)
    if isinstance(target_vars, Variable):
        target_vars = [target_vars]
    return pruned._prune(target_vars)
