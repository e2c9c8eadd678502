"""Host-side IO ops: feed / fetch / print / save / load / save_combine /
load_combine / assign-from-host (reference paddle/fluid/operators/{feed_op.cc,
fetch_op.cc, print_op.cc, save_op.cc:66, load_op.cc, save_combine_op.cc,
load_combine_op.cc}).

These run on the host between jitted device segments -- the executor
partitions each block into maximal device segments separated by host ops
(executor.py), the TPU-native equivalent of the reference's per-op host
dispatch for these op types.

Tensor file format: a 4-byte magic + JSON header (dtype/shape) + raw
little-endian bytes, one tensor per entry; `save_combine` packs many entries
into one file. This replaces the reference's version+proto header binary
format (save_op.cc SerializeToStream) with the same capability.

The tensor file path is a pipeline (PR 50). A save has three stages -- the
device-to-host copy, the serialisation, the file -- and a load the same
three backwards. An op alone runs them one after another. Under
`io.save_vars` / `io.load_vars` the ops of one call share a `SaveStream` /
`LoadStream` (carried by the io program), and the stages run beside each
other:

- save: the device copies of the variables to come are started
  (`copy_to_host_async`) up to `_WINDOW_BYTES` ahead of the op that is
  writing, so the device-to-host link works while the op's thread is in
  `write`, and the op's `np.asarray` finds its copy done; `write_tensor`
  writes the array's own buffer (no `tobytes` copy). The file is written
  by the op's thread, one file at a time: on the machine the benchmark
  runs on four writers at once wrote at a third of one writer's rate, and
  one writer thread beside the op's was no faster than none (PERF.md
  section 6, PR 50): the copies run in the runtime's own threads.
- load: `read_tensor` reads into the array it returns (`readinto`); in a
  process with one device a variable's host-to-device copy is started as
  its entry arrives (`jax.device_put` to the executor's device, 64-bit
  dtypes left on the host: what `Executor`'s pin of a host-resident
  persistable does at a program's first run, which then finds a device
  array and does nothing), and runs beside the next file's read.

The format, the bytes of every file and the set of files are what the ops
alone write: a file from either path loads in the other.
"""
from __future__ import annotations

import collections
import json
import os
import struct
import time

import numpy as np

from ..registry import register_op

_MAGIC = b'PTT1'   # paddle-tpu tensor v1

# bytes of one save whose device copy has been started and whose file is
# not closed yet: what a save may hold of the model on the host beside
# the device's copy (one variable is always let through, however large)
_WINDOW_BYTES = 2 << 30


def _raw(arr):
    """The bytes of a C-contiguous array as a flat uint8 view (a
    memoryview refuses bfloat16 and the other ml_dtypes; a view does
    not)."""
    return arr.reshape(-1).view(np.uint8)


def write_tensor(f, arr):
    """One entry: magic, header, the array's own buffer. Returns the
    bytes written."""
    arr = np.ascontiguousarray(arr)
    header = json.dumps({'dtype': arr.dtype.name,
                         'shape': list(arr.shape)}).encode('utf-8')
    f.write(_MAGIC + struct.pack('<I', len(header)) + header)
    f.write(_raw(arr))
    return 8 + len(header) + arr.nbytes


def read_tensor(f):
    """One entry, read into the array that is returned (writable)."""
    magic = f.read(4)
    if magic != _MAGIC:
        raise ValueError('bad tensor file magic: %r' % magic)
    (hlen,) = struct.unpack('<I', f.read(4))
    header = json.loads(f.read(hlen).decode('utf-8'))
    arr = np.empty(tuple(header['shape']), np.dtype(header['dtype']))
    buf, got = _raw(arr), 0
    while got < buf.size:
        n = f.readinto(buf[got:])
        if not n:
            raise ValueError('tensor file ends after %d of %d bytes'
                             % (got, buf.size))
        got += n
    return arr


class SaveStream(object):
    """The save ops of one `io.save_vars` call: the device copies of the
    variables to come run beside the write of the one in the op's hands.
    `values` are (name, scope value) in op order."""

    def __init__(self, values=()):
        # what is no array (None, a list) is the op's to refuse or convert
        self._ahead = collections.deque(
            (name, value, getattr(value, 'nbytes', 0))
            for name, value in values)
        self._held = {}               # copy started, entry not in its file
        self._held_bytes = 0
        self.bytes = self.files = 0
        self.copy_wait = 0.0
        self.held_max = 0

    def fetch(self, name, get):
        """`get(name)`, the variable on the host, with the copies behind
        it started as far as the window has room. Everything before
        `name` is written, so `name` itself always has."""
        while self._ahead:
            ahead, value, size = self._ahead[0]
            if self._held and self._held_bytes + size > _WINDOW_BYTES:
                break
            self._ahead.popleft()
            self._held[ahead] = size
            self._held_bytes += size
            self.held_max = max(self.held_max, self._held_bytes)
            # what is not fully addressable is gathered by host_value,
            # in the op; a host value has nothing to copy
            if getattr(value, 'is_fully_addressable', False):
                value.copy_to_host_async()
        t0 = time.perf_counter()
        arr = get(name)
        self.copy_wait += time.perf_counter() - t0
        return arr

    def written(self, name, nbytes):
        """`name`'s entry is in its file: its bytes leave the window."""
        self._held_bytes -= self._held.pop(name, 0)
        self.bytes += nbytes


class LoadStream(object):
    """The load ops of one `io.load_vars` call: a variable's device copy
    starts as its entry arrives, and the next entry is read beside it.
    `device` is None where loaded values stay on the host."""

    def __init__(self, device=None):
        self._device = device
        self.bytes = self.files = 0

    def place(self, arr):
        """Start `arr`'s host-to-device copy: the same `device_put`, and
        the same exclusion of 64-bit dtypes (x64 is off: they would be
        narrowed), as the executor's pin of a host-resident
        persistable."""
        if self._device is None or \
                arr.dtype in (np.int64, np.uint64, np.float64):
            return arr
        import jax
        return jax.device_put(arr, self._device)

    def count(self, f):
        """The file's size and one more file, for io.load.*."""
        self.bytes += os.fstat(f.fileno()).st_size
        self.files += 1


def _stream(ctx, alone):
    """The SaveStream / LoadStream that io.save_vars / io.load_vars hung
    on the program this op runs in; for an op alone (layers.load, a
    program of the user's own) one of its own, `alone()`, which starts
    nothing ahead and leaves a loaded value on the host."""
    return getattr(ctx.block.program, '_io_stream', None) or alone()


# -- feed/fetch are pure markers; the executor consumes them directly -------
register_op('feed', host=True, no_grad=True)
register_op('fetch', host=True, no_grad=True)


def _print_emit(ctx, op):
    import sys
    x = np.asarray(ctx.get(op.single_input('In')))
    msg = op.attr('message', '')
    first_n = op.attr('first_n', -1)
    count = op.attrs.setdefault('__print_count__', 0)
    op.attrs['__print_count__'] = count + 1
    if first_n > 0 and count >= first_n:
        pass
    else:
        parts = [msg] if msg else []
        if op.attr('print_tensor_name', True):
            parts.append('Variable: %s' % op.single_input('In'))
        if op.attr('print_tensor_shape', True):
            parts.append('shape: %s' % (list(x.shape),))
        if op.attr('print_tensor_dtype', True):
            parts.append('dtype: %s' % x.dtype)
        parts.append('data: %s' % np.array2string(x, threshold=20))
        out = ('\n'.join(parts)) + '\n'
        (sys.stderr if op.attr('print_phase', 'both') else sys.stdout).write(out)
    if op.output('Out'):
        ctx.set(op.single_output('Out'), x)


register_op('print', emit=_print_emit, host=True, no_grad=True)


def _save_emit(ctx, op):
    path = op.attr('file_path')
    overwrite = op.attr('overwrite', True)
    if os.path.exists(path) and not overwrite:
        raise RuntimeError('%s exists and overwrite=False' % path)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    name = op.single_input('X')
    stream = _stream(ctx, SaveStream)
    arr = stream.fetch(name, ctx.get)
    if op.attr('save_as_fp16', False):
        arr = arr.astype(np.float16)
    with open(path, 'wb') as f:
        stream.written(name, write_tensor(f, arr))
    stream.files += 1


register_op('save', emit=_save_emit, host=True, no_grad=True)


def _load_emit(ctx, op):
    path = op.attr('file_path')
    stream = _stream(ctx, LoadStream)
    with open(path, 'rb') as f:
        arr = read_tensor(f)
        stream.count(f)
    if op.attr('load_as_fp16', False):
        arr = arr.astype(np.float16)
    ctx.set_raw(op.single_output('Out'), stream.place(arr))


register_op('load', emit=_load_emit, host=True, no_grad=True)


def _save_combine_emit(ctx, op):
    path = op.attr('file_path')
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    stream = _stream(ctx, SaveStream)
    with open(path, 'wb') as f:
        for name in op.input('X'):
            arr = stream.fetch(name, ctx.get)
            if op.attr('save_as_fp16', False):
                arr = arr.astype(np.float16)
            stream.written(name, write_tensor(f, arr))
    stream.files += 1


register_op('save_combine', emit=_save_combine_emit, host=True, no_grad=True)


def _load_combine_emit(ctx, op):
    path = op.attr('file_path')
    stream = _stream(ctx, LoadStream)
    with open(path, 'rb') as f:
        for name in op.output('Out'):
            ctx.set_raw(name, stream.place(read_tensor(f)))
        stream.count(f)


register_op('load_combine', emit=_load_combine_emit, host=True, no_grad=True)


def _delete_var_emit(ctx, op):
    for name in op.input('X'):
        ctx.delete(name)


register_op('delete_var', emit=_delete_var_emit, host=True, no_grad=True)


def _read_emit(ctx, op):
    """Pop one batch from the named py_reader (reference read op +
    blocking-queue pop). Values are set raw: with double buffering they
    are jax.Arrays already resident on device, and the following jitted
    segment consumes them without any host copy."""
    from ..reader.pipeline import get_reader
    values = get_reader(op.attr('reader_name')).read()
    outs = op.output('Out')
    if len(values) != len(outs):
        raise ValueError('py_reader %r yields %d slots, program expects %d'
                         % (op.attr('reader_name'), len(values), len(outs)))
    for name, val in zip(outs, values):
        ctx.set_raw(name, val)


register_op('read', emit=_read_emit, host=True, no_grad=True)
