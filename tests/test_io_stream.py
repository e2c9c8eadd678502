"""The tensor file path as a pipeline (ops/io_ops.py SaveStream /
LoadStream under io.save_vars / io.load_vars): same files, same bytes,
same API as the ops alone; device copies ahead of the writing op inside a
bounded window, no `tobytes` copy, a read into the array that is returned,
and in a one-device process a loaded variable's device copy started as its
file arrives.
"""
import io
import json
import os
import struct

import jax
import ml_dtypes
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import checkpoint
from paddle_tpu.checkpoint import manifest as ckpt_manifest
from paddle_tpu.executor import OpExecutionError
from paddle_tpu.obs import telemetry, trace
from paddle_tpu.ops import io_ops


def _parent_form(arr):
    """What the tree before PR 50 wrote for `arr` (write_tensor: magic,
    header, tobytes). np.ascontiguousarray makes a 0-d array [1]: the
    header has said so since the format's first day."""
    arr = np.ascontiguousarray(arr)
    header = json.dumps({'dtype': arr.dtype.name,
                         'shape': list(arr.shape)}).encode('utf-8')
    return b'PTT1' + struct.pack('<I', len(header)) + header + arr.tobytes()


_RNG = np.random.RandomState(50)
_ARRAYS = {
    'float32': _RNG.randn(5, 7).astype('float32'),
    'bfloat16': _RNG.randn(4, 3).astype(ml_dtypes.bfloat16),
    'float16': _RNG.randn(6).astype('float16'),
    'int64': _RNG.randint(-2**40, 2**40, (3, 2)).astype('int64'),
    'int32': _RNG.randint(-99, 99, (2, 2, 2)).astype('int32'),
    'bool': _RNG.rand(9) > 0.5,
    'zero_d': np.float32(2.5),
    'empty': np.zeros((0, 4), 'float32'),
    'transposed': _RNG.randn(3, 5).astype('float32').T,
}


@pytest.fixture
def registry():
    telemetry.enable()
    telemetry.reset()
    trace.clear()
    yield telemetry
    telemetry.disable()


def _io_counters():
    return {k: v for k, v in telemetry.snapshot()['counters'].items()
            if k.startswith('io.')}


def _declare(names_to_arrays):
    """A program that declares one persistable variable an array, and
    the scope that holds them."""
    prog = fluid.Program()
    scope = fluid.global_scope()
    for name, arr in names_to_arrays.items():
        prog.global_block().create_var(
            name=name, shape=list(np.shape(arr)),
            dtype=np.asarray(arr).dtype.name, persistable=True)
        scope.set_var(name, arr)
    return prog


# -- (a) the format is the parent's, byte for byte ---------------------------

@pytest.mark.parametrize('kind', sorted(_ARRAYS))
def test_write_tensor_bytes_are_the_parents(kind):
    arr = _ARRAYS[kind]
    f = io.BytesIO()
    n = io_ops.write_tensor(f, arr)
    assert f.getvalue() == _parent_form(arr)
    assert n == len(f.getvalue())
    f.seek(0)
    back = io_ops.read_tensor(f)
    want = np.ascontiguousarray(arr)
    assert back.dtype == want.dtype and back.shape == want.shape
    assert back.tobytes() == want.tobytes()
    assert back.flags.writeable or back.size == 0


@pytest.mark.parametrize('kind', sorted(_ARRAYS))
def test_save_op_file_is_the_parents_and_loads_back(kind, tmp_path):
    arr = _ARRAYS[kind]
    prog = _declare({'v': arr})
    exe = fluid.Executor(fluid.CPUPlace())
    fluid.io.save_vars(exe, str(tmp_path), prog, vars=['v'])
    with open(str(tmp_path / 'v'), 'rb') as f:
        assert f.read() == _parent_form(arr)
    fluid.global_scope().set_var('v', None)
    fluid.io.load_vars(exe, str(tmp_path), prog, vars=['v'])
    back = np.asarray(fluid.global_scope().find_var('v'))
    want = np.ascontiguousarray(arr)
    assert back.dtype == want.dtype and back.shape == want.shape
    assert back.tobytes() == want.tobytes()


def test_read_tensor_refuses_a_short_file():
    f = io.BytesIO()
    io_ops.write_tensor(f, _ARRAYS['float32'])
    with pytest.raises(ValueError, match='ends after'):
        io_ops.read_tensor(io.BytesIO(f.getvalue()[:-3]))


def test_a_parents_file_loads_and_an_op_alone_reads_the_streams(tmp_path):
    """Either tree's model loads in the other: a file in the parent's
    form loads through load_vars, and what save_vars wrote is read by
    the bare reader the parent had (read(n) + frombuffer)."""
    arr = _ARRAYS['float32']
    with open(str(tmp_path / 'w'), 'wb') as f:
        f.write(_parent_form(arr))
    prog = _declare({'w': np.zeros_like(arr)})
    exe = fluid.Executor(fluid.CPUPlace())
    fluid.io.load_vars(exe, str(tmp_path), prog, vars=['w'])
    np.testing.assert_array_equal(
        np.asarray(fluid.global_scope().find_var('w')), arr)
    out = tmp_path / 'out'
    fluid.io.save_vars(exe, str(out), prog, vars=['w'])
    with open(str(out / 'w'), 'rb') as f:
        assert f.read(4) == b'PTT1'
        (hlen,) = struct.unpack('<I', f.read(4))
        header = json.loads(f.read(hlen))
        data = np.frombuffer(f.read(), header['dtype'])
    np.testing.assert_array_equal(data.reshape(header['shape']), arr)


# -- (b) save_vars returns with every file closed and whole ------------------

def _device_model(n=24):
    arrays = {'p%02d' % i: _RNG.randn(64, 33 + i).astype('float32')
              for i in range(n)}
    placed = {k: jax.device_put(v, jax.devices()[0])
              for k, v in arrays.items()}
    return arrays, _declare(placed)


@pytest.mark.parametrize('filename', [None, 'all_in_one'])
def test_save_vars_of_device_arrays_returns_with_files_whole(
        tmp_path, filename):
    arrays, prog = _device_model()
    exe = fluid.Executor(fluid.TPUPlace())
    fluid.io.save_persistables(exe, str(tmp_path), prog, filename=filename)
    if filename is None:
        assert sorted(os.listdir(str(tmp_path))) == sorted(arrays)
        for name, arr in arrays.items():
            with open(str(tmp_path / name), 'rb') as f:
                assert f.read() == _parent_form(arr)
    else:
        assert os.listdir(str(tmp_path)) == [filename]
        with open(str(tmp_path / filename), 'rb') as f:
            assert f.read() == b''.join(
                _parent_form(arrays[n]) for n in sorted(arrays))
    for name in arrays:
        fluid.global_scope().set_var(name, None)
    fluid.io.load_persistables(exe, str(tmp_path), prog, filename=filename)
    for name, arr in arrays.items():
        np.testing.assert_array_equal(
            np.asarray(fluid.global_scope().find_var(name)), arr)


def test_save_as_fp16_behaves_as_it_did(tmp_path):
    arr = _ARRAYS['float32']
    prog = _declare({'h': arr})
    io_prog = fluid.io._build_io_program(prog, [prog.global_block().var('h')],
                                         str(tmp_path), None, 'save')
    io_prog.global_block().ops[0].attrs['save_as_fp16'] = True
    fluid.Executor(fluid.CPUPlace()).run(io_prog)
    with open(str(tmp_path / 'h'), 'rb') as f:
        assert f.read() == _parent_form(arr.astype('float16'))


# -- (c) a failure comes out of save_vars and names the variable -------------

def test_overwrite_false_on_an_existing_file_raises_naming_the_variable(
        tmp_path, monkeypatch):
    prog = _declare({'keep': _ARRAYS['float32'], 'other': _ARRAYS['int32']})
    exe = fluid.Executor(fluid.CPUPlace())
    fluid.io.save_vars(exe, str(tmp_path), prog, vars=['keep', 'other'])
    before = (tmp_path / 'keep').read_bytes()
    build = fluid.io._build_io_program

    def refuse(*args):
        io_prog = build(*args)
        for op in io_prog.global_block().ops:
            op.attrs['overwrite'] = False
        return io_prog
    monkeypatch.setattr(fluid.io, '_build_io_program', refuse)
    with pytest.raises(OpExecutionError) as ei:
        fluid.io.save_vars(exe, str(tmp_path), prog, vars=['keep', 'other'])
    assert 'keep' in str(ei.value) and 'overwrite=False' in str(ei.value)
    assert (tmp_path / 'keep').read_bytes() == before


def test_an_unwritable_target_raises_naming_the_variable(tmp_path):
    prog = _declare({'a0': _ARRAYS['float32'], 'blocked': _ARRAYS['int32']})
    # a directory stands where the variable's file should be
    os.makedirs(str(tmp_path / 'blocked'))
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(OpExecutionError) as ei:
        fluid.io.save_vars(exe, str(tmp_path), prog, vars=['a0', 'blocked'])
    assert 'blocked' in str(ei.value)
    # the file before it was written and closed all the same
    assert (tmp_path / 'a0').read_bytes() == _parent_form(_ARRAYS['float32'])


def test_a_failed_write_raises_from_save_vars_naming_the_variable(
        tmp_path, monkeypatch):
    prog = _declare({'good': _ARRAYS['float32'], 'bad': _ARRAYS['int32']})
    real = io_ops.write_tensor

    def failing(f, arr):
        if arr.dtype == np.int32:
            raise OSError(28, 'No space left on device')
        return real(f, arr)
    monkeypatch.setattr(io_ops, 'write_tensor', failing)
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(OpExecutionError) as ei:
        fluid.io.save_vars(exe, str(tmp_path), prog, vars=['good', 'bad'])
    assert 'X=[bad' in str(ei.value) and 'No space left' in str(ei.value)
    assert (tmp_path / 'good').read_bytes() == \
        _parent_form(_ARRAYS['float32'])


def test_a_variable_the_scope_lacks_raises_as_it_did(tmp_path):
    prog = _declare({'there': _ARRAYS['float32']})
    prog.global_block().create_var(name='absent', shape=[2],
                                   dtype='float32', persistable=True)
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(OpExecutionError, match="'absent' not found"):
        fluid.io.save_vars(exe, str(tmp_path), prog,
                           vars=['there', 'absent'])


def test_a_missing_file_raises_from_load_vars_naming_the_variable(tmp_path):
    prog = _declare({'here': _ARRAYS['float32'], 'gone': _ARRAYS['int32']})
    exe = fluid.Executor(fluid.CPUPlace())
    fluid.io.save_vars(exe, str(tmp_path), prog, vars=['here', 'gone'])
    os.remove(str(tmp_path / 'gone'))
    with pytest.raises(OpExecutionError) as ei:
        fluid.io.load_vars(exe, str(tmp_path), prog, vars=['here', 'gone'])
    assert 'gone' in str(ei.value) and 'FileNotFoundError' in str(ei.value)


# -- (d) the window bounds what a save holds on the host ---------------------

class _StubArray(object):
    """A device array's face to SaveStream: counts copies started."""
    is_fully_addressable = True
    started = []

    def __init__(self, name, nbytes):
        self.name, self.nbytes = name, nbytes

    def copy_to_host_async(self):
        _StubArray.started.append(self.name)


def test_copies_run_ahead_of_the_writer_inside_the_window(monkeypatch):
    monkeypatch.setattr(io_ops, '_WINDOW_BYTES', 1000)
    _StubArray.started = []
    names = ['s%02d' % i for i in range(20)]
    stream = io_ops.SaveStream([(n, _StubArray(n, 300)) for n in names])
    for i, name in enumerate(names):
        stream.fetch(name, lambda n: n)
        # three of 300 fit a window of 1000: this one and two ahead
        assert _StubArray.started == names[:min(i + 3, len(names))]
        assert stream._held_bytes == sum(stream._held.values()) <= 1000
        stream.written(name, 300)
    assert stream.held_max == 900 and stream.bytes == 6000


def test_a_variable_larger_than_the_window_goes_alone(monkeypatch):
    monkeypatch.setattr(io_ops, '_WINDOW_BYTES', 1000)
    _StubArray.started = []
    sizes = [('small0', 100), ('huge', 5000), ('small1', 100)]
    stream = io_ops.SaveStream([(n, _StubArray(n, s)) for n, s in sizes])
    stream.fetch('small0', lambda n: n)
    assert _StubArray.started == ['small0']      # huge would pass 1000
    stream.written('small0', 100)
    stream.fetch('huge', lambda n: n)
    # let through alone, and nothing rides beside it
    assert _StubArray.started == ['small0', 'huge']
    stream.written('huge', 5000)
    stream.fetch('small1', lambda n: n)
    assert _StubArray.started == ['small0', 'huge', 'small1']
    assert stream.held_max == 5000


def test_host_values_and_strangers_pass_through_the_stream():
    """A numpy value has no copy to start; a name the stream was not
    told of (and whatever is not an array) is the op's to fetch."""
    stream = io_ops.SaveStream([('host', np.ones(3, 'float32')),
                                ('odd', [1, 2, 3])])
    assert stream.fetch('host', lambda n: n) == 'host'
    assert stream.fetch('stranger', lambda n: n) == 'stranger'
    assert stream.held_max == 12


def test_save_vars_of_many_variables_stays_under_the_window(
        tmp_path, monkeypatch, registry):
    arrays, prog = _device_model(40)
    one = 64 * 33 * 4
    monkeypatch.setattr(io_ops, '_WINDOW_BYTES', 6 * one)
    exe = fluid.Executor(fluid.TPUPlace())
    fluid.io.save_persistables(exe, str(tmp_path), prog)
    span, = [s for s in trace.spans() if s['name'] == 'io.save']
    assert one < span['held_max'] <= 6 * one
    assert span['files'] == 40
    assert span['bytes'] == sum(
        os.path.getsize(str(tmp_path / n)) for n in arrays)


# -- (e) FLAGS_ckpt_verify still stands in front of the scope ----------------

@pytest.mark.parametrize('filename', [None, 'all_in_one'])
def test_ckpt_verify_round_trip_and_corruption_before_the_scope(
        tmp_path, filename):
    arrays, prog = _device_model(6)
    exe = fluid.Executor(fluid.TPUPlace())
    fluid.set_flags({'FLAGS_ckpt_verify': True})
    try:
        fluid.io.save_persistables(exe, str(tmp_path), prog,
                                   filename=filename)
        files = [filename] if filename else sorted(arrays)
        assert set(ckpt_manifest.read_digests(str(tmp_path))) == set(files)
        fluid.io.load_persistables(exe, str(tmp_path), prog,
                                   filename=filename)
        victim = str(tmp_path / files[-1])
        blob = bytearray(open(victim, 'rb').read())
        blob[len(blob) // 2] ^= 0x01
        with open(victim, 'wb') as f:
            f.write(bytes(blob))
        marker = object()
        for name in arrays:
            fluid.global_scope().set_var(name, marker)
        with pytest.raises(checkpoint.CheckpointCorruptError) as ei:
            fluid.io.load_persistables(exe, str(tmp_path), prog,
                                       filename=filename)
        assert files[-1] in str(ei.value)
        assert all(fluid.global_scope().find_var(n) is marker
                   for n in arrays)
    finally:
        fluid.set_flags({'FLAGS_ckpt_verify': False})


# -- (f) end to end, and the counters ----------------------------------------

def _fc_model():
    x = fluid.layers.data(name='x', shape=[13], dtype='float32')
    h = fluid.layers.fc(input=x, size=32, act='relu')
    return fluid.layers.fc(input=h, size=3)


def test_saved_model_runs_bit_equal_and_the_counters_count_the_disk(
        tmp_path, registry):
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    out = _fc_model()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    xb = _RNG.randn(8, 13).astype('float32')
    live, = exe.run(feed={'x': xb}, fetch_list=[out])
    telemetry.reset()
    fluid.io.save_inference_model(str(tmp_path), ['x'], [out], exe)
    weights = [f for f in os.listdir(str(tmp_path)) if f != '__model__']
    on_disk = sum(os.path.getsize(str(tmp_path / f)) for f in weights)
    c = _io_counters()
    assert c['io.save.bytes'] == on_disk
    assert c['io.save.files'] == len(weights) == 4
    assert 0 <= c['io.save.copy_wait_seconds'] <= c['io.save.seconds']
    assert c['io.load.files'] == 0

    pred = AnalysisPredictor(AnalysisConfig(str(tmp_path)))
    c = _io_counters()
    assert c['io.load.bytes'] == on_disk
    assert c['io.load.files'] == 4 and c['io.load.seconds'] > 0
    served, = pred.run({'x': xb})
    assert np.asarray(served).tobytes() == np.asarray(live).tobytes()
    spans = {s['name']: s for s in trace.spans()
             if s['name'] in ('io.save', 'io.load')}
    assert spans['io.save']['bytes'] == spans['io.load']['bytes'] == on_disk
    assert spans['io.save']['files'] == spans['io.load']['files'] == 4


def test_with_the_registry_off_nothing_is_counted(tmp_path):
    telemetry.disable()
    before = _io_counters()
    prog = _declare({'q': _ARRAYS['float32']})
    exe = fluid.Executor(fluid.CPUPlace())
    fluid.io.save_vars(exe, str(tmp_path), prog, vars=['q'])
    fluid.io.load_vars(exe, str(tmp_path), prog, vars=['q'])
    assert _io_counters() == before


# -- the load's device copy --------------------------------------------------

def test_one_device_process_loads_onto_the_executors_device(
        tmp_path, monkeypatch):
    """Where the process has one device, a loaded variable is put there
    as its file arrives (what the executor's pin would do at the first
    run); 64-bit dtypes stay on the host, as the pin leaves them."""
    arrays = {'w32': _ARRAYS['float32'], 'step64': _ARRAYS['int64']}
    prog = _declare(dict(arrays))
    exe = fluid.Executor(fluid.TPUPlace())
    for filename in (None, 'all_in_one'):
        d = str(tmp_path / str(filename))
        fluid.io.save_persistables(exe, d, prog, filename=filename)
        monkeypatch.setattr(jax, 'device_count', lambda *a: 1)
        fluid.io.load_persistables(exe, d, prog, filename=filename)
        monkeypatch.undo()
        w = fluid.global_scope().find_var('w32')
        assert isinstance(w, jax.Array) and w.devices() == {exe.device}
        s = fluid.global_scope().find_var('step64')
        assert isinstance(s, np.ndarray) and s.dtype == np.int64
        np.testing.assert_array_equal(np.asarray(w), arrays['w32'])
        np.testing.assert_array_equal(s, arrays['step64'])


def test_with_several_devices_a_loaded_variable_stays_on_the_host(tmp_path):
    assert jax.device_count() > 1
    prog = _declare({'w': _ARRAYS['float32']})
    exe = fluid.Executor(fluid.TPUPlace())
    fluid.io.save_persistables(exe, str(tmp_path), prog)
    fluid.io.load_persistables(exe, str(tmp_path), prog)
    assert isinstance(fluid.global_scope().find_var('w'), np.ndarray)


def test_ops_alone_take_the_plain_path(tmp_path):
    """A save / load op in a program of the user's own (layers.load, a
    transpiled checkpoint block) has no stream: it writes and reads in
    the op, the same bytes."""
    arr = _ARRAYS['float32']
    prog = _declare({'solo': arr})
    block = prog.global_block()
    block.append_op(type='save', inputs={'X': ['solo']}, outputs={},
                    attrs={'file_path': str(tmp_path / 'solo')})
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(prog)
    assert (tmp_path / 'solo').read_bytes() == _parent_form(arr)
    back = fluid.Program()
    back.global_block().create_var(name='solo', shape=list(arr.shape),
                                   dtype='float32', persistable=True)
    back.global_block().append_op(
        type='load', inputs={}, outputs={'Out': ['solo']},
        attrs={'file_path': str(tmp_path / 'solo')})
    fluid.global_scope().set_var('solo', None)
    exe.run(back)
    got = fluid.global_scope().find_var('solo')
    assert isinstance(got, np.ndarray)
    np.testing.assert_array_equal(got, arr)
