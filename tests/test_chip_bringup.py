"""First run on the chip (chip_smoke.py and what it rests on).

What must hold, on the CPU:

- the compile cache is placed from outside: with
  JAX_COMPILATION_CACHE_DIR set the package sets no cache directory,
  unset it is <checkout>/.jax_cache from any process;
- chip_smoke.py exits non-zero without a TPU and names what it found,
  and fails in a directory that holds nothing else of the repo; its
  explicit --dry-run walks the same phases at a tiny width with
  interpret-mode kernels, one chip (train + serve) and, marked slow,
  four (dp); a py_reader feeds a dp mesh;
- the peak table is exact: an unknown device_kind raises;
- flash_attention() counts its route, so a d=64 shape that misses the
  kernel is visible;
- the flash_attention op lowers for a TPU under a dp mesh (JAX refuses a
  bare Mosaic call there; the op runs it per shard);
- compiled_hlo_texts raises on a segment it cannot re-lower.
"""
import json
import os
import shutil
import subprocess
import sys
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.obs import perf, telemetry

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_ROOT, 'chip_smoke.py')


# --------------------------------------------------------------------------
# compile cache placement
# --------------------------------------------------------------------------

_CACHE_PROBE = '''
import json, sys
sys.path.insert(0, %r)
import jax
calls, update = [], jax.config.update
jax.config.update = lambda k, v: (calls.append(k), update(k, v))[1]
import paddle_tpu
print(json.dumps({"calls": calls,
                  "dir": jax.config.jax_compilation_cache_dir}))
''' % _ROOT


def _cache_probe(cwd, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k != 'JAX_COMPILATION_CACHE_DIR'}
    env.update(JAX_PLATFORMS='cpu', **env_over)
    out = subprocess.run([sys.executable, '-c', _CACHE_PROBE], cwd=cwd,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_compile_cache_env_set_code_sets_nothing(tmp_path):
    got = _cache_probe(_ROOT, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert 'jax_compilation_cache_dir' not in got['calls']
    assert got['dir'] == str(tmp_path)     # JAX's own reading of the env


def test_compile_cache_default_is_fixed_beside_the_package(tmp_path):
    want = os.path.join(_ROOT, '.jax_cache')
    got = _cache_probe(str(tmp_path))      # another process, another cwd
    assert got['dir'] == want
    assert got['calls'].count('jax_compilation_cache_dir') == 1
    # and this process, which imported the package from its own cwd
    assert jax.config.jax_compilation_cache_dir == os.environ.get(
        'JAX_COMPILATION_CACHE_DIR', want)


# --------------------------------------------------------------------------
# chip_smoke.py
# --------------------------------------------------------------------------

def _smoke(*argv, cwd=_ROOT, script=_SMOKE, **env_over):
    env = dict(os.environ, **env_over)
    env.pop('XLA_FLAGS', None)
    return subprocess.run([sys.executable, script] + list(argv), cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _rows(out):
    return [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith('{')]


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    out = _smoke(JAX_PLATFORMS='cpu')
    assert out.returncode != 0
    assert "platform='cpu'" in out.stderr
    assert '"ok"' not in out.stdout
    # alone in a directory, without the program, it fails too
    lone = shutil.copy(_SMOKE, str(tmp_path))
    out = _smoke(cwd=str(tmp_path), script=lone, JAX_PLATFORMS='cpu')
    assert out.returncode != 0
    assert out.stdout == ''


def test_chip_smoke_dry_run_one_chip():
    out = _smoke('--dry-run')
    assert out.returncode == 0, out.stderr[-3000:]
    rows = _rows(out)
    assert [r.get('phase') for r in rows[:-1]] == ['device', 'train',
                                                    'serve']
    for r in rows[:-1]:
        assert r['platform'] == 'cpu' and r['dry_run'] is True
        assert r['compile_cache_dir'] and r['jax']
    train, serve = rows[1], rows[2]
    assert train['flash_route']['pallas.flash.naive'] == 0
    assert train['flash_route']['pallas.flash.kernel'] > 0
    assert train['losses'][-1] < train['losses'][0]
    assert serve['completed'] == 8 and serve['compiled_segments'] == 3
    assert rows[-1] == {'ok': True, 'device': {
        'platform': 'cpu', 'kind': 'cpu', 'count': 1}}


@pytest.mark.slow
def test_chip_smoke_dry_run_four_chips():
    """dp=4 through py_reader: the batch lands a quarter per device, the
    flash op runs per shard, and the loss agrees with one device. (slow:
    tier-1 covers the two repairs it rests on directly, below.)"""
    out = _smoke('--dry-run', '--chips', '4')
    assert out.returncode == 0, out.stderr[-3000:]
    rows = _rows(out)
    assert [r.get('phase') for r in rows[:-1]] == [
        'device', 'train', 'train', 'dp4_vs_dp1']
    dp4 = rows[2]
    assert dp4['dp'] == 4 and dp4['feed_rows_per_device'] == 2
    assert dp4['collectives']['all-reduce'] > 0
    assert rows[3]['first_loss_rel_diff'] <= rows[3]['tolerance']
    assert rows[-1]['device']['count'] == 4


def test_py_reader_feeds_a_dp_mesh():
    """A py_reader batch is placed like a feed: the reader's placer thread
    puts it on one device, the mesh step wants it split over dp (this
    raised "incompatible devices" before the executor re-placed it)."""
    def losses(devices):
        prog, startup = fluid.Program(), fluid.Program()
        prog.random_seed = startup.random_seed = 3
        with fluid.program_guard(prog, startup), fluid.unique_name.guard():
            rdr = fluid.layers.py_reader(
                capacity=2, shapes=[(-1, 8), (-1, 1)],
                dtypes=['float32', 'float32'],
                name='mesh_reader_%d' % len(devices),
                use_double_buffer=True)
            x, y = fluid.layers.read_file(rdr)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(
                fluid.layers.fc(input=x, size=1), y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        rng = np.random.RandomState(0)
        batch = [rng.randn(8, 8).astype('float32'),
                 rng.randn(8, 1).astype('float32')]
        rdr.decorate_tensor_provider(lambda: iter([batch] * 3))
        with fluid.scope_guard(fluid.Scope()):
            fluid.Executor(fluid.CPUPlace()).run(startup)
            pe = fluid.ParallelExecutor(loss_name=loss.name,
                                        main_program=prog, devices=devices)
            rdr.start()
            try:
                return [float(pe.run(fetch_list=[loss.name])[0])
                        for _ in range(3)]
            finally:
                rdr.reset()

    one, four = losses(jax.devices()[:1]), losses(jax.devices()[:4])
    np.testing.assert_allclose(four, one, rtol=1e-5)
    assert four[-1] < four[0]


# --------------------------------------------------------------------------
# one exact peak table
# --------------------------------------------------------------------------

class _FakeDevice(object):
    def __init__(self, kind, platform='tpu'):
        self.device_kind, self.platform = kind, platform


def test_peak_table_is_exact_and_unknown_kind_raises():
    assert perf.device_peak_flops(_FakeDevice('TPU v5 lite')) == 197e12
    for kind in ('TPU v9', 'TPU v5 litepod', 'cpu'):
        with pytest.raises(ValueError, match='device_kind'):
            perf.device_peak_flops(_FakeDevice(kind))
    with pytest.raises(RuntimeError, match="platform='cpu'"):
        perf.require_tpu()


# --------------------------------------------------------------------------
# flash route: counted, and per shard under a mesh
# --------------------------------------------------------------------------

@pytest.fixture
def counters():
    telemetry.reset()
    telemetry.enable()
    # the route a call took; which forward schedule a trace compiled
    # (pallas.flash.fwd.*) is tests/test_flash_attention.py's
    yield lambda: {k: telemetry.snapshot()['counters'][k]
                   for k in ('pallas.flash.kernel', 'pallas.flash.naive')}
    telemetry.disable(final_flush=False)
    telemetry.reset()


def test_flash_route_counter_moves_on_d64(counters):
    from paddle_tpu.pallas.flash_attention import flash_attention
    x = jnp.ones((2, 128, 64), jnp.float32)
    flash_attention(x, x, x)                 # d=64 misses the kernel
    assert counters() == {'pallas.flash.kernel': 0, 'pallas.flash.naive': 1}
    fluid.set_flags({'pallas_interpret': True})
    try:
        y = jnp.ones((2, 128, 128), jnp.float32)
        flash_attention(y, y, y)
    finally:
        fluid.set_flags({'pallas_interpret': False})
    assert counters() == {'pallas.flash.kernel': 1, 'pallas.flash.naive': 1}


def test_flash_op_lowers_per_shard_for_tpu_under_dp_mesh():
    """Cross-lowered for a TPU from here: the Mosaic call sees B*H/4 rows
    under a dp=4 mesh; without the mesh the same sharded operands are
    refused by JAX, which is what stopped the first four-chip run."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu import registry
    from paddle_tpu.executor import EmitContext
    B, H, T, d = 8, 2, 128, 128
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        q, k, v = (fluid.layers.data(name=n, shape=[H, T, d],
                                     dtype='float32') for n in 'qkv')
        out = fluid.layers.flash_attention(q, k, v, causal=True)
    op, = [o for o in prog.global_block().ops
           if o.type == 'flash_attention']
    mesh = Mesh(np.array(jax.devices()[:4]), ('dp',))

    def lower(ctx_mesh):
        def f(qa, ka, va):
            ctx = EmitContext({'q': qa, 'k': ka, 'v': va},
                              prog.global_block(), None, True)
            ctx.mesh = ctx_mesh
            registry._REGISTRY['flash_attention'].emit(ctx, op)
            return ctx.get(out.name)
        arg = jax.ShapeDtypeStruct(
            (B, H, T, d), jnp.float32,
            sharding=NamedSharding(mesh, P('dp')))
        with mock.patch.object(jax, 'default_backend', return_value='tpu'):
            return jax.jit(f).trace(arg, arg, arg).lower(
                lowering_platforms=('tpu',)).as_text()

    text = lower(mesh)
    assert 'tpu_custom_call' in text
    assert 'tensor<%dx%dx%dxf32>' % (B * H // 4, T, d) in text
    with pytest.raises(NotImplementedError, match='shard_map'):
        lower(None)


# --------------------------------------------------------------------------
# the HLO reader does not drop what it cannot read
# --------------------------------------------------------------------------

def test_compiled_hlo_texts_raises_on_a_segment_it_cannot_lower():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    y = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed={'x': np.ones((2, 4), 'float32')}, fetch_list=[y])
    assert all('HloModule' in t for t in exe.compiled_hlo_texts())
    for prepared in exe._prepared_cache.values():
        for step in prepared.steps:
            step._arg_struct = ('not', 'the', 'signature')
    with pytest.raises(Exception):
        exe.compiled_hlo_texts()
