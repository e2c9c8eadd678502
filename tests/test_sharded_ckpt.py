"""Sharded-mesh checkpoint subsystem (ISSUE 7 acceptance).

The contracts under test:
- reshard matrix: a generation saved on ANY of {dp=4, dp=2x2tp, tp=4}
  restores on ANY other (and on a grown dp=8 mesh, and with no mesh at
  all) to values np.array_equal to the unsharded reference
- no host gather on save: the largest single host allocation during
  save() is one shard, and each param-shard lands in its own file
- durability: flipping ONE bit in ANY payload file of current/ is
  detected by the digest manifest, the generation is quarantined aside
  and restore falls back to current.prev/; a missing COMMIT marker is
  skipped silently (crash mid-save, not corruption)
- fencing: a stale incarnation is refused at OWNER claim AND re-checked
  right before the commit rotation (zombie saves never clobber a
  successor's generations)
- elastic recovery (chaos): a Supervisor-run mesh training job
  (ZeRO-3 over 4 virtual devices, async sharded checkpoints) kill-9'd
  mid-step resumes from the last committed generation and finishes with
  weights + Adam moments BIT-exact vs a fault-free run
plus the satellites: Trainer(sharded=True) in-process resume,
io save/load filter_fn + FLAGS_ckpt_verify digests,
MeshConfig.from_flags / exception-safe mesh_scope / fit_spec,
PagedDecodePredictor.load_sharded serve-after-reshard parity, and the
ckpt.* telemetry instruments + trace spans.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import checkpoint
from paddle_tpu.checkpoint import manifest as ckpt_manifest
from paddle_tpu.checkpoint import restore as ckpt_restore
from paddle_tpu.checkpoint.elastic import MeshCheckpointer
from paddle_tpu.distributed.resilience import StaleIncarnationError
from paddle_tpu.distributed.supervisor import Supervisor
from paddle_tpu.obs import telemetry, trace
from paddle_tpu.parallel import mesh as mesh_mod

_TESTS = os.path.dirname(os.path.abspath(__file__))
_MESH_WORKER = os.path.join(_TESTS, 'mesh_worker.py')


# ---------------------------------------------------------------------------
# fixtures: reference values + mesh topologies
# ---------------------------------------------------------------------------

_SPECS = {'w': ('dp', 'tp'), 'b': ('dp',), 'scalar': None}
_MESHES = {'dp4': dict(dp=4), 'dp2tp2': dict(dp=2, tp=2),
           'tp4': dict(tp=4)}


def _ref_values():
    rng = np.random.RandomState(42)
    return {'w': rng.randn(8, 8).astype('float32'),
            'b': rng.randn(8).astype('float32'),
            'scalar': np.array(3.25, 'float32')}


def _build_mesh(axes):
    return mesh_mod.MeshConfig(**axes).build()


def _place(values, mesh):
    """Shard the reference values onto `mesh` per their canonical specs
    (fit_spec drops axes the mesh lacks, as a real trainer would)."""
    out = {}
    for name, val in values.items():
        spec = mesh_mod.fit_spec(_SPECS[name], np.shape(val), mesh)
        out[name] = jax.device_put(val, mesh_mod.named_sharding(mesh, spec))
    return out


# ---------------------------------------------------------------------------
# reshard matrix: save on any topology, restore on any other
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('restore_mesh', sorted(_MESHES))
@pytest.mark.parametrize('save_mesh', sorted(_MESHES))
def test_reshard_matrix(tmp_path, save_mesh, restore_mesh):
    ref = _ref_values()
    smesh = _build_mesh(_MESHES[save_mesh])
    checkpoint.save_sharded(str(tmp_path), _place(ref, smesh),
                            extras={'step': 1}, incarnation=0)
    rmesh = _build_mesh(_MESHES[restore_mesh])
    values, extras, gen = checkpoint.restore_sharded(str(tmp_path),
                                                     mesh=rmesh)
    assert gen == 1 and extras == {'step': 1}
    assert set(values) == set(ref)
    for name, want in ref.items():
        got = values[name]
        # really resharded: lives on the NEW mesh, not merely replicated
        assert got.sharding.mesh.axis_names == rmesh.axis_names, name
        assert np.array_equal(np.asarray(got), want), \
            '%s diverged %s -> %s' % (name, save_mesh, restore_mesh)


def test_restore_on_grown_mesh_and_host_path(tmp_path):
    ref = _ref_values()
    checkpoint.save_sharded(
        str(tmp_path), _place(ref, _build_mesh(dict(dp=2, tp=2))),
        incarnation=0)
    # grown mesh (more devices than saved on)
    vals, _, _ = checkpoint.restore_sharded(
        str(tmp_path), mesh=_build_mesh(dict(dp=8)))
    for name, want in ref.items():
        assert np.array_equal(np.asarray(vals[name]), want), name
    # no mesh at all: plain host arrays
    vals, _, _ = checkpoint.restore_sharded(str(tmp_path))
    for name, want in ref.items():
        assert isinstance(vals[name], np.ndarray)
        assert np.array_equal(vals[name], want), name


def test_no_host_gather_on_save(tmp_path):
    """The no-host-gather contract: saving a dp=4-sharded (8, 8) param
    allocates at most ONE shard on the host and writes one file per
    shard — never the gathered global value."""
    mesh = _build_mesh(dict(dp=4))
    w = np.arange(64, dtype='float32').reshape(8, 8)
    arr = jax.device_put(w, mesh_mod.named_sharding(mesh, ('dp', None)))
    saver = checkpoint.AsyncShardedSaver(str(tmp_path), incarnation=0)
    saver.save({'w': arr}, block=True)
    stats = saver.last_stats
    saver.close()
    shard_bytes = w.nbytes // 4
    assert stats['max_host_bytes'] == shard_bytes  # one shard, not 4x
    assert stats['files'] == 4 and stats['bytes'] == w.nbytes
    cur = os.path.join(str(tmp_path), checkpoint.sharded.CURRENT_DIR)
    bins = sorted(f for f in os.listdir(cur) if f.endswith('.bin'))
    assert len(bins) == 4, bins
    for f in bins:
        assert os.path.getsize(os.path.join(cur, f)) == shard_bytes, f


# ---------------------------------------------------------------------------
# durability: digests, quarantine, .prev fallback, COMMIT discipline
# ---------------------------------------------------------------------------

def _save_two_generations(root):
    """gen 1 holds ref1, gen 2 (current/) holds ref1+1."""
    mesh = _build_mesh(dict(dp=2, tp=2))
    ref1 = _ref_values()
    ref2 = {k: v + 1 for k, v in ref1.items()}
    saver = checkpoint.AsyncShardedSaver(root, incarnation=0)
    saver.save(_place(ref1, mesh), extras={'gen': 'one'}, block=True)
    saver.save(_place(ref2, mesh), extras={'gen': 'two'}, block=True)
    saver.close()
    return ref1, ref2


def test_bit_flip_in_every_shard_file_detected_with_prev_fallback(tmp_path):
    """For EVERY payload file of the committed generation (each shard
    .bin and the manifest itself): one flipped bit is detected, the
    generation is quarantined aside and restore serves current.prev/."""
    template = str(tmp_path / 'template')
    ref1, _ref2 = _save_two_generations(template)
    cur = os.path.join(template, checkpoint.sharded.CURRENT_DIR)
    victims = sorted(
        f for f in os.listdir(cur)
        if f not in (ckpt_manifest.DIGESTS_FILE,
                     checkpoint.sharded.COMMIT_FILE))
    assert any(v.endswith('.bin') for v in victims)
    assert checkpoint.sharded.MANIFEST_FILE in victims
    for victim in victims:
        root = str(tmp_path / ('case_' + victim.replace('.', '_')))
        shutil.copytree(template, root)
        path = os.path.join(root, checkpoint.sharded.CURRENT_DIR, victim)
        with open(path, 'rb') as f:
            blob = bytearray(f.read())
        blob[len(blob) // 2] ^= 0x40
        with open(path, 'wb') as f:
            f.write(bytes(blob))
        # the open itself reports a reason (naming the file)
        got = ckpt_restore._try_open(
            os.path.join(root, checkpoint.sharded.CURRENT_DIR))
        assert isinstance(got, str), victim
        ckpt = checkpoint.load_checkpoint(root)
        assert ckpt is not None and ckpt.extras == {'gen': 'one'}, victim
        assert os.path.isdir(os.path.join(
            root, checkpoint.sharded.CURRENT_DIR + '.corrupt')), victim
        for name, want in ref1.items():
            assert np.array_equal(ckpt.read(name), want), (victim, name)


def test_missing_commit_skipped_without_quarantine(tmp_path):
    """No COMMIT marker = crash mid-save, not corruption: the dir is
    skipped silently (kept, NOT quarantined) and .prev serves."""
    root = str(tmp_path)
    _save_two_generations(root)
    cur = os.path.join(root, checkpoint.sharded.CURRENT_DIR)
    os.remove(os.path.join(cur, checkpoint.sharded.COMMIT_FILE))
    ckpt = checkpoint.load_checkpoint(root)
    assert ckpt.extras == {'gen': 'one'}
    assert os.path.isdir(cur)
    assert not os.path.isdir(cur + '.corrupt')


def test_no_loadable_generation_returns_none(tmp_path):
    values, extras, gen = checkpoint.restore_sharded(
        str(tmp_path / 'never_written'))
    assert values is None and extras is None and gen == 0


def test_out_of_order_async_commits_never_roll_current_back(tmp_path):
    """Two saves in flight on the async pool can FINISH out of order
    (gen N+1's writer thread beats gen N's). The late older generation
    must be dropped, never rotated over the newer one — or a resume
    would silently rewind training."""
    import time
    root = str(tmp_path)
    mesh = _build_mesh(dict(dp=2, tp=2))
    ref1 = _ref_values()
    ref2 = {k: v + 1 for k, v in ref1.items()}
    saver = checkpoint.AsyncShardedSaver(root, incarnation=0, workers=1)
    snap1, mh1 = saver.snapshot(_place(ref1, mesh))
    snap2, mh2 = saver.snapshot(_place(ref2, mesh))
    # replay the race deterministically: the NEWER generation commits
    # first, the older one lands late
    saver._do_write_and_commit(2, snap2, {'gen': 'two'}, mh2, time.time())
    saver._do_write_and_commit(1, snap1, {'gen': 'one'}, mh1, time.time())
    assert saver.last_stats['superseded'] is True
    saver.close()
    ckpt = checkpoint.load_checkpoint(root)
    assert ckpt.generation == 2 and ckpt.extras == {'gen': 'two'}
    for name, want in ref2.items():
        assert np.array_equal(ckpt.read(name), want), name
    # the dropped generation's staging dir is cleaned up
    assert not [d for d in os.listdir(root) if d.startswith('.staging')]


def test_generation_rotation_and_numbering(tmp_path):
    root = str(tmp_path)
    _save_two_generations(root)
    with open(os.path.join(root, checkpoint.sharded.CURRENT_DIR,
                           checkpoint.sharded.MANIFEST_FILE)) as f:
        cur_gen = json.load(f)['generation']
    with open(os.path.join(root, checkpoint.sharded.PREV_DIR,
                           checkpoint.sharded.MANIFEST_FILE)) as f:
        prev_gen = json.load(f)['generation']
    assert (cur_gen, prev_gen) == (2, 1)
    # a new saver (restarted process) continues the numbering
    saver = checkpoint.AsyncShardedSaver(root, incarnation=0)
    assert saver.generation == 3
    saver.close()


# ---------------------------------------------------------------------------
# OWNER fencing
# ---------------------------------------------------------------------------

def test_stale_incarnation_refused_at_claim(tmp_path):
    root = str(tmp_path)
    checkpoint.AsyncShardedSaver(root, incarnation=1).close()
    with pytest.raises(StaleIncarnationError):
        checkpoint.AsyncShardedSaver(root, incarnation=0)
    # an equal or higher incarnation re-claims fine
    checkpoint.AsyncShardedSaver(root, incarnation=1).close()
    checkpoint.AsyncShardedSaver(root, incarnation=2).close()


def test_fence_rechecked_before_rotation(tmp_path):
    """A successor claims the root while the old incarnation's save is
    in flight: the old save must NOT rotate over the successor's
    generation."""
    root = str(tmp_path)
    mesh = _build_mesh(dict(dp=4))
    old = checkpoint.AsyncShardedSaver(root, incarnation=0)
    successor = checkpoint.AsyncShardedSaver(root, incarnation=5)
    successor.save(_place(_ref_values(), mesh),
                   extras={'who': 'successor'}, block=True)
    successor.close()
    with pytest.raises(StaleIncarnationError):
        old.save(_place(_ref_values(), mesh), block=True)
    with pytest.raises(StaleIncarnationError):
        old.close()   # the async error surfaces again on drain
    ckpt = checkpoint.load_checkpoint(root)
    assert ckpt.extras == {'who': 'successor'}


# ---------------------------------------------------------------------------
# MeshCheckpointer: scope-level save/restore, is_cache exclusion
# ---------------------------------------------------------------------------

def test_mesh_checkpointer_scope_roundtrip_and_cache_exclusion(tmp_path):
    prog = fluid.Program()
    block = prog.global_block()
    block.create_var(name='p', shape=[4], dtype='float32',
                     persistable=True)
    block.create_var(name='kv', shape=[4], dtype='float32',
                     persistable=True, is_cache=True)
    block.create_var(name='tmp', shape=[4], dtype='float32',
                     persistable=False)
    scope = fluid.Scope()
    scope.set_var('p', np.arange(4, dtype='float32'))
    scope.set_var('kv', np.ones(4, 'float32'))
    scope.set_var('tmp', np.ones(4, 'float32'))
    assert set(MeshCheckpointer.checkpoint_vars(scope, prog)) == {'p'}

    mc = MeshCheckpointer(str(tmp_path), incarnation=7)
    mc.save_scope(scope, prog, extras={'step_id': 3}, block=True)
    assert mc.last_stats['generation'] == 1
    mc.close()

    scope2 = fluid.Scope()
    reader = MeshCheckpointer(str(tmp_path))   # restore-only: no claim
    extras = reader.restore_scope(scope2, prog)
    assert extras == {'step_id': 3}
    assert np.array_equal(np.asarray(scope2.find_var('p')),
                          np.arange(4, dtype='float32'))
    assert scope2.find_var('kv') is None      # caches never checkpointed
    # the restore-only reader did NOT overwrite the trainer's OWNER
    with open(os.path.join(str(tmp_path),
                           checkpoint.sharded.OWNER_FILE)) as f:
        assert json.load(f)['incarnation'] == 7


# ---------------------------------------------------------------------------
# Trainer(sharded=True): in-process kill-and-resume
# ---------------------------------------------------------------------------

class _Abort(Exception):
    pass


def _sharded_trainer_run(ckpt_dir, abort_at=None):
    from paddle_tpu import unique_name
    unique_name.switch()

    def train_func():
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        pred = fluid.layers.fc(
            input=x, size=1,
            param_attr=fluid.ParamAttr(
                name='sw', initializer=fluid.initializer.Normal(
                    scale=0.1, seed=3)))
        return fluid.layers.mean(fluid.layers.square_error_cost(pred, y))

    def reader():
        rng = np.random.RandomState(7)
        w = np.linspace(-1, 1, 4).astype('float32')[:, None]
        for _ in range(10):
            x = rng.randn(8, 4).astype('float32')
            yield [x, x @ w]

    trainer = fluid.Trainer(
        train_func, lambda: fluid.optimizer.Adam(0.02),
        place=fluid.CPUPlace(),
        checkpoint_config=fluid.CheckpointConfig(
            checkpoint_dir=ckpt_dir, step_interval=3, sharded=True))
    seen = []

    def handler(event):
        if isinstance(event, fluid.EndStepEvent):
            seen.append((event.epoch, event.step,
                         float(np.asarray(event.metrics[0]))))
            if abort_at is not None and \
                    (event.epoch, event.step) == abort_at:
                raise _Abort()
    try:
        trainer.train(num_epochs=1, event_handler=handler,
                      reader=reader, feed_order=['x', 'y'])
    except _Abort:
        pass
    if trainer._mesh_checkpointer is not None:
        trainer._mesh_checkpointer.close()   # drain async saves
    return seen, trainer


def test_trainer_sharded_resume_exact(tmp_path):
    """CheckpointConfig(sharded=True): the two-generation sharded root
    replaces checkpoint_N dirs, and a killed trainer resumes at the
    exact next step with losses IDENTICAL to an uninterrupted run."""
    full, _ = _sharded_trainer_run(str(tmp_path / 'full'))

    ckpt = str(tmp_path / 'ck')
    _sharded_trainer_run(ckpt, abort_at=(0, 7))     # last save at step 5
    assert os.path.exists(os.path.join(
        ckpt, checkpoint.sharded.CURRENT_DIR,
        checkpoint.sharded.COMMIT_FILE))
    resumed, _ = _sharded_trainer_run(ckpt)

    assert resumed[0][:2] == (0, 6)
    full_by_key = {(e, s): v for e, s, v in full}
    for e, s, v in resumed:
        assert v == full_by_key[(e, s)], 'step (%d, %d)' % (e, s)
    assert resumed[-1][:2] == full[-1][:2] == (0, 9)


# ---------------------------------------------------------------------------
# acceptance (chaos): Supervisor-run mesh job kill-9'd mid-step resumes
# bit-exact from the sharded checkpoint
# ---------------------------------------------------------------------------

def _run_mesh(workdir, ckpt_root, steps=8, kill_nth=None, dp=4):
    os.makedirs(workdir, exist_ok=True)
    env = dict(os.environ)
    env.pop('JAX_PLATFORMS', None)
    env.pop('XLA_FLAGS', None)     # the worker pins its own device count
    env.update({'MESH_STEPS': str(steps), 'MESH_CKPT': ckpt_root,
                'MESH_CKPT_EVERY': '2', 'MESH_DP': str(dp),
                'MESH_TP': '1'})
    if kill_nth is not None:
        env['FLAGS_fault_plan'] = json.dumps(
            {'rules': [{'when': 'step', 'type': '*', 'nth': kill_nth,
                        'action': 'exit'}]})
    sup = Supervisor(max_restarts=2, backoff=0.3, log_dir=workdir)
    sup.add_role('mesh', [sys.executable, _MESH_WORKER], env=env)
    sup.start()
    states = sup.wait(timeout=180)
    sup.stop()
    result = None
    for line in sup.output('mesh').splitlines():
        if line.startswith('RESULT '):
            result = json.loads(line[len('RESULT '):])
    return states, dict(sup.restarts), result


@pytest.mark.chaos
@pytest.mark.timeout(400)
def test_mesh_kill9_resumes_bit_exact(tmp_path):
    """ISSUE 7 acceptance: a ZeRO-3 mesh training job under the
    Supervisor, saving async sharded generations, is kill-9'd mid-step;
    the restarted incarnation resumes from the last committed
    generation and every final weight AND Adam moment is BIT-exact
    (np.array_equal, not allclose) vs a fault-free run."""
    b_states, b_restarts, base = _run_mesh(
        str(tmp_path / 'base'), str(tmp_path / 'base_ckpt'))
    assert b_states == {'mesh': 'done'} and b_restarts == {'mesh': 0}
    assert base is not None

    kill_ckpt = str(tmp_path / 'kill_ckpt')
    k_states, k_restarts, killed = _run_mesh(
        str(tmp_path / 'kill'), kill_ckpt, kill_nth=5)
    assert k_states == {'mesh': 'done'}
    assert k_restarts == {'mesh': 1}, 'fault plan never fired'
    assert killed is not None

    assert set(base['weights']) == set(killed['weights'])
    for name in sorted(base['weights']):
        assert np.array_equal(np.asarray(base['weights'][name]),
                              np.asarray(killed['weights'][name])), name

    # the sharded layout is real: ZeRO-3 split mb1 (shape (16,), dp=4)
    # into 4 per-shard files, and the restarted incarnation owns the root
    cur = os.path.join(kill_ckpt, checkpoint.sharded.CURRENT_DIR)
    mb1_shards = [f for f in os.listdir(cur) if f.startswith('mb1.s')]
    assert len(mb1_shards) == 4, sorted(os.listdir(cur))
    with open(os.path.join(kill_ckpt,
                           checkpoint.sharded.OWNER_FILE)) as f:
        assert json.load(f)['incarnation'] == 1


# ---------------------------------------------------------------------------
# io satellites: filter_fn + FLAGS_ckpt_verify digests
# ---------------------------------------------------------------------------

def test_io_filter_fn_and_ckpt_verify(tmp_path):
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    fluid.layers.fc(input=x, size=2,
                    param_attr=fluid.ParamAttr(name='fw'),
                    bias_attr=fluid.ParamAttr(name='fb'))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    # filter_fn composes on top of the persistable predicate
    plain = str(tmp_path / 'plain')
    fluid.io.save_persistables(exe, plain,
                               filter_fn=lambda v: v.name != 'fb')
    assert os.path.exists(os.path.join(plain, 'fw'))
    assert not os.path.exists(os.path.join(plain, 'fb'))
    # flag off: no digest manifest written
    assert ckpt_manifest.read_digests(plain) is None

    fluid.set_flags({'FLAGS_ckpt_verify': True})
    try:
        verified = str(tmp_path / 'verified')
        fluid.io.save_persistables(exe, verified)
        digests = ckpt_manifest.read_digests(verified)
        assert set(digests) == {'fw', 'fb'}
        fluid.io.load_persistables(exe, verified)   # clean load passes
        # one corrupt payload -> ONE error naming the var and file
        path = os.path.join(verified, 'fb')
        with open(path, 'rb') as f:
            blob = bytearray(f.read())
        blob[len(blob) // 2] ^= 0x01
        with open(path, 'wb') as f:
            f.write(bytes(blob))
        with pytest.raises(checkpoint.CheckpointCorruptError) as ei:
            fluid.io.load_persistables(exe, verified)
        assert 'fb' in str(ei.value)
    finally:
        fluid.set_flags({'FLAGS_ckpt_verify': False})


# ---------------------------------------------------------------------------
# mesh satellites: from_flags, exception-safe scope, fit_spec
# ---------------------------------------------------------------------------

def test_mesh_config_from_flags():
    try:
        fluid.set_flags({'FLAGS_mesh_shape': 'dp=2,tp=2'})
        cfg = mesh_mod.MeshConfig.from_flags()
        assert cfg.axis_sizes['dp'] == 2 and cfg.axis_sizes['tp'] == 2
        mesh = cfg.build()
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) == \
            {'dp': 2, 'tp': 2}
        # '' = pure data parallelism over every local device
        fluid.set_flags({'FLAGS_mesh_shape': ''})
        assert mesh_mod.MeshConfig.from_flags().axis_sizes['dp'] == \
            len(jax.devices())
        fluid.set_flags({'FLAGS_mesh_shape': 'bogus'})
        with pytest.raises(ValueError):
            mesh_mod.MeshConfig.from_flags()
        fluid.set_flags({'FLAGS_mesh_shape': 'zz=4'})
        with pytest.raises(ValueError):
            mesh_mod.MeshConfig.from_flags().build()
    finally:
        fluid.set_flags({'FLAGS_mesh_shape': ''})


def test_mesh_scope_restores_previous_mesh_on_exception():
    base = mesh_mod.get_mesh()
    with pytest.raises(RuntimeError):
        with mesh_mod.mesh_scope(mesh_mod.MeshConfig(dp=2)) as m:
            assert mesh_mod.get_mesh() is m
            assert dict(zip(m.axis_names, m.devices.shape)) == {'dp': 2}
            raise RuntimeError('boom')
    assert mesh_mod.get_mesh() is base


def test_fit_spec_adapts_to_new_topology():
    tp4 = _build_mesh(dict(tp=4))
    # axis the mesh lacks falls away; surviving axis keeps its dim
    assert mesh_mod.fit_spec(('dp', 'tp'), (8, 8), tp4) == (None, 'tp')
    # axis whose size no longer divides the dim falls away
    assert mesh_mod.fit_spec(('tp',), (6,), tp4) == (None,)
    dp2tp2 = _build_mesh(dict(dp=2, tp=2))
    # multi-axis dims survive when every factor divides
    assert mesh_mod.fit_spec((('dp', 'tp'),), (8,), dp2tp2) == \
        (('dp', 'tp'),)
    # short specs are padded with None to the shape's rank
    assert mesh_mod.fit_spec(('dp',), (8, 8), dp2tp2) == ('dp', None)
    assert mesh_mod.fit_spec(None, (8,), dp2tp2) is None


# ---------------------------------------------------------------------------
# serving satellite: PagedDecodePredictor.load_sharded serve-after-reshard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('geometry', [
    {}, dict(page_tokens=4, kv_pages=8, prefill_chunk=8)],
    ids=['default_pool', 'explicit_pool'])
def test_serve_after_reshard_parity(tmp_path, geometry):
    """Weights saved SHARDED on a dp=2xtp=2 training mesh, loaded by a
    single-device PagedDecodePredictor (the flags' page geometry, and
    one given in the call): greedy decode is identical to the
    predictor's original weights (the save/reshard/load round trip is
    exact), page pools are never part of the checkpoint, and a missing
    param raises naming it."""
    from paddle_tpu import unique_name
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models.transformer import (TransformerConfig,
                                               language_model_logits)
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    cfg = TransformerConfig(vocab=32, dim=16, heads=2, layers=1, ffn=32,
                            max_len=8, use_tp=False, use_sp=False)
    model_dir = str(tmp_path / 'model')
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 7
    with unique_name.guard(), program_guard(prog, startup):
        toks = fluid.layers.data(name='tokens',
                                 shape=[1, cfg.max_len, 1],
                                 dtype='int64', append_batch_size=False)
        logits = language_model_logits(toks, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ['tokens'], [logits],
                                      exe, main_program=prog)
    predictor = AnalysisPredictor(AnalysisConfig(model_dir,
                                                 place=fluid.CPUPlace()))
    dec = predictor.prepare_decoding(slots=2, **geometry)
    prompt = [3, 1, 4]
    ref_tokens = dec.generate(prompt, 4)

    # save the weights sharded on a training mesh
    mesh = _build_mesh(dict(dp=2, tp=2))
    cache_names = set(dec._pair.cache_names)
    names = [n for n in dec._pair.spec.param_names()
             if n not in cache_names]
    params = {}
    for name in names:
        val = np.asarray(dec._weight_scope.find_var(name))
        spec = ('tp',) if val.ndim and val.shape[0] % 2 == 0 else None
        params[name] = jax.device_put(
            val, mesh_mod.named_sharding(
                mesh, mesh_mod.fit_spec(spec, val.shape, mesh)))
    root = str(tmp_path / 'ckpt')
    checkpoint.save_sharded(root, params, incarnation=0)
    # caches are runtime state: never in the checkpoint
    ckpt = checkpoint.load_checkpoint(root)
    assert not (set(ckpt.var_names()) & cache_names)

    # scramble the live weights, then roll to the sharded checkpoint
    for name in names:
        val = np.asarray(dec._weight_scope.find_var(name))
        dec._weight_scope.set_var(name, np.zeros_like(val))
    dec.load_sharded(root)
    dec.reset()
    assert dec.generate(prompt, 4) == ref_tokens

    # a checkpoint missing a referenced param raises, naming it
    partial = dict(params)
    missing = sorted(partial)[0]
    del partial[missing]
    root2 = str(tmp_path / 'partial')
    checkpoint.save_sharded(root2, partial, incarnation=0)
    with pytest.raises(RuntimeError, match='missing'):
        dec.load_sharded(root2)


# ---------------------------------------------------------------------------
# observability satellite: ckpt.* instruments + trace spans
# ---------------------------------------------------------------------------

def test_ckpt_instruments_and_spans(tmp_path):
    obs_dir = str(tmp_path / 'obs')
    telemetry.reset()
    telemetry.enable()
    trace.enable(obs_dir, role='ckpt-test')
    try:
        mesh = _build_mesh(dict(dp=4))
        root = str(tmp_path / 'ck')
        checkpoint.save_sharded(root, _place(_ref_values(), mesh),
                                incarnation=0)
        got, _, _ = checkpoint.restore_sharded(root, mesh=mesh)
        assert got is not None
    finally:
        trace.disable()
        telemetry.disable()
    snap = telemetry.snapshot()
    telemetry.reset()
    assert snap['counters']['ckpt.generations'] == 1
    assert snap['hists']['ckpt.save_latency']['count'] == 1
    assert snap['hists']['ckpt.restore_latency']['count'] == 1
    assert snap['hists']['ckpt.bytes_written']['sum'] > 0
    spans = set()
    for fn in os.listdir(obs_dir):
        with open(os.path.join(obs_dir, fn)) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get('type') == 'span':
                    spans.add(rec['name'])
    assert {'ckpt.snapshot', 'ckpt.write',
            'ckpt.restore.open', 'ckpt.restore.read'} <= spans


# ---------------------------------------------------------------------------
# the sweep tool's --mesh-kill leg (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.timeout(600)
def test_chaos_sweep_mesh_kill_leg():
    env = dict(os.environ)
    env.pop('JAX_PLATFORMS', None)
    env.pop('XLA_FLAGS', None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(_TESTS, '..', 'tools', 'chaos_sweep.py'),
         '--mesh-kill', '--quick', '--seeds', '1'],
        capture_output=True, text=True, timeout=560, env=env)
    assert proc.returncode == 0, proc.stdout + '\n' + proc.stderr
    assert 'recovered' in proc.stdout or 'nokill' in proc.stdout
