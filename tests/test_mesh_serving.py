"""Mesh-sharded serving: GSPMD prefill/decode over the paged KV cache
(ISSUE 20 acceptance).

The contracts under test:
- greedy decode on a 1x1 mesh is BIT-exact (list equality on token
  ids) against the plain single-chip path — mesh mode is a layout
  change, never an arithmetic change
- dense, paged, and speculative predictors on a tp=2 serving mesh all
  reproduce the single-chip stream bit-exactly, with compile-once
  preserved (jit_cache_stats compiled_segments stable across
  generates) and the page pool physically sharded on its heads axis
- a TP-trained program (use_tp=True) survives save_inference_model:
  the transpiler recovers each weight's PartitionSpec from the
  sharding_constraint ops (column fc -> (None, 'tp'), row fc ->
  ('tp', None)), serve_param_specs() keeps the column-style subset,
  and the loaded model serves bit-exact on tp=2 with qkv/up weights
  physically sharded
- cross-topology: a sharded checkpoint saved on a dp=2,tp=2 TRAINING
  mesh rolls into predictors serving on a 2x2 mesh and on tp=2 via
  load_sharded, both bit-exact — train-on-n/serve-on-m is a pure
  reshard
- genuinely unsupported layouts stay a loud DecodeTranspileError
  naming the op (moe_ffn, ring_attention) or the unknown mesh axis
- the serving stats surface (ServingEngine -> SRV_HEALTH) carries
  mesh_shape / mesh_devices
- the chaos_sweep --mesh-serve leg: kill-9 of a mesh-backed replica
  mid-stream recovers with streams bit-exact vs the single-chip
  fleet baseline (slow)
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import checkpoint, unique_name
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.models.transformer import (TransformerConfig,
                                           language_model_logits)
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.transpiler.decode_transpiler import (
    DecodeTranspileError, extract_decode_spec)
from test_paged import _save_lm

_TESTS = os.path.dirname(os.path.abspath(__file__))

CFG = TransformerConfig(vocab=64, dim=32, heads=4, layers=2, ffn=64,
                        max_len=32)
TP_CFG = TransformerConfig(vocab=64, dim=32, heads=4, layers=2, ffn=64,
                           max_len=32, use_tp=True)
PROMPT = [3, 11, 5, 2]
GEN = 10


@pytest.fixture(scope='module')
def lm_dir(tmp_path_factory):
    tmp = tmp_path_factory.mktemp('mesh_lm')
    _save_lm(tmp, CFG, 7)
    return str(tmp)


@pytest.fixture(scope='module')
def tp_lm_dir(tmp_path_factory):
    tmp = tmp_path_factory.mktemp('mesh_tp_lm')
    _save_lm(tmp, TP_CFG, 7)
    return str(tmp)


def _predictor(model_dir):
    # every mesh predictor gets its OWN AnalysisPredictor: mesh mode
    # pins the parent scope's weights onto the serving mesh, so
    # sharing one across single-chip and mesh decs would reshard the
    # reference path mid-test
    return AnalysisPredictor(AnalysisConfig(model_dir,
                                            place=fluid.CPUPlace()))


@pytest.fixture(scope='module')
def ref_stream(lm_dir):
    return _predictor(lm_dir).prepare_decoding(slots=2).generate(
        PROMPT, GEN)


@pytest.fixture(scope='module')
def tp_ref_stream(tp_lm_dir):
    return _predictor(tp_lm_dir).prepare_decoding(slots=2).generate(
        PROMPT, GEN)


# --------------------------------------------------------------------------
# bit-exact parity: 1x1 degenerate mesh, tp=2 dense/paged/speculative
# --------------------------------------------------------------------------

def test_mesh_1x1_bit_exact(lm_dir, ref_stream):
    dec = _predictor(lm_dir).prepare_decoding(slots=2, mesh='tp=1')
    # build() canonicalizes the degenerate all-size-1 spec to 'dp=1'
    assert dec.mesh_shape == 'dp=1' and dec.mesh_devices == 1
    assert dec.generate(PROMPT, GEN) == ref_stream


def test_mesh_tp2_dense_bit_exact_and_compile_once(lm_dir, ref_stream):
    dec = _predictor(lm_dir).prepare_decoding(slots=2, mesh='tp=2')
    assert dec.mesh_shape == 'tp=2' and dec.mesh_devices == 2
    assert dec.generate(PROMPT, GEN) == ref_stream
    # compile-once survives sharding: a second stream re-enters the
    # SAME compiled SPMD programs (state round-trips under pinned
    # shardings, so donation never changes the layout)
    before = dict(dec.jit_cache_stats())
    dec.generate([5, 9], GEN)
    after = dict(dec.jit_cache_stats())
    assert after['compiled_segments'] == before['compiled_segments']
    assert after['segment_misses'] == before['segment_misses']


def test_mesh_tp2_paged_bit_exact_pool_sharded(lm_dir, ref_stream):
    dec = _predictor(lm_dir).prepare_decoding(
        slots=2, page_tokens=4, prefill_chunk=8,
        mesh='tp=2')
    assert dec.generate(PROMPT, GEN) == ref_stream

    def pool_spec():
        pool = dec._scope.find_var(dec._pair.cache_names[0])
        return tuple(pool.sharding.spec)
    # per-layer pool [pages, page_tokens, heads, dk] shards on heads
    assert pool_spec() == (None, None, 'tp', None)
    # the preempt save/restore round-trip re-pins the pool in place —
    # sharding identical after a stream's pages leave and return
    dec.reset()
    dec.open_stream(0, PROMPT)
    while dec.prefill_step(0) is None:
        pass
    snap = dec.save_stream(0)
    dec.release(0)
    dec.restore_stream(0, snap)
    assert pool_spec() == (None, None, 'tp', None)
    dec.reset()
    assert dec.generate(PROMPT, GEN) == ref_stream


def test_mesh_tp2_speculative_bit_exact(lm_dir, ref_stream):
    dec = _predictor(lm_dir).prepare_decoding(
        slots=2, speculative=True, spec_k=2, draft_layers=1,
        page_tokens=4, prefill_chunk=8, mesh='tp=2')
    assert dec.generate(PROMPT, GEN) == ref_stream


# --------------------------------------------------------------------------
# TP spec recovery: the lifted hard-reject (satellite 1 + tentpole)
# --------------------------------------------------------------------------

def test_tp_model_spec_recovery_and_tp2_serving(tp_lm_dir,
                                                tp_ref_stream):
    """A use_tp=True program reloaded from save_inference_model (all
    dist_attr lost) recovers its weight PartitionSpecs from the
    surviving sharding_constraint ops and serves bit-exact on tp=2."""
    dec = _predictor(tp_lm_dir).prepare_decoding(slots=2, mesh='tp=2')
    specs = dec._pair.spec.param_specs
    for layer in range(TP_CFG.layers):
        assert specs['layer%d_qkv_0.w' % layer] == (None, 'tp')
        assert specs['layer%d_up_0.w' % layer] == (None, 'tp')
        assert specs['layer%d_proj_0.w' % layer] == ('tp', None)
        assert specs['layer%d_down_0.w' % layer] == ('tp', None)
    # only column-style layouts survive to serving (a row-sharded
    # weight would change the reduction order -> not bit-exact)
    serve = dec._pair.spec.serve_param_specs()
    assert set(serve) == {'layer%d_%s_0.w' % (l, k)
                          for l in range(TP_CFG.layers)
                          for k in ('qkv', 'up')}
    assert all(s == (None, 'tp') for s in serve.values())
    assert dec.generate(PROMPT, GEN) == tp_ref_stream
    # a column weight really lives sharded on the serving mesh
    w = dec._weight_scope.find_var('layer0_qkv_0.w')
    assert tuple(w.sharding.spec) == (None, 'tp')
    assert len(w.sharding.device_set) == 2


# --------------------------------------------------------------------------
# cross-topology: sharded checkpoint saved on a training mesh, served
# resharded on 2x2 and tp=2 (acceptance)
# --------------------------------------------------------------------------

def test_cross_topology_resharded_decode_bit_exact(tp_lm_dir,
                                                   tp_ref_stream,
                                                   tmp_path):
    # save the TP model's weights SHARDED on a dp=2,tp=2 TRAINING mesh
    src = _predictor(tp_lm_dir).prepare_decoding(slots=2)
    tmesh = mesh_mod.MeshConfig(dp=2, tp=2).build()
    cache = set(src._pair.cache_names)
    params = {}
    for n in src._pair.spec.param_names():
        if n in cache:
            continue
        v = np.asarray(src._weight_scope.find_var(n))
        spec = mesh_mod.fit_spec(('tp',) if v.ndim else None,
                                 v.shape, tmesh)
        params[n] = jax.device_put(
            v, mesh_mod.named_sharding(tmesh, spec))
    root = str(tmp_path / 'ckpt')
    checkpoint.save_sharded(root, params, incarnation=0)

    # same checkpoint, two different SERVING topologies: paged on the
    # full 2x2 mesh, dense on tp=2 — weights scrambled first so the
    # stream can only come from the resharded checkpoint bytes
    for mesh_spec, kwargs in [
            ('dp=2,tp=2', dict(page_tokens=4,
                               prefill_chunk=8)),
            ('tp=2', {})]:
        dec = _predictor(tp_lm_dir).prepare_decoding(
            slots=2, mesh=mesh_spec, **kwargs)
        for n in params:
            v = np.asarray(dec._weight_scope.find_var(n))
            dec._weight_scope.set_var(n, np.zeros_like(v))
        dec.load_sharded(root)
        dec.reset()
        assert dec.generate(PROMPT, GEN) == tp_ref_stream, mesh_spec


# --------------------------------------------------------------------------
# unsupported layouts: still a loud, named error
# --------------------------------------------------------------------------

def _build_program(cfg):
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 7
    with unique_name.guard(), program_guard(prog, startup):
        toks = fluid.layers.data(name='tokens',
                                 shape=[1, cfg.max_len, 1],
                                 dtype='int64', append_batch_size=False)
        language_model_logits(toks, cfg)
    return prog


@pytest.mark.parametrize('kwargs,pattern', [
    (dict(moe_experts=2), 'moe_ffn'),
    (dict(ring_attention=True, use_sp=True), 'ring_attention'),
], ids=['moe_ffn', 'ring_attention'])
def test_unsupported_ops_fail_loud(kwargs, pattern):
    cfg = TransformerConfig(vocab=64, dim=32, heads=4, layers=1,
                            ffn=64, max_len=16, **kwargs)
    with pytest.raises(DecodeTranspileError, match=pattern):
        extract_decode_spec(_build_program(cfg))


def test_unknown_mesh_axis_fails_loud_naming_weight():
    cfg = TransformerConfig(vocab=64, dim=32, heads=4, layers=1,
                            ffn=64, max_len=16)
    prog = _build_program(cfg)
    blk = prog.global_block()
    wname = [v for v in blk.vars if v.endswith('qkv_0.w')][0]
    blk.var(wname).dist_attr = (None, 'zz')
    with pytest.raises(DecodeTranspileError,
                       match='unknown mesh axis'):
        extract_decode_spec(prog)


# --------------------------------------------------------------------------
# stats surface: mesh_shape / mesh_devices reach the health wire
# --------------------------------------------------------------------------

def test_server_stats_carry_mesh_shape(lm_dir, ref_stream):
    from paddle_tpu.serving import LMServer
    with LMServer(lm_dir, slots=2, workers=1, mesh='tp=2') as srv:
        assert srv.generate(PROMPT, GEN) == ref_stream
        stats = srv.stats()
        assert stats['mesh_shape'] == 'tp=2'
        assert stats['mesh_devices'] == 2


# --------------------------------------------------------------------------
# the sweep tool's --mesh-serve leg (slow)
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.timeout(600)
def test_chaos_sweep_mesh_serve_leg():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('XLA_FLAGS', None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(_TESTS, '..', 'tools', 'chaos_sweep.py'),
         '--mesh-serve', '--quick', '--seeds', '1', '--budget', '420'],
        capture_output=True, text=True, timeout=560, env=env)
    assert proc.returncode == 0, proc.stdout + '\n' + proc.stderr
    assert 'recovered' in proc.stdout or 'nokill' in proc.stdout
