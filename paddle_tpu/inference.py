"""Inference engine: the Predictor ABI over saved inference models.

Capability analog of the reference inference API —
paddle/fluid/inference/api/paddle_inference_api.h (PaddlePredictor,
NativeConfig, CreatePaddlePredictor) — redesigned for the XLA execution
model: a Predictor owns a private Scope with the loaded weights resident
on device, the pruned inference Program compiles ONCE per fed batch
shape through the executor's whole-block jit cache, and clone() shares
the weight scope between predictors (the reference's
PaddlePredictor::Clone contract) so serving threads don't duplicate HBM.

The reference's TensorRT/analysis sub-engines are N/A by design: XLA is
the graph optimizer here.
"""
from __future__ import annotations

import numpy as np

from . import io as io_mod
from .executor import Executor, Scope, TPUPlace, scope_guard

__all__ = ['Config', 'Predictor', 'create_predictor',
           'create_paddle_predictor', 'AnalysisConfig',
           'AnalysisPredictor', 'create_analysis_predictor']


class Config(object):
    """(reference NativeConfig) model_dir holds a save_inference_model
    artifact; model_filename/params_filename follow io.py's layout."""

    def __init__(self, model_dir, model_filename=None,
                 params_filename=None, place=None):
        self.model_dir = model_dir
        self.model_filename = model_filename
        self.params_filename = params_filename
        self.place = place


class Predictor(object):
    def __init__(self, config, _clone_of=None):
        self._config = config
        self._place = config.place if config.place is not None \
            else TPUPlace()
        self._exe = Executor(self._place)
        if _clone_of is not None:
            # clone from memory (reference PaddlePredictor::Clone is
            # independent of the model directory): share the weight
            # scope, copy the program so compile caches stay per-clone
            self._scope = _clone_of._scope
            self._program = _clone_of._program.clone(for_test=True)
            self._feed_names = list(_clone_of._feed_names)
            self._fetch_vars = [
                self._program.global_block().var(v.name)
                for v in _clone_of._fetch_vars]
        else:
            self._scope = Scope()
            with scope_guard(self._scope):
                (self._program, self._feed_names,
                 self._fetch_vars) = io_mod.load_inference_model(
                    config.model_dir, self._exe,
                    model_filename=config.model_filename,
                    params_filename=config.params_filename)
        self._program._is_test = True

    # -- reference PaddlePredictor surface ---------------------------------
    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return [v.name for v in self._fetch_vars]

    def run(self, inputs, return_numpy=True):
        """inputs: dict name->array, or list matching get_input_names()
        order. Returns list of np.ndarray outputs — or, with
        return_numpy=False, device arrays without a host sync (the
        async serving/throughput path: dispatches pipeline, and the
        caller fetches when it actually needs values)."""
        if not isinstance(inputs, dict):
            if len(inputs) != len(self._feed_names):
                raise ValueError(
                    'predictor expects %d inputs %s, got %d'
                    % (len(self._feed_names), self._feed_names,
                       len(inputs)))
            inputs = dict(zip(self._feed_names, inputs))
        else:
            # validate the dict against the model ABI up front: the
            # executor would only notice a missing feed deep inside
            # compilation, and would silently ignore an unknown one
            unknown = sorted(set(inputs) - set(self._feed_names))
            missing = sorted(set(self._feed_names) - set(inputs))
            if unknown or missing:
                parts = []
                if unknown:
                    parts.append('unknown input name(s) %s' % unknown)
                if missing:
                    parts.append('missing input name(s) %s' % missing)
                raise ValueError(
                    '%s — this model\'s inputs are get_input_names() '
                    '= %s' % ('; '.join(parts), self._feed_names))
        # scope= kwarg, NOT scope_guard: run() must be safe from serving
        # threads, and the guard swaps a process-global
        outs = self._exe.run(self._program, feed=inputs,
                             fetch_list=self._fetch_vars,
                             scope=self._scope,
                             return_numpy=return_numpy)
        if not return_numpy:
            return outs
        return [np.asarray(o) for o in outs]

    def clone(self):
        """A predictor sharing this one's weights (device arrays are
        shared through the common Scope; programs/compile caches are
        per-clone). Works from memory — the model dir may be gone."""
        return Predictor(self._config, _clone_of=self)


def create_predictor(config):
    return Predictor(config)


# reference CreatePaddlePredictor spelling
create_paddle_predictor = create_predictor


class AnalysisConfig(Config):
    """(reference contrib AnalysisConfig / analysis_predictor.cc) —
    Config plus IR-optimization switches consumed by
    AnalysisPredictor."""

    def __init__(self, model_dir, model_filename=None,
                 params_filename=None, place=None, ir_optim=True):
        super(AnalysisConfig, self).__init__(
            model_dir, model_filename=model_filename,
            params_filename=params_filename, place=place)
        self.ir_optim = ir_optim

    def switch_ir_optim(self, flag=True):
        self.ir_optim = flag
        return self


class AnalysisPredictor(Predictor):
    """Predictor that runs offline graph rewrites on the loaded program
    before serving (reference inference/api/analysis_predictor.cc runs
    the ir fusion passes — fc_fuse, conv+bn, ... — before Prepare).
    Here the rewrite set is the InferenceTranspiler's batch-norm
    folding; elementwise/activation fusion is XLA's job at JIT time, so
    those reference passes have no offline analog by design."""

    def __init__(self, config, _clone_of=None):
        super(AnalysisPredictor, self).__init__(config, _clone_of=_clone_of)
        if _clone_of is None and getattr(config, 'ir_optim', True):
            from .transpiler import InferenceTranspiler
            InferenceTranspiler().transpile(
                self._program, self._place, scope=self._scope)

    def clone(self):
        return AnalysisPredictor(self._config, _clone_of=self)

    def prepare_decoding(self, slots=None, page_tokens=None, kv_pages=None,
                         prefill_chunk=None, speculative=False,
                         spec_k=None, draft_layers=None,
                         draft_predictor=None, mesh=None, paged=True,
                         snapshot_rows=0, window_pages=None):
        """Transpile the loaded LM into the paged prefill + decode pair
        and return a serving.PagedDecodePredictor over this predictor's
        weight scope — page-pool cache with copy-on-write prefix sharing
        and chunked prefill (serving/paged.py; page_tokens / kv_pages /
        prefill_chunk default from FLAGS_serving_*). speculative=True
        returns a serving.SpeculativeDecodePredictor: draft/verify
        greedy speculation with bit-exact acceptance
        (serving/speculative.py; spec_k / draft_layers default from
        FLAGS_spec_*; draft_predictor supplies an explicit smaller
        draft LM instead of the layer-truncated self-draft). mesh makes
        every decode/prefill/verify program ONE GSPMD SPMD program over
        a device mesh ('tp=2' / MeshConfig / jax Mesh; None = read
        FLAGS_serve_mesh_shape, '' = single-chip) — greedy decode stays
        bit-exact vs single-chip (serving/mesh.py). snapshot_rows, a
        size of the deployment beside slots and kv_pages for a model
        with recurrent layers: how many prefix boundaries keep their
        recurrent state on the device, so that the prefix cache can
        hand out their pages (0: none are kept and nothing is shared,
        as for every such model before). window_pages, for a model
        with sliding-window layers: the pages the pools of those layers
        hold, sized apart from kv_pages (None: a full window table for
        every slot); speculation, a mesh, page shipping and
        save_stream / restore_stream refuse such a model by name. Raises
        transpiler.DecodeTranspileError if the program is not a
        recognizable decoder-only LM."""
        # `paged` is accepted only because benchmarks/builders still pass
        # paged=True (ROADMAP R0c drops it there, then here)
        if not paged:
            raise ValueError(
                'prepare_decoding(paged=False): the dense ring KV cache '
                'was removed; every decoder serves from the page pool')
        if speculative:
            if snapshot_rows:
                raise ValueError('snapshot_rows with speculative=True: '
                                 'speculation refuses recurrent state')
            if window_pages:
                raise ValueError('window_pages with speculative=True: '
                                 'speculation refuses sliding layers')
            from .serving import SpeculativeDecodePredictor
            return SpeculativeDecodePredictor(
                self, slots=slots, spec_k=spec_k,
                draft_layers=draft_layers,
                draft_predictor=draft_predictor,
                page_tokens=page_tokens, kv_pages=kv_pages,
                prefill_chunk=prefill_chunk, mesh=mesh)
        from .serving import PagedDecodePredictor
        return PagedDecodePredictor(self, slots=slots,
                                    page_tokens=page_tokens,
                                    kv_pages=kv_pages,
                                    prefill_chunk=prefill_chunk,
                                    mesh=mesh, snapshot_rows=snapshot_rows,
                                    window_pages=window_pages)


def create_analysis_predictor(config):
    if not isinstance(config, AnalysisConfig):
        config = AnalysisConfig(
            config.model_dir, model_filename=config.model_filename,
            params_filename=config.params_filename, place=config.place)
    return AnalysisPredictor(config)
