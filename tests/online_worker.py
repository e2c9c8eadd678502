"""Subprocess worker for the online-learning e2e test
(test_online.py::test_online_cluster_serving_tracks_training).

Three roles over one tiny transformer LM:

- pserver: hosts the sliced params, publishes a digest-stamped version
  per closed sync round (ParameterService param_names plumbing);
- trainer: N sync rounds of LM training through the transpiler, then
  prints the crc32 digests of its post-round-N pulled params — the
  version-N truth the serving side must converge to;
- serving: an LMServer with enable_refresh() against the pserver
  fleet; decodes before AND after the refresh loop catches up, then
  prints its installed-param digests. NEVER restarted.

Shutdown choreography (filesystem handshake in ON_DIR): the trainer
finishes its rounds and writes TRAINER_DONE, but holds its COMPLETE
(exe.close()) until the serving process writes SERVING_DONE — pservers
must stay up until the subscriber has pulled version N.

Both processes build the model from a FRESH program with the same
construction order, so unique_name gives the trunk params identical
names (the trainer's loss head rides the same language_model_logits
the serving graph transpiles).
"""
import json
import os
import sys
import time

import jax

jax.config.update('jax_platforms', 'cpu')

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np                     # noqa: E402
import paddle_tpu as fluid             # noqa: E402
from paddle_tpu.distributed import wire               # noqa: E402
from paddle_tpu.integrity import crc32                # noqa: E402
from paddle_tpu.models.transformer import (           # noqa: E402
    TransformerConfig, language_model_logits)

CFG = TransformerConfig(vocab=32, dim=16, heads=2, layers=1, ffn=32,
                        max_len=8, use_tp=False, use_sp=False)
BATCH = 4
PROMPT = [3, 1, 4]
GEN = 8


def _digest(value):
    return crc32(wire._payload_of(np.asarray(value))[1])


def _wait_for(path, timeout=300):
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise RuntimeError('timed out waiting for %s' % path)
        time.sleep(0.05)


def build_logits(batch):
    toks = fluid.layers.data(name='tokens',
                             shape=[batch, CFG.max_len, 1],
                             dtype='int64', append_batch_size=False)
    return language_model_logits(toks, CFG)


def run_trainer(eps, steps, workdir):
    logits = build_logits(BATCH)
    # labels AFTER the trunk: the serving graph stops at the logits, so
    # every unique_name the two processes share is already spent
    labels = fluid.layers.data(name='labels',
                               shape=[BATCH, CFG.max_len, 1],
                               dtype='int64', append_batch_size=False)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, labels))
    params = [p.name for p in
              fluid.default_main_program().global_block()
              .all_parameters()]
    fluid.optimizer.SGD(0.1).minimize(loss)

    t = fluid.DistributeTranspiler()
    t.transpile(0, pservers=eps, trainers=1, sync_mode=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(t.get_trainer_startup_program())
    prog = t.get_trainer_program()
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(steps):
        feed = {'tokens': rng.randint(
                    0, CFG.vocab, (BATCH, CFG.max_len, 1), 'int64'),
                'labels': rng.randint(
                    0, CFG.vocab, (BATCH, CFG.max_len, 1), 'int64')}
        l, = exe.run(prog, feed=feed, fetch_list=[loss])
        losses.append(float(l))
    # post-round-N state: the last fetch_barrier pulled the pserver
    # fleet's version-N bytes into this scope
    digests = {p: _digest(fluid.fetch_var(p)) for p in params
               if fluid.global_scope().find_var(p) is not None}
    with open(os.path.join(workdir, 'TRAINER_DONE'), 'w') as f:
        f.write('done')
    print('RESULT ' + json.dumps({'losses': losses,
                                  'digests': digests}), flush=True)
    # hold COMPLETE until serving has pulled version N — the pservers
    # shut down once every trainer completes
    _wait_for(os.path.join(workdir, 'SERVING_DONE'))
    exe.close()


def run_pserver(eps, steps, pserver_id):
    # same graph + same transpile config as the trainer: the pserver
    # program derives its owned blocks (and param_names) from it
    logits = build_logits(BATCH)
    labels = fluid.layers.data(name='labels',
                               shape=[BATCH, CFG.max_len, 1],
                               dtype='int64', append_batch_size=False)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, labels))
    fluid.optimizer.SGD(0.1).minimize(loss)
    t = fluid.DistributeTranspiler()
    t.transpile(0, pservers=eps, trainers=1, sync_mode=True)
    ep = eps.split(',')[pserver_id]
    main_prog, startup = t.get_pserver_programs(ep)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main_prog)       # blocks until the trainer COMPLETEs


def run_serving(eps, steps, workdir):
    from paddle_tpu.serving import LMServer
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = startup.random_seed = 11
    with fluid.program_guard(prog, startup):
        logits = build_logits(1)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    model_dir = os.path.join(workdir, 'saved_model')
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ['tokens'], [logits],
                                      exe, main_program=prog)
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    pred = AnalysisPredictor(AnalysisConfig(model_dir,
                                            place=fluid.CPUPlace()))
    dec = pred.prepare_decoding(slots=2)
    srv = LMServer(dec)
    try:
        before = srv.generate(PROMPT, max_new_tokens=GEN)
        sub = srv.enable_refresh(eps.split(','))
        # ride the poll loop until version N is installed — NO restart,
        # no manual pull: the subsystem's own machinery must converge
        deadline = time.monotonic() + 240
        while sub.installed_version < steps:
            if time.monotonic() > deadline:
                raise RuntimeError(
                    'refresh never reached version %d: %r'
                    % (steps, sub.stats()))
            time.sleep(0.05)
        after = srv.generate(PROMPT, max_new_tokens=GEN)
        digests = {n: _digest(dec._weight_scope.find_var(n))
                   for n in dec.param_names()}
        stats = srv.stats()
        print('RESULT ' + json.dumps({
            'digests': digests,
            'installed_version': sub.installed_version,
            'refreshes': stats['refreshes'],
            'refresh_failures': stats['refresh_failures'],
            'weight_swaps': stats['weight_swaps'],
            'tokens_before': [int(x) for x in before],
            'tokens_after': [int(x) for x in after]}), flush=True)
        with open(os.path.join(workdir, 'SERVING_DONE'), 'w') as f:
            f.write('done')
    finally:
        srv.close()


def main():
    role = os.environ['ON_ROLE']
    eps = os.environ['PS_ENDPOINTS']
    steps = int(os.environ['PS_STEPS'])
    workdir = os.environ['ON_DIR']
    if role == 'pserver':
        run_pserver(eps, steps, int(os.environ['PS_PSERVER_ID']))
    elif role == 'trainer':
        run_trainer(eps, steps, workdir)
    else:
        run_serving(eps, steps, workdir)


if __name__ == '__main__':
    main()
