"""The general traffic generators. A traffic mix is a data file
(benchmarks/traffic/<name>.json) naming one of these by `generator` and
one of harness/drives.py's loops by `drive`; its parameters are data.
A generator takes (params, seed, model) and returns the plan its drive
consumes. --seed decides order, pairing and token ids, never the
multiset of lengths or gaps (harness/sampling.py).
"""
from __future__ import annotations

import numpy as np

from . import sampling


def train_batches(params, seed, model, seconds):
    """Plan for the steps drive: sequences of `seq_len` uniform token
    ids, `per_step` of them a step, split evenly over the cell's chips."""
    return {'seq_len': int(params['seq_len']),
            'per_step': int(params['per_step']),
            'vocab': int(model['vocab_size']), 'seed': int(seed)}


def batch_stream(plan):
    """Endless distinct [B, T, 1] (tokens, labels) batches of a plan."""
    rng = sampling.rng_of(plan['seed'], 1)
    shape = (plan['per_step'], plan['seq_len'], 1)
    while True:
        toks = rng.integers(0, plan['vocab'], size=shape, dtype=np.int64)
        yield [toks, np.roll(toks, -1, axis=1)]


def _requests(params, n, seed, stream, model):
    """n requests: prompt and output lengths on their quantile grids,
    paired and ordered by the seed; prompt + output <= context."""
    rng = sampling.rng_of(seed, stream)
    p_lo, p_hi = params['prompt_tokens']
    o_lo, o_hi = params['output_tokens']
    prompts = sampling.shuffled(
        sampling.log_uniform_lengths(p_lo, p_hi, n), rng)
    outputs = sampling.shuffled(
        sampling.log_uniform_lengths(o_lo, o_hi, n), rng)
    context = int(model['n_positions'])
    vocab = int(model['vocab_size'])
    reqs = []
    for i in range(n):
        p, o = int(prompts[i]), int(outputs[i])
        p = min(p, context - o)
        reqs.append({'prompt': rng.integers(1, vocab, size=p,
                                            dtype=np.int64),
                     'max_new': o})
    return reqs


def open_loop(params, seed, model, seconds):
    """Plan for the open-loop drive: round(rate * seconds) judged
    requests due inside the window at exponential gaps, then as many
    again at the same rate, unjudged, to keep the load on while the
    judged ones finish."""
    rate = float(params['rate_rps'])
    n = max(1, int(round(rate * seconds)))
    plan = {'judged': n, 'timeout_s': float(params['timeout_s']),
            'requests': []}
    start = 0.0
    for part, stream in (('judged', 2), ('tail', 3)):
        rng = sampling.rng_of(seed, stream + 10)
        gaps = sampling.shuffled(sampling.exponential_gaps(rate, n), rng)
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        start += float(gaps.sum())
        for r, t in zip(_requests(params, n, seed, stream, model), due):
            r['due'] = float(t)
            plan['requests'].append(r)
    return plan
