"""Two classes of request in ONE queue, half of the requests each: long
ones, one of the deployment's cached documents followed by a fresh
question (harness/traffic_docs.py's corpus and its design of triples),
and short ones, a prompt with no shared prefix. A service that answers
ordinary chat turns and questions about long documents from one queue
on one model (the model-configs guide's "short and long in one queue").

The multiset of (class, document, prompt length, output length) is one
fixed design for every seed (`design`): the long half is
traffic_docs.triples, the short half pairs the quantiles of its
log-uniform prompt and output laws by a permutation drawn from the
mix's own `design_seed`, and the two classes alternate down the design.
--seed decides the order of arrival, which gap goes where and the token
ids, and every part of the plan (the judged window, the tail behind it)
replays the seed's one deal of order and gaps with token ids of its
own, so a window holds the same work under every seed.
"""
from __future__ import annotations

import numpy as np

from . import sampling, traffic_docs


def design(params, n):
    """n requests in design order, long and short alternating:
    ('long', document, question length, output length) or ('short',
    None, prompt length, output length)."""
    n_long = n // 2
    n_short = n - n_long
    long_part = [('long',) + t for t in traffic_docs.triples(params, n_long)]
    p_lo, p_hi = params['prompt_tokens']
    o_lo, o_hi = params['short_output_tokens']
    prompts = sampling.log_uniform_lengths(p_lo, p_hi, n_short)
    outputs = sampling.log_uniform_lengths(o_lo, o_hi, n_short)
    pairing = sampling.rng_of(params['design_seed'], 1).permutation(n_short)
    short_part = [('short', None, int(prompts[i]), int(outputs[pairing[i]]))
                  for i in range(n_short)]
    out = []
    for i in range(n_short):
        out.append(short_part[i])
        if i < n_long:
            out.append(long_part[i])
    return out


def mixed(params, seed, model, seconds):
    """Plan for the open-loop drive, as harness/traffic.open_loop makes
    it: round(rate * seconds) judged requests due inside the window at
    exponential gaps, then as many again, unjudged."""
    rate = float(params['rate_rps'])
    n = max(1, int(round(rate * seconds)))
    docs = traffic_docs.documents(params, model)
    vocab = int(model['vocab_size'])
    context = int(model['n_positions'])
    plan = {'judged': n, 'timeout_s': float(params['timeout_s']),
            'requests': []}
    # the seed's one deal, replayed in both parts
    deal = sampling.rng_of(seed, 12)
    gaps = sampling.shuffled(sampling.exponential_gaps(rate, n), deal)
    order = deal.permutation(n)
    requests = design(params, n)
    start = 0.0
    for stream in (2, 3):
        rng = sampling.rng_of(seed, stream + 20)        # this part's ids
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        start += float(gaps.sum())
        for i, t in zip(order, due):
            kind, d, p, o = requests[i]
            if kind == 'long':
                p = min(p, context - o - len(docs[d]))
                prompt = np.concatenate([docs[d], rng.integers(
                    1, vocab, size=p, dtype=np.int64)])
            else:
                prompt = rng.integers(1, vocab, size=min(p, context - o),
                                      dtype=np.int64)
            plan['requests'].append({'prompt': prompt, 'max_new': o,
                                     'due': float(t), 'class': kind,
                                     'document': d})
    return plan
