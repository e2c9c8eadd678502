"""harness/traffic_sessions.chat_sessions under ONE deal: a trace,
replayed in every run with other words in it.

chat_sessions fixes a window's design (the sessions, the multisets of
gaps, message, answer and think lengths) and lets --seed deal it: which
gap goes where, the order in which the sessions arrive, which lengths
meet in a turn. Here the mix's own `deal_seed` deals it, as `sys_seed`
draws the system prompts: the deployment's, the same in every run.
--seed still makes the inputs: every token behind a system prompt is
relabelled by a permutation of the vocabulary drawn from --seed (what
two prompts share they still share), so two seeds send conversations of
the same shapes at the same times with other words in them, and other
weights answer them.

Why (PERF.md section 6, PR 60): a session of agent_loops_open is a burst
of 6-10 turns 0.3-1.5 s apart, a window of 45 s holds 68 of them and
about ten run at once, five to sixteen as the deal has it, in waves of
5-10 s. A gap between two tokens costs what the wave it falls in costs
(a chunk in front of a step is three plain gaps), so `tpot_p50_ms`
follows the deal: 6.0 % over six seeds on the chip where a new cell is
admitted under 3 %, at every rate tried, and still 9.5 % from the least
to the largest of three with the sessions' starts one even gap apart. A
queue model of the engine's pass ranked the chip's seeds by their deals
alone.
"""
from __future__ import annotations

import numpy as np

from . import sampling, traffic_sessions


def replayed_sessions(params, seed, model, seconds):
    """Plan for the open-loop drive, as traffic_sessions.chat_sessions
    gives it (same keys, same meaning) for `deal_seed`, relabelled."""
    plan = traffic_sessions.chat_sessions(
        params, params['deal_seed'], model, seconds)
    system = traffic_sessions.system_prompts(params, model)
    vocab = int(model['vocab_size'])
    # ids are drawn from 1 .. vocab - 1
    relabel = np.concatenate([[0], 1 + sampling.rng_of(
        seed, 13).permutation(vocab - 1)])

    def own(prompt, head):
        return np.concatenate([prompt[:head], relabel[prompt[head:]]])

    def head_of(prompt):
        return next(len(s) for s in system if len(prompt) > len(s)
                    and np.array_equal(prompt[:len(s)], s))

    turns = {key: [dict(t, prompt=own(t['prompt'], len(system[t['system']])))
                   for t in plan[key]] for key in ('requests', 'preroll')}
    warm = plan['warm'][:len(system)] + [
        own(p, head_of(p)) for p in plan['warm'][len(system):]]
    return dict(plan, warm=warm, **turns)
