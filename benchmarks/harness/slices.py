"""What a kernel's reader pairs, one rule for every program: an op's
executions in the traced slice (`trace.reduce_events`' `op_runs`, in
whatever program the op ran) and what those executions carried, which the
program itself said of each step (the slice's own counts,
`builders/gpt2.slice_counts` and `_StepProbe.counters`: sums over the
steps of the slice's seconds, so rows and seconds are of the same
executions)."""


def expert_least(run, costs, sublayers, peak):
    """The least seconds the chip could take for the `moe_experts` ops of
    the traced slice: for the decode steps and the prefill chunks, each at
    its own mean, the larger of the bytes of the held experts a layer's
    rows chose over the HBM peak and the pairs' FLOPs over the bf16 peak
    (`costs.expert_bytes` / `costs.expert_flops`), for the layer calls the
    expert sublayers counted in the slice's seconds, scaled to the
    executions the trace holds (`op_runs` x `sublayers` a run). None where
    the op did not run or nothing was counted."""
    c, m = run['counters'], run['config']
    runs = run['trace']['op_runs'].get('moe_experts', 0)
    groups = [(c.get(pre + 'layer_calls', 0), c.get(pre + 'experts_touched'),
               c.get(pre + 'pairs'))
              for pre in ('slice_moe_', 'slice_moe_prefill_')]
    counted = sum(calls for calls, _, _ in groups)
    if not runs or not counted:
        return None
    least = sum(
        calls * max(costs.expert_bytes(m, experts / calls)
                    / peak['hbm_bytes_s'],
                    costs.expert_flops(m, pairs / calls) / peak['bf16_flops'])
        for calls, experts, pairs in groups if calls)
    return least * runs * sublayers / counted
