"""Operations and bytes the GraniteMoeHybrid block
(paddle_tpu/models/granite_h.py) needs, from its shapes alone. `m` is a
configuration file's keys (HF granitemoehybrid names;
`num_local_experts` counts the experts HELD, `router_experts` the
published count the router keeps); the layers run are the first
`num_hidden_layers` of `layer_types`, each a mixer and an expert
sublayer. Everything is float32 (4 bytes). Norm gains are counted with
their layer, the embedding's rows are a gather and are left out of a
step's bytes; the tied head reads the embedding once a step.
"""
BYTES = 4


def kinds(m):
    return list(m['layer_types'][:int(m['num_hidden_layers'])])


def _mamba(m):
    """(heads H, head size P, groups G, state N, taps K, inner H P,
    convolved channels H P + 2 G N)."""
    h, p = int(m['mamba_n_heads']), int(m['mamba_d_head'])
    g, n = int(m['mamba_n_groups']), int(m['mamba_d_state'])
    return h, p, g, n, int(m['mamba_d_conv']), h * p, h * p + 2 * g * n


def expert_params(m):
    """One routed expert: W1, W3 [d, F] and W2 [F, d]."""
    return 3 * int(m['hidden_size']) * int(m['intermediate_size'])


def mixer_params(m, kind):
    """A mixer with its norm. mamba: the in-projection d (2 H P + 2 G N
    + H), the convolution (K + 1) (H P + 2 G N), dt_bias, A_log and D
    3 H, the gated norm H P, the out-projection H P d. attention: q and
    o d H dh each, k and v d KVH dh each."""
    d = int(m['hidden_size'])
    if kind == 'mamba':
        h, _, _, _, k, inner, conv = _mamba(m)
        return (d * (inner + conv + h) + (k + 1) * conv + 3 * h + inner
                + inner * d + d)
    heads = int(m['num_attention_heads'])
    dh = int(m.get('head_dim') or d // heads)
    return 2 * d * heads * dh + 2 * d * int(m['num_key_value_heads']) * dh + d


def sublayer_params(m, held=None):
    """An expert sublayer with its norm outside its routed experts (the
    router d E, the shared expert 3 d S), and `held` routed experts
    (those the file holds where not given)."""
    d = int(m['hidden_size'])
    held = int(m['num_local_experts']) if held is None else held
    return (d * int(m.get('router_experts', m['num_local_experts']))
            + 3 * d * int(m['shared_intermediate_size']) + d
            + held * expert_params(m))


def param_count(m):
    """All parameters held: the layers run, the tied embedding over the
    vocabulary served, the final norm."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    return sum(mixer_params(m, k) + sublayer_params(m) for k in kinds(m)) \
        + v * d + d


def weight_bytes(m):
    return BYTES * param_count(m)


def state_bytes_per_lane(m):
    """One lane's state and convolution rows in ONE mamba layer: H P N
    and (K - 1) (H P + 2 G N)."""
    h, p, _, n, k, _, conv = _mamba(m)
    return BYTES * (h * p * n + (k - 1) * conv)


def snapshot_row_bytes(m):
    """One snapshot row, which is one slot's recurrent state: every
    mamba layer's state and convolution rows."""
    return kinds(m).count('mamba') * state_bytes_per_lane(m)


def state_copy_bytes(m):
    """Bytes ONE run of a state copy program has to move: a row read and
    a row written."""
    return 2 * snapshot_row_bytes(m)


def kv_bytes_per_token(m):
    """K and V of one token in the attention layers."""
    heads = int(m['num_attention_heads'])
    dh = int(m.get('head_dim') or int(m['hidden_size']) // heads)
    return BYTES * 2 * int(m['num_key_value_heads']) * dh \
        * kinds(m).count('attention')


def expert_bytes(m, experts_touched):
    """Bytes ONE moe_experts op has to read: the three matrices of each
    held expert that at least one of its rows chose."""
    return BYTES * experts_touched * expert_params(m)


def expert_flops(m, pairs):
    """FLOPs ONE moe_experts op needs: three products for each pair of
    row and held expert."""
    return 2 * pairs * expert_params(m)


def decode_step_bytes(m, live_tokens, state_lanes, experts_touched):
    """Bytes one decode step HAS to move: every weight outside the
    routed experts once (the tied embedding among them: the head reads
    it), the three matrices of the `experts_touched` experts a layer
    that the step's lanes chose among those held (a mean over the
    layers), the K and V of every live token in the attention layers,
    and for each lane that takes part its state and convolution rows,
    read and written, in every mamba layer."""
    dense = param_count(m) \
        - len(kinds(m)) * int(m['num_local_experts']) * expert_params(m)
    return (BYTES * (dense + len(kinds(m)) * experts_touched
                     * expert_params(m))
            + live_tokens * kv_bytes_per_token(m)
            + 2 * state_lanes * snapshot_row_bytes(m))
