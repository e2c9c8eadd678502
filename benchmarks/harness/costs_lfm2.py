"""Operations and bytes the LFM2 block with routed experts
(paddle_tpu/models/lfm2.py) needs, from its shapes alone. `m` is a
configuration file's keys (HF lfm2_moe names); the layers run are the
first `num_hidden_layers` of `layer_types`, each a mixer (a gated short
convolution, or attention on heads of 64) and a feed-forward (a dense
SwiGLU in the first `num_dense_layers`, `num_experts` routed experts,
all held, in the others). Everything is float32 (4 bytes). Norm gains
are counted with their layer; the embedding's rows are a gather and are
left out of a step's bytes; the tied head reads the embedding once a
step.
"""
BYTES = 4


def kinds(m):
    return list(m['layer_types'][:int(m['num_hidden_layers'])])


def head_dim(m):
    return int(m.get('head_dim') or int(m['hidden_size'])
               // int(m['num_attention_heads']))


def expert_params(m):
    """One routed expert: W1, W3 [d, F] and W2 [F, d]."""
    return 3 * int(m['hidden_size']) * int(m['moe_intermediate_size'])


def mixer_params(m, kind):
    """A mixer with its norm. conv: the in-projection d x 3 d, K taps a
    channel, the out-projection d x d. attention: q and o d H dh each, k
    and v d KVH dh each, two gains [dh]."""
    d = int(m['hidden_size'])
    if kind == 'conv':
        return 3 * d * d + int(m['conv_L_cache']) * d + d * d + d
    dh = head_dim(m)
    return (2 * d * int(m['num_attention_heads']) * dh
            + 2 * d * int(m['num_key_value_heads']) * dh + 2 * dh + d)


def ff_params(m, layer, experts=None):
    """Layer `layer`'s feed-forward with its norm: a dense SwiGLU of
    width intermediate_size, or the router d E, its bias E and `experts`
    routed experts (all of them where not given)."""
    d = int(m['hidden_size'])
    if layer < int(m['num_dense_layers']):
        return 3 * d * int(m['intermediate_size']) + d
    e = int(m['num_experts'])
    return d * e + e + d \
        + (e if experts is None else experts) * expert_params(m)


def expert_layers(m):
    return max(0, len(kinds(m)) - int(m['num_dense_layers']))


def param_count(m):
    """All parameters held: the layers run, the tied embedding, the
    final norm."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    return sum(mixer_params(m, k) + ff_params(m, i)
               for i, k in enumerate(kinds(m))) + v * d + d


def weight_bytes(m):
    return BYTES * param_count(m)


def kv_bytes_per_token(m):
    """K and V of one token in the attention layers, at 64-wide rows as
    the pool holds them (two heads a lane row, none padded)."""
    return BYTES * 2 * int(m['num_key_value_heads']) * head_dim(m) \
        * kinds(m).count('full_attention')


def conv_rows_bytes(m):
    """The K-1 rows a conv layer keeps for one page (or one lane's
    newest page): what an entry of its pool holds."""
    return BYTES * (int(m['conv_L_cache']) - 1) * int(m['hidden_size'])


def page_bytes(m, page_tokens):
    """What one page number holds over all pools: the K/V rows of
    `page_tokens` tokens and a conv entry a conv layer."""
    return page_tokens * kv_bytes_per_token(m) \
        + kinds(m).count('conv') * conv_rows_bytes(m)


def expert_bytes(m, experts_touched):
    """Bytes ONE moe_experts op has to read: the three matrices of each
    expert that at least one of its rows chose."""
    return BYTES * experts_touched * expert_params(m)


def expert_flops(m, pairs):
    """FLOPs ONE moe_experts op needs: three products for each pair of
    row and expert."""
    return 2 * pairs * expert_params(m)


def decode_step_bytes(m, live_tokens, lanes, experts_touched):
    """Bytes one decode step HAS to move: every weight outside the
    routed experts once (the tied embedding among them: the head reads
    it), the three matrices of the `experts_touched` experts a layer
    that the step's lanes chose (a mean over the expert layers), the K
    and V of every live token in the attention layers, and for each lane
    its conv rows, read and written, in every conv layer."""
    n_e = expert_layers(m)
    dense = param_count(m) \
        - n_e * int(m['num_experts']) * expert_params(m)
    return (BYTES * (dense + n_e * experts_touched * expert_params(m))
            + live_tokens * kv_bytes_per_token(m)
            + 2 * lanes * kinds(m).count('conv') * conv_rows_bytes(m))
