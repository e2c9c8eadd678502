"""Operations and bytes the SmallThinker block
(paddle_tpu/models/smallthinker.py) needs, from its shapes alone. `m` is
a configuration file's keys (HF smallthinker names); the layers run are
the first `num_hidden_layers` entries of the published layouts, each an
attention sublayer (global, or sliding over `sliding_window_size`
tokens) and an expert sublayer whose router reads the attention's input.
Everything is float32 (4 bytes). Norm gains are counted with their
layer; the embedding's rows are a gather and are left out of a step's
bytes; the untied head is read whole every step.
"""
BYTES = 4


def layers(m):
    """(global layers, sliding layers) among those run."""
    n = int(m['num_hidden_layers'])
    sliding = sum(int(v) for v in m['sliding_window_layout'][:n])
    return n - sliding, sliding


def attention_params(m):
    """q and o d H dh each, k and v d KVH dh each."""
    d, dh = int(m['hidden_size']), int(m['head_dim'])
    return 2 * d * int(m['num_attention_heads']) * dh \
        + 2 * d * int(m['num_key_value_heads']) * dh


def router_params(m):
    return int(m['hidden_size']) * int(m['moe_num_primary_experts'])


def expert_params(m):
    """One expert: W1, W3 [d, F] and W2 [F, d]."""
    return 3 * int(m['hidden_size']) * int(m['moe_ffn_hidden_size'])


def layer_params(m):
    """A layer as held: attention, router, every expert, two norms."""
    return (attention_params(m) + router_params(m)
            + int(m['moe_num_primary_experts']) * expert_params(m)
            + 2 * int(m['hidden_size']))


def param_count(m):
    """All parameters held: the layers run, the embedding and the untied
    head over the whole vocabulary, the final norm."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    return int(m['num_hidden_layers']) * layer_params(m) + 2 * v * d + d


def weight_bytes(m):
    return BYTES * param_count(m)


def kv_bytes_per_token(m):
    """K and V of one token in ONE layer."""
    return BYTES * 2 * int(m['num_key_value_heads']) * int(m['head_dim'])


def window_rows(m, position):
    """Rows a sliding layer's attention reads for the row at `position`:
    the last sliding_window_size up to its own."""
    return min(int(position) + 1, int(m['sliding_window_size']))


def attention_bytes(m, rows):
    """Bytes ONE paged attention op has to read for `rows` K/V rows (K
    and V of each, once whatever the number of query heads)."""
    return rows * kv_bytes_per_token(m)


def expert_bytes(m, experts_touched):
    """Bytes ONE moe_experts op has to read: the three matrices of each
    expert that at least one of its rows chose."""
    return BYTES * experts_touched * expert_params(m)


def expert_flops(m, pairs):
    return 2 * pairs * expert_params(m)


def decode_step_bytes(m, full_rows, window_rows, experts_touched):
    """Bytes one decode step HAS to move: every weight outside the
    experts once (attention and router of every layer, norms, the head;
    the embedding's rows are a gather), the three matrices of the
    `experts_touched` experts a layer its lanes chose (a mean over the
    layers), and the K/V rows its attention reads: `full_rows` in each
    global layer, `window_rows` in each sliding one."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    n = int(m['num_hidden_layers'])
    outside = param_count(m) - v * d \
        - n * int(m['moe_num_primary_experts']) * expert_params(m)
    g, s = layers(m)
    return (BYTES * outside + n * expert_bytes(m, experts_touched)
            + (g * full_rows + s * window_rows) * kv_bytes_per_token(m))
