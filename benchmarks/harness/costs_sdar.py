"""Operations and bytes the SDAR block with routed experts
(paddle_tpu/models/sdar_moe.py) needs, from its shapes alone. `m` is a
configuration file's keys (HF sdar_moe names; `num_experts` counts the
experts HELD, `router_experts` the router's published width). Every
layer is an attention sublayer (q/k normed a head at a time, rotary,
grouped K/V heads) and an expert sublayer. Everything is float32 (4
bytes). Norm gains are counted with their layer; the embedding's rows
are a gather and are left out of a step's bytes; the untied head is read
whole every pass.
"""
BYTES = 4


def attention_params(m):
    """q and o d H dh each, k and v d KVH dh each, two gains [dh]."""
    d, dh = int(m['hidden_size']), int(m['head_dim'])
    return 2 * d * int(m['num_attention_heads']) * dh \
        + 2 * d * int(m['num_key_value_heads']) * dh + 2 * dh


def router_params(m):
    return int(m['hidden_size']) * int(m.get('router_experts',
                                             m['num_experts']))


def expert_params(m):
    """One expert: W1, W3 [d, F] and W2 [F, d]."""
    return 3 * int(m['hidden_size']) * int(m['moe_intermediate_size'])


def layer_params(m):
    """A layer as held: attention, router, the held experts, two norms."""
    return (attention_params(m) + router_params(m)
            + int(m['num_experts']) * expert_params(m)
            + 2 * int(m['hidden_size']))


def param_count(m):
    """All parameters held: the layers run, the embedding and the untied
    head over the vocabulary held, the final norm."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    return int(m['num_hidden_layers']) * layer_params(m) + 2 * v * d + d


def weight_bytes(m):
    return BYTES * param_count(m)


def kv_bytes_per_token(m):
    """K and V of one token in ONE layer."""
    return BYTES * 2 * int(m['num_key_value_heads']) * int(m['head_dim'])


def block_attention_bytes(m, rows):
    """Bytes ONE paged_block_attention op has to read for `rows` K/V
    rows (K and V of each live token of its lanes, the block's own rows
    among them: once a lane a pass, whatever the number of query heads
    and of block rows that share them)."""
    return rows * kv_bytes_per_token(m)


def expert_bytes(m, experts_touched):
    """Bytes ONE moe_experts op has to read: the three matrices of each
    held expert that at least one of its rows chose."""
    return BYTES * experts_touched * expert_params(m)


def expert_flops(m, pairs):
    return 2 * pairs * expert_params(m)


def block_step_bytes(m, live_rows, experts_touched):
    """Bytes one block step HAS to move: every weight outside the
    experts once (attention and router of every layer, norms, the head;
    the embedding's rows are a gather), the three matrices of the
    `experts_touched` held experts a layer that its rows chose (a mean
    over the layers), and K and V of the `live_rows` tokens its lanes
    hold (committed and the block's own), in every layer."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    n = int(m['num_hidden_layers'])
    outside = param_count(m) - v * d \
        - n * int(m['num_experts']) * expert_params(m)
    return (BYTES * outside + n * expert_bytes(m, experts_touched)
            + n * live_rows * kv_bytes_per_token(m))
