"""Mixture-of-experts FFN ops (no reference analog -- the reference's
nearest precursor is the distributed lookup table, SURVEY.md §2.11; this
is the modern EP capability the framework adds).

Two dispatch formulations:

- ``topk`` (default): GShard/Switch-style token routing. Each token's
  top-k experts are selected, tokens claim slots in a per-expert
  capacity buffer in slot-major priority order, and overflow tokens are
  dropped (their combine weight is zero, so they pass through with zero
  expert contribution). Dispatch and combine are one-hot einsums over a
  static [S, E, C] lattice -- with the expert dimension sharded over the
  'ep' mesh axis GSPMD lowers the dispatch einsum to an all-to-all over
  ICI. Expert compute is E*C*D*H with E*C = k*S*capacity_factor:
  **independent of the expert count** at fixed k (the property that
  makes EP scale; asserted in tests/test_moe_dispatch.py).

- ``dense``: every token is combined with every expert via einsum and
  weighted by the (top-k masked) gate. Exact (no capacity dropping) but
  compute grows linearly in E -- the small-E fallback and the numeric
  reference for the topk parity test.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..registry import register_op, op_emitter, register_vjp_grad

_ACT = {'gelu': jax.nn.gelu, 'relu': jax.nn.relu, 'tanh': jnp.tanh,
        'sigmoid': jax.nn.sigmoid, '': lambda v: v, None: lambda v: v}


def _topk_route(gate, k):
    """Top-k mask, renormalized; gradient flows through the gate probs."""
    E = gate.shape[-1]
    if k >= E:
        return gate
    thresh = jnp.sort(gate, axis=-1)[..., E - k][..., None]
    mask = (gate >= thresh).astype(gate.dtype)
    route = gate * mask
    return route / jnp.maximum(
        jnp.sum(route, axis=-1, keepdims=True), 1e-9)


def _dispatch_combine(route, k, capacity):
    """Build the [S, E, C] dispatch (0/1) and combine (weighted) tensors
    from renormalized routing probs [S, E].

    Slot-major priority: all tokens' first choices claim capacity before
    any second choice does (the GShard ordering), so overflow drops a
    token's weakest expert first.
    """
    S, E = route.shape
    top_w, top_i = jax.lax.top_k(route, k)            # [S, k]
    # slot-major flattening: choice order = (k-slot, token)
    flat_e = top_i.T.reshape(-1)                      # [k*S] int
    flat_w = top_w.T.reshape(-1)                      # [k*S]
    e_oh = jax.nn.one_hot(flat_e, E, dtype=route.dtype)      # [kS, E]
    # position within the expert = how many earlier choices picked it.
    # int32 cumsum regardless of route.dtype: in bf16 (AMP) counts above
    # ~256 round, making tokens collide onto one capacity slot
    e_cnt = e_oh.astype(jnp.int32)
    pos = jnp.sum((jnp.cumsum(e_cnt, axis=0) - e_cnt) * e_cnt, axis=-1)
    keep = (pos < capacity).astype(route.dtype)       # [kS]
    c_oh = jax.nn.one_hot(pos, capacity, dtype=route.dtype) \
        * keep[:, None]                               # [kS, C]
    choice = e_oh[:, :, None] * c_oh[:, None, :]      # [kS, E, C] 0/1
    dispatch = choice.reshape(k, S, E, capacity).sum(0)
    combine = (choice * flat_w[:, None, None]) \
        .reshape(k, S, E, capacity).sum(0)
    return dispatch, combine


@op_emitter('moe_ffn')
def _moe_ffn_emit(ctx, op):
    x = ctx.get(op.single_input('X'))          # [..., D]
    gate = ctx.get(op.single_input('Gate'))    # [..., E] probabilities
    w_up = ctx.get(op.single_input('WUp'))     # [E, D, H]
    w_down = ctx.get(op.single_input('WDown'))  # [E, H, D]
    act = _ACT[op.attr('act', 'gelu')]
    k = op.attr('k', 1)
    mode = op.attr('dispatch', 'topk')
    E = gate.shape[-1]
    route = _topk_route(gate, k)

    if mode == 'dense':
        h = jnp.einsum('...d,edh->...eh', x, w_up)
        h = act(h)
        y = jnp.einsum('...eh,ehd->...ed', h, w_down)
        out = jnp.einsum('...ed,...e->...d', y, route)
    else:
        D = x.shape[-1]
        lead = x.shape[:-1]
        S = int(math.prod(lead))
        cf = float(op.attr('capacity_factor', 2.0))
        C = max(1, int(math.ceil(S * min(k, E) * cf / E)))
        xf = x.reshape(S, D)
        dispatch, combine = _dispatch_combine(route.reshape(S, E),
                                              min(k, E), C)
        # expert inputs [E, C, D]: with w_up/w_down sharded over 'ep'
        # this einsum IS the all-to-all
        ein = jnp.einsum('sec,sd->ecd', dispatch, xf)
        h = act(jnp.einsum('ecd,edh->ech', ein, w_up))
        y = jnp.einsum('ech,ehd->ecd', h, w_down)
        out = jnp.einsum('sec,ecd->sd', combine, y).reshape(x.shape)
    ctx.set(op.single_output('Out'), out)


def _moe_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = x.shape
    out.dtype = x.dtype
    out.lod_level = x.lod_level


register_op('moe_ffn', infer_shape=_moe_infer)
register_vjp_grad('moe_ffn', in_slots=('X', 'Gate', 'WUp', 'WDown'))


@op_emitter('moe_aux_loss')
def _moe_aux_loss_emit(ctx, op):
    """Load-balance auxiliary loss (Shazeer/GShard): E * sum_e(f_e * P_e)
    where f_e = fraction of tokens whose TOP choice is expert e (hard,
    non-differentiable) and P_e = mean gate probability (the gradient
    path). Minimized (=1) at a uniform expert distribution."""
    gate = ctx.get(op.single_input('Gate'))    # [..., E]
    E = gate.shape[-1]
    flat = gate.reshape(-1, E)
    top1 = jax.nn.one_hot(jnp.argmax(flat, axis=-1), E, dtype=gate.dtype)
    f = jnp.mean(top1, axis=0)
    p = jnp.mean(flat, axis=0)
    ctx.set(op.single_output('Out'), E * jnp.sum(f * p))


def _aux_infer(op, block):
    out = block.var_recursive(op.single_output('Out'))
    out.shape = []
    out.dtype = block.var_recursive(op.single_input('Gate')).dtype
    out.lod_level = 0


register_op('moe_aux_loss', infer_shape=_aux_infer)
register_vjp_grad('moe_aux_loss', in_slots=('Gate',))


# -- the served expert layer ---------------------------------------------------
#
# What a serving chip holds of an expert layer under expert parallelism:
# `experts_held` consecutive experts of the layer's E, from
# `expert_offset` on. The router is whole: every token is scored over
# all E experts and takes its top k of them with no capacity, so no
# pair of (token, expert) is ever dropped; this chip computes the part
# of the sum that its own experts give, and nothing stands in for the
# other chips or for the exchange with them.
#
#   s = sigmoid(W_r x);  the top_k largest of s + b (among all experts,
#   or, group-limited, among those of the topk_group best of n_group
#   groups, a group scoring the sum of its two largest);
#   w = scale * s_sel / sum(s_sel);  r = sum_{e held} w_e W2_e relu(W1_e l)^2
#   or, for experts of three matrices,  sum_{e held} w_e W2_e (silu(W1_e l) * W3_e l)
#
# A second scoring (attr gate 'softmax': the GraniteMoe router) ranks by
# the logits themselves and weighs by a softmax over the chosen ones:
#
#   l = W_r x;  the top_k largest of l;  w = scale * softmax(l_sel)
#
# with l the row the experts work on: the token's latent row (the
# projection down to it and back up are the block's own matmuls,
# outside this op), or x itself. The router's product
# runs at precision "highest", as the published gate computes in
# float32: a rounded score changes WHICH experts a token takes, not a
# digit of the result. (A chosen expert's weight is never exactly 0: a
# sigmoid is not, short of logits under -100.)
#
# What is read of the held stack follows the rows (`_held_part`): a
# prefill chunk's 256 rows touch every held expert, and every row goes
# through every held expert in one batched product that reads the stack
# once whatever was chosen (`held_experts`); a step's rows (the slots, or
# slots x a block's rows: at most pallas/moe_experts.STEP_ROWS) choose a
# quarter to nine tenths of them, and on a TPU a kernel reads the chosen
# experts' tiles straight out of the stack and no other
# (pallas/moe_experts.py). The sum is the same: a skipped expert's term
# was 0 times a finite number.

def _within_kept_groups(b, n_group, topk_group):
    """b [R, E] with the scores outside each row's `topk_group` best
    groups set to -inf: the E experts are `n_group` consecutive groups,
    a group scores the sum of its two largest entries, and the best
    groups are found by rank as the experts are (an equal score of a
    lower index counts as higher)."""
    g = b.reshape(b.shape[0], n_group, -1)
    first = jnp.argmax(g, axis=-1)
    second = jnp.max(jnp.where(
        jnp.arange(g.shape[-1]) == first[..., None], -jnp.inf, g), axis=-1)
    score = jnp.max(g, axis=-1) + second                        # [R, G]
    mine, other = score[:, :, None], score[:, None, :]
    i = jnp.arange(n_group)
    ahead = (other > mine) | ((other == mine) & (i[None, :] < i[:, None]))
    kept = jnp.sum(ahead, axis=-1) < topk_group                 # [R, G]
    return jnp.where(kept[..., None], g, -jnp.inf).reshape(b.shape)


def served_weights(x, router_w, bias, top_k, scale, n_group=1,
                   topk_group=1, gate='sigmoid'):
    """x [R, D], router_w [D, E], bias [E] -> w [R, E] float32: a row's
    weight for each expert, 0 for those it did not choose. With
    n_group > 1 the choice is group-limited (the DeepSeek-V3 gate): a
    row chooses among the experts of its topk_group best groups only.
    gate 'softmax' ranks by the logits (plus the bias) and weighs by a
    softmax over the logits of the chosen (a chosen expert's weight is
    not 0 short of logits 87 apart).

    The k largest of s + b are found by rank, not by a sort: an expert
    is chosen when fewer than k others score higher (an equal score of
    a lower index counts as higher, lax.top_k's order), one fused
    compare-and-count over [R, E, E] that the chip runs in tens of
    microseconds where its top_k takes 0.4 ms for 64 rows of 512."""
    s = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    if gate == 'sigmoid':
        s = jax.nn.sigmoid(s)
    elif gate != 'softmax':
        raise ValueError('gate %r is not sigmoid or softmax' % (gate,))
    b = s if bias is None else s + bias.astype(jnp.float32)
    if n_group > 1:
        b = _within_kept_groups(b, n_group, topk_group)
    mine, other = b[:, :, None], b[:, None, :]
    e = jnp.arange(b.shape[1])
    ahead = (other > mine) | ((other == mine) & (e[None, :] < e[:, None]))
    chosen = jnp.sum(ahead, axis=-1) < top_k
    if gate == 'softmax':
        top = jnp.max(jnp.where(chosen, s, -jnp.inf), -1, keepdims=True)
        sel = jnp.where(chosen, jnp.exp(s - top), 0.0)
    else:
        sel = jnp.where(chosen, s, 0.0)
    return scale * sel / jnp.sum(sel, -1, keepdims=True)


def _relu2(v):
    return jnp.square(jax.nn.relu(v))


def held_experts(lat, w, w1, w2):
    """lat [R, L], w [R, held], W1 [held, L, F], W2 [held, F, L] ->
    sum_e w[:, e] W2_e relu(W1_e lat)^2, [R, L]. Every row goes through
    every held expert in one batched product, a row's result weighted
    by w (zero where it did not choose the expert): no pair can be
    dropped, the weights are read once whatever the router chose, and
    the products hide under that read: on a v5e 64 experts of 1024 x
    2688 take 1.92 ms at 90 % of the HBM peak for 64 rows and for a
    prefill chunk's 256 alike. That is what a chunk's rows take (they
    touch every held expert) and every row off a TPU; a decode step's
    rows on a TPU read the chosen experts only, through the kernel of
    pallas/moe_experts.py, to which this is the reference. (Pairs
    sorted by expert and multiplied by groups, rows x 22 / 8 products
    in place of rows x 64, took 5.0 ms there: each group's weights were
    copied out of the stack before they were multiplied. PERF.md
    section 6, PR 36; the kernel copies nothing and sorts nothing:
    section 6, PR 54.)"""
    h = _relu2(jnp.einsum('rl,elf->erf', lat, w1))
    return jnp.einsum('erf,efl->rl', h * w.T.astype(lat.dtype)[..., None],
                      w2)


_GATE_ACT = {'silu': jax.nn.silu, 'relu': jax.nn.relu}


def held_gated_experts(lat, w, w1, w3, w2, act='silu'):
    """As held_experts for experts of three matrices: sum_e w[:, e]
    W2_e (act(W1_e lat) * W3_e lat), W1 and W3 [held, L, F], W2
    [held, F, L]; `act` silu (SwiGLU) or relu (ReGLU)."""
    h = _GATE_ACT[act](jnp.einsum('rl,elf->erf', lat, w1)) \
        * jnp.einsum('rl,elf->erf', lat, w3)
    return jnp.einsum('erf,efl->rl', h * w.T.astype(lat.dtype)[..., None],
                      w2)


@op_emitter('moe_experts')
def _moe_experts_emit(ctx, op):
    """The held experts' part of a served expert layer. X [.., D] (what
    the router scores), Lat [.., L] (what the experts work on), RouterW
    [D, E], Bias [E] (none: no selection bias), W1 [held, L, F], W2
    [held, F, L]; attrs top_k,
    scale, expert_offset, n_group and topk_group where the choice
    is group-limited (1 and 1: over all experts), and gate ('sigmoid'
    where not given, or 'softmax' over the chosen logits) -> Out [.., L]. The
    experts' form follows from the weights handed in: with W3 [held, L,
    F] beside W1 an expert is W2 (act(W1 l) * W3 l), attr act 'silu'
    where not given or 'relu', without it W2 relu(W1 l)^2. Rows may be marked dead, by Live [rows] (a decode step's
    lanes; [lanes] for a block step's [lanes, B] rows: a lane's flag
    covers its rows) or Len [1] (a chunk's rows from Len on): they choose nothing
    and count nothing. Stats [4] int32, where asked for, is this call's
    (pairs on held experts, held experts with at least one pair, pairs
    selected here and not computed, 1): the third is 0, there being no
    capacity to overflow. What the sum reads of the stack follows the
    op's static row count and the backend (`_held_part`)."""
    x = ctx.get(op.single_input('X'))
    lat = ctx.get(op.single_input('Lat'))
    w1 = ctx.get(op.single_input('W1'))
    w2 = ctx.get(op.single_input('W2'))
    lead, width = lat.shape[:-1], lat.shape[-1]
    rows = int(math.prod(lead))
    offset = int(op.attr('expert_offset', 0))
    w = served_weights(
        x.reshape(rows, x.shape[-1]), ctx.get(op.single_input('RouterW')),
        ctx.get(op.single_input('Bias')) if op.input('Bias') else None,
        int(op.attr('top_k')),
        float(op.attr('scale', 1.0)), int(op.attr('n_group', 1)),
        int(op.attr('topk_group', 1)),
        op.attr('gate', 'sigmoid'))[:, offset:offset + w1.shape[0]]
    if op.input('Live'):
        live = ctx.get(op.single_input('Live')).astype(bool).reshape(-1)
        if live.shape[0] != rows:
            # a block step: a lane's flag covers all its rows
            live = jnp.repeat(live, rows // live.shape[0])
        w = jnp.where(live[:, None], w, 0.0)
    elif op.input('Len'):
        n = ctx.get(op.single_input('Len')).astype(jnp.int32).reshape(())
        w = jnp.where((jnp.arange(rows) < n)[:, None], w, 0.0)
    out = _held_part(lat.reshape(rows, width), w, w1,
                     ctx.get(op.single_input('W3')) if op.input('W3')
                     else None, w2, op.attr('act', 'silu'))
    ctx.set(op.single_output('Out'), out.reshape(lat.shape))
    if op.output('Stats'):
        ctx.set(op.single_output('Stats'), jnp.stack(
            [jnp.sum(w != 0), jnp.sum(jnp.any(w != 0, axis=0)),
             0, 1]).astype(jnp.int32))


def _held_part(lat, w, w1, w3, w2, act):
    """The held experts' sum for lat [R, L] and w [R, held]. On a TPU
    (or under FLAGS_pallas_interpret) a step's rows (a decode step's
    slots or a block step's slots x B, up to `STEP_ROWS` of
    pallas/moe_experts.py where the walk's blocks fit the kernel's
    memory: by the op's static shapes alone) take the kernel there,
    which reads only the experts a row chose, any(w != 0) by column: the
    expression Stats counts, so the op's experts_touched is the number
    of experts read. A chunk's rows, and every row elsewhere, take the
    batched product over the whole stack. Which of the two an emission
    took is counted in ops.moe_experts.kernel /
    ops.moe_experts.fallback."""
    from ..pallas import moe_experts as _me
    from ..flags import get_flag
    from ..obs import telemetry
    on_tpu = jax.default_backend() == 'tpu'
    if _me.step_supported(lat.shape[0], lat.shape[1], w1.shape[2],
                          w1.shape[0], 2 if w3 is None else 3,
                          w1.dtype.itemsize) and (
            on_tpu or bool(get_flag('pallas_interpret'))):
        telemetry.counter('ops.moe_experts.kernel').inc()
        ids, n = _me.touched_ids(jnp.any(w != 0, axis=0))
        return _me.moe_experts(lat, w, ids, n, w1, w3, w2,
                               act='relu2' if w3 is None else act,
                               interpret=not on_tpu)
    telemetry.counter('ops.moe_experts.fallback').inc()
    if w3 is None:
        return held_experts(lat, w, w1, w2)
    return held_gated_experts(lat, w, w1, w3, w2, act)


def _moe_experts_infer(op, block):
    lat = block.var_recursive(op.single_input('Lat'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape, out.dtype = lat.shape, lat.dtype
    if op.output('Stats'):
        st = block.var_recursive(op.single_output('Stats'))
        st.shape, st.dtype = (4,), 'int32'


def _moe_experts_no_backward(op, block):
    raise NotImplementedError(
        'op moe_experts has no backward: the served expert layer is built '
        'for serving only (train with moe_ffn)')


register_op('moe_experts', infer_shape=_moe_experts_infer,
            grad=_moe_experts_no_backward)
