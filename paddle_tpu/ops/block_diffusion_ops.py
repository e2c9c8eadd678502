"""What a model that generates by diffusion over blocks (models/
sdar_moe.py) does between two passes over a block, on the device: op
`block_unmask`, behind the head of the block step program
(models/transformer.build_paged_block_program). The family's published
decoding routine at temperature 0: every row's candidate is the argmax
of its logits and its confidence that candidate's probability; a pass
hands the `transfer` most confident of the rows still masked their
candidates (or the first of them, or all over a threshold). With the
ids left on the device the serving loop can dispatch pass n + 1 before
pass n's ids have reached the host (serving/paged.py block_step). No
gradient.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..registry import register_op, op_emitter

RULES = ('low_confidence_static', 'sequential', 'low_confidence_dynamic')


def unmask(logits, ids, transfer, mask_id, rule, threshold=0.9):
    """logits [S, B, V], ids [S, B], transfer [S] -> (ids with up to
    transfer[s] of lane s's masked rows replaced by their candidates,
    the rows still masked behind that [S] int32). A row's candidate is
    the argmax of its logits over every id but the mask's (a row that
    took the mask id would stay masked for ever; ties to the lower id),
    its confidence the softmax's value there. rule:

      low_confidence_static   the n masked rows of largest confidence
                              (ties to the lower row)
      sequential              the first n masked rows
      low_confidence_dynamic  every masked row whose confidence is over
                              `threshold` if those are at least n, else
                              the n of largest confidence

    with n = min(transfer[s], the rows still masked)."""
    if rule not in RULES:
        raise ValueError('block_unmask rule %r is none of %s' % (rule, RULES))
    logits = logits.astype(jnp.float32)
    masked = ids == mask_id                                     # [S, B]
    cand = jnp.where(jnp.arange(logits.shape[-1]) == mask_id, -jnp.inf,
                     logits)
    x0 = jnp.argmax(cand, axis=-1)
    conf = jnp.exp(jnp.max(cand, axis=-1)
                   - jax.nn.logsumexp(logits, axis=-1))         # [S, B]
    n = jnp.minimum(transfer.astype(jnp.int32),
                    masked.sum(axis=-1).astype(jnp.int32))[:, None]
    rows = jnp.arange(ids.shape[1])
    if rule == 'sequential':
        score = jnp.broadcast_to(-rows.astype(jnp.float32), conf.shape)
    else:
        score = conf
    score = jnp.where(masked, score, -jnp.inf)
    # a row's rank among its lane's rows: how many come before it
    before = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (rows[None, None, :] < rows[None, :, None]))
    take = masked & (before.sum(axis=-1) < n)
    if rule == 'low_confidence_dynamic':
        over = masked & (conf > threshold)
        take = jnp.where(over.sum(axis=-1, keepdims=True) >= n, over, take)
        take &= n > 0
    out = jnp.where(take, x0.astype(ids.dtype), ids)
    return out, (out == mask_id).sum(axis=-1).astype(jnp.int32)


@op_emitter('block_unmask')
def _block_unmask_emit(ctx, op):
    """Logits [S, B, V], Ids [S, B] (the block's ids going into the
    pass: fixed and unmasked tokens, the mask id elsewhere), Transfer
    [S] int32, Live [S] int32 (a dead lane's ids pass through); attrs
    rule, threshold, mask_id -> Out [S, B] (Ids' dtype), Masked [S]
    int32 (rows still masked behind the pass). `unmask` above."""
    ids = ctx.get(op.single_input('Ids'))
    transfer = ctx.get(op.single_input('Transfer')).astype(jnp.int32)
    if op.input('Live'):
        transfer = jnp.where(
            ctx.get(op.single_input('Live')).astype(bool), transfer, 0)
    out, left = unmask(ctx.get(op.single_input('Logits')), ids, transfer,
                       int(op.attr('mask_id')), op.attr('rule'),
                       float(op.attr('threshold', 0.9)))
    ctx.set(op.single_output('Out'), out)
    ctx.set(op.single_output('Masked'), left)


def _block_unmask_infer(op, block):
    ids = block.var_recursive(op.single_input('Ids'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape, out.dtype = ids.shape, ids.dtype
    left = block.var_recursive(op.single_output('Masked'))
    left.shape, left.dtype = (ids.shape[0],), 'int32'


register_op('block_unmask', infer_shape=_block_unmask_infer, no_grad=True)
