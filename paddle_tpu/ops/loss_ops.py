"""Loss and sampled-objective ops completing the reference's loss
inventory (operators/{hinge_loss,log_loss,margin_rank_loss,
squared_l2_distance,maxout,sampling_id,nce,hierarchical_sigmoid}_op.*).

The two sampled objectives are the interesting redesigns:

- nce: the reference's CPU kernel draws negatives per row with a custom
  sampler object; here sampling uses the executor's per-step PRNG key
  (ctx.rng) and the whole loss — gather of class rows, logit
  correction, binary logistic over true + sampled classes — is one
  static-shape XLA program (gathers batch well on TPU).
- hierarchical_sigmoid: the reference walks a MatrixBitCode over a
  complete binary heap; here the heap path (ancestors of leaf
  label+num_classes) is computed with static shift counts, so the
  whole path of length ceil(log2(C))+1 is a fixed-size gather + masked
  binary-logistic sum. Σ_label P(label|x) == 1 exactly (asserted in
  tests), because every internal heap node has two children.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..obs import telemetry as _tm
from ..registry import (register_op, op_emitter, register_vjp_grad,
                        same_shape_infer, amp_cast)


# ---------------------------------------------------------------------------
# element-wise losses
# ---------------------------------------------------------------------------

@op_emitter('hinge_loss')
def _hinge_loss_emit(ctx, op):
    logits = ctx.get(op.single_input('Logits'))
    labels = ctx.get(op.single_input('Labels'))   # {0, 1}
    sign = 2.0 * labels.astype(logits.dtype) - 1.0
    ctx.set(op.single_output('Loss'),
            jnp.maximum(1.0 - sign * logits, 0.0))


register_op('hinge_loss',
            infer_shape=same_shape_infer('Logits', 'Loss'))
register_vjp_grad('hinge_loss', in_slots=('Logits',),
                  out_slots=('Loss',), nondiff_slots=('Labels',))


@op_emitter('log_loss')
def _log_loss_emit(ctx, op):
    p = ctx.get(op.single_input('Predicted'))
    y = ctx.get(op.single_input('Labels'))
    eps = op.attr('epsilon', 1e-4)
    loss = -y * jnp.log(p + eps) - (1.0 - y) * jnp.log(1.0 - p + eps)
    ctx.set(op.single_output('Loss'), loss)


register_op('log_loss',
            infer_shape=same_shape_infer('Predicted', 'Loss'))
register_vjp_grad('log_loss', in_slots=('Predicted',),
                  out_slots=('Loss',), nondiff_slots=('Labels',))


@op_emitter('margin_rank_loss')
def _margin_rank_loss_emit(ctx, op):
    x1 = ctx.get(op.single_input('X1'))
    x2 = ctx.get(op.single_input('X2'))
    label = ctx.get(op.single_input('Label'))     # +1: x1 ranks higher
    margin = op.attr('margin', 0.0)
    out = jnp.maximum(-label * (x1 - x2) + margin, 0.0)
    ctx.set(op.single_output('Out'), out)
    if op.output('Activated'):
        ctx.set(op.single_output('Activated'),
                (out > 0).astype(x1.dtype))


register_op('margin_rank_loss',
            infer_shape=same_shape_infer('X1', 'Out'))
register_vjp_grad('margin_rank_loss', in_slots=('X1', 'X2'),
                  out_slots=('Out',), nondiff_slots=('Label',))


@op_emitter('squared_l2_distance')
def _squared_l2_distance_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    y = ctx.get(op.single_input('Y'))
    sub = x - y                                   # y may broadcast [1,D]
    sub = jnp.broadcast_to(sub, x.shape)
    ctx.set(op.single_output('sub_result'), sub)
    ctx.set(op.single_output('Out'),
            jnp.sum(sub * sub, axis=tuple(range(1, sub.ndim)),
                    keepdims=True))


def _sql2_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    sub = block.var_recursive(op.single_output('sub_result'))
    sub.shape = x.shape
    sub.dtype = x.dtype
    out = block.var_recursive(op.single_output('Out'))
    out.shape = [x.shape[0], 1]
    out.dtype = x.dtype


register_op('squared_l2_distance', infer_shape=_sql2_infer)
register_vjp_grad('squared_l2_distance', in_slots=('X', 'Y'),
                  out_slots=('Out',))


# ---------------------------------------------------------------------------
# fused_softmax_cross_entropy — the LM-head loss without the logits
# tensor (TPU redesign of the reference's fc + softmax_with_cross_entropy
# pair, softmax_with_cross_entropy_op.cc). At vocab 32k+ the pair
# materializes [B*T, V] fp32 logits in BOTH passes; here the head matmul
# and the loss are one op, computed as a lax.scan over token chunks with
# a jax.checkpoint'd body: each chunk's [chunk, V] logits live only in
# VMEM-scale scratch, and the backward recomputes them per chunk (the
# scan transpose accumulates dW across chunks).
#
# Under a mesh whose 'dp' axis splits X's leading dimension the scan
# runs per dp shard (shard_map, 'dp' alone manual; tp/sp/... stay with
# GSPMD): each device flattens, pads and scans the rows it owns, W and
# Bias enter replicated, and shard_map's transpose sums their
# cotangents over 'dp', once, after each shard's own accumulation. W
# is cast for the MXU before it enters, so that sum runs in the dtype
# the scan accumulates dW in (bf16 under AMP, as GSPMD sums every
# other weight's gradient there; float32 without AMP).
# Left to GSPMD the scan walks the sharded axis: a step needs its chunk
# whole and the chunk lives on one device, so the partitioner gathers X
# and every device runs every chunk and computes the whole dW itself,
# dp times its share (gpt1b3_train_dp4: 198 ms a step against 44 ms).
# No mesh, a mesh of one, no 'dp' axis or a leading dimension dp does
# not divide take the global scan, unchanged.
#
# inputs:  X [B, T, D] (or [N, D]) features, W [D, V], optional Bias [V],
#          Label [..., 1] int
# outputs: Loss [..., 1] f32
# attrs:   chunk (tokens per scan step of a dp shard, default 1024),
#          ignore_index
# ---------------------------------------------------------------------------

# Which lowering an emission took, bumped once per trace (the forward
# and the grad's re-trace each count): a dp program whose batch the
# mesh does not divide shows up in `global`, not as a slow head.
_ROUTE_PER_SHARD = _tm.counter('ops.fused_head.per_shard')
_ROUTE_GLOBAL = _tm.counter('ops.fused_head.global')


def _split_over_dp(mesh, x):
    """Whether the mesh has a 'dp' axis of more than one device that
    divides X's leading dimension (shard_map cannot pad as GSPMD can)."""
    dp = mesh.shape.get('dp', 1) if mesh is not None else 1
    return dp > 1 and x.shape[0] % dp == 0


@op_emitter('fused_softmax_cross_entropy')
def _fused_swce_emit(ctx, op):
    from jax import lax
    x = ctx.get(op.single_input('X'))
    w = ctx.get(op.single_input('W'))
    bias = ctx.get(op.single_input('Bias')) if op.input('Bias') else None
    label = ctx.get(op.single_input('Label'))
    chunk_attr = int(op.attr('chunk', 1024))
    ignore = op.attr('ignore_index', -100)

    def rows_loss(x, label, w, bias=None):
        """Loss [..., 1] of the rows handed in: all of them, or the
        rows of one dp shard."""
        lead_shape = x.shape[:-1]
        D = x.shape[-1]
        N = 1
        for s in lead_shape:
            N *= s
        x2 = x.reshape(N, D)
        lbl = label.reshape(N).astype(jnp.int32)

        chunk = min(chunk_attr, N)
        pad = (-N) % chunk
        if pad:
            x2 = jnp.concatenate(
                [x2, jnp.zeros((pad, D), x2.dtype)], axis=0)
            # padded rows pick class 0 of a zero feature row — finite,
            # and sliced off below
            lbl = jnp.concatenate([lbl, jnp.zeros((pad,), lbl.dtype)])
        n_chunks = (N + pad) // chunk

        x2c, wc = amp_cast(ctx, x2, w)

        def chunk_loss(x_c, l_c):
            logits = lax.dot_general(
                x_c, wc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)    # [chunk, V] f32
            if bias is not None:
                logits = logits + bias.astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(
                logits, l_c[:, None], axis=-1)[:, 0]
            loss = lse - picked
            return jnp.where(l_c == ignore, 0.0, loss)

        body = jax.checkpoint(chunk_loss)

        def scan_step(_, xs):
            return None, body(*xs)

        _, losses = lax.scan(
            scan_step, None,
            (x2c.reshape(n_chunks, chunk, D),
             lbl.reshape(n_chunks, chunk)))
        loss_flat = losses.reshape(-1)[:N]
        return loss_flat.reshape(lead_shape + (1,))

    operands = [x, label, w] + ([] if bias is None else [bias])
    mesh = getattr(ctx, 'mesh', None)
    if _split_over_dp(mesh, x):
        from jax.sharding import PartitionSpec as P
        _ROUTE_PER_SHARD.inc()
        operands[2] = amp_cast(ctx, w)
        # check_vma=False: the cotangents of W and Bias are summed where
        # they leave the shard_map; with the check on the sum is placed
        # where the scan body first mixes W with a shard's rows, inside
        # the backward loop
        rows_loss = jax.shard_map(
            rows_loss, mesh=mesh,
            in_specs=(P('dp'), P('dp')) + (P(),) * (len(operands) - 2),
            out_specs=P('dp'), axis_names=frozenset({'dp'}),
            check_vma=False)
    else:
        _ROUTE_GLOBAL.inc()
    ctx.set(op.single_output('Loss'), rows_loss(*operands))


def _fused_swce_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    loss = block.var_recursive(op.single_output('Loss'))
    loss.shape = tuple(x.shape[:-1]) + (1,)
    loss.dtype = 'float32'


register_op('fused_softmax_cross_entropy', infer_shape=_fused_swce_infer)
register_vjp_grad('fused_softmax_cross_entropy',
                  in_slots=('X', 'W', 'Bias'), out_slots=('Loss',),
                  nondiff_slots=('Label',))


# ---------------------------------------------------------------------------
# maxout (reference maxout_op.cc): NCHW, channel groups reduced by max
# ---------------------------------------------------------------------------

@op_emitter('maxout')
def _maxout_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    groups = op.attr('groups')
    n, c, h, w = x.shape
    out = x.reshape(n, c // groups, groups, h, w).max(axis=2)
    ctx.set(op.single_output('Out'), out)


def _maxout_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    groups = op.attr('groups')
    out = block.var_recursive(op.single_output('Out'))
    out.shape = [x.shape[0], x.shape[1] // groups, x.shape[2],
                 x.shape[3]]
    out.dtype = x.dtype


register_op('maxout', infer_shape=_maxout_infer)
register_vjp_grad('maxout', in_slots=('X',))


# ---------------------------------------------------------------------------
# sampling_id (reference sampling_id_op.cc): categorical draw per row
# ---------------------------------------------------------------------------

@op_emitter('sampling_id', stateful=True)
def _sampling_id_emit(ctx, op):
    x = ctx.get(op.single_input('X'))             # [B, C] probabilities
    key = ctx.rng(op)
    ids = jax.random.categorical(key, jnp.log(jnp.maximum(x, 1e-30)),
                                 axis=-1)
    ctx.set(op.single_output('Out'), ids.astype(jnp.int64))


def _sampling_id_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = [x.shape[0]]
    out.dtype = 'int64'


register_op('sampling_id', infer_shape=_sampling_id_infer, no_grad=True)


# ---------------------------------------------------------------------------
# nce (reference nce_op.h): noise-contrastive estimation, uniform noise
# ---------------------------------------------------------------------------

@op_emitter('nce', stateful=True)
def _nce_emit(ctx, op):
    x = ctx.get(op.single_input('Input'))         # [B, D]
    label = ctx.get(op.single_input('Label'))     # [B] or [B, 1]
    w = ctx.get(op.single_input('Weight'))        # [C, D]
    bias = ctx.get(op.single_input('Bias')) if op.input('Bias') else None
    num_neg = op.attr('num_neg_samples', 10)
    num_classes = op.attr('num_total_classes')
    label = label.reshape(label.shape[0])
    B = x.shape[0]

    # key from the segment key + a per-op tag attr, NOT ctx.rng(op):
    # the vjp grad re-traces this emitter under the GRAD op's index, and
    # folding that in would make the backward sample different negatives
    # than the cost it differentiates (the dropout/Mask problem, solved
    # here by a stable tag instead of a saved output)
    key = jax.random.fold_in(ctx.rng_key, op.attr('rng_tag', 0))
    negs = jax.random.randint(key, (B, num_neg), 0, num_classes)

    def logit(classes):
        rows = w[classes]                          # gather [.., D]
        s = jnp.einsum('bd,b...d->b...', x, rows)
        if bias is not None:
            s = s + bias[classes]
        return s

    # uniform noise: q = 1/C, correction log(num_neg * q)
    log_nq = jnp.log(jnp.asarray(num_neg / num_classes, x.dtype))
    s_pos = logit(label) - log_nq                 # [B]
    s_neg = logit(negs) - log_nq                  # [B, S]
    # binary logistic: true class target 1, sampled classes target 0
    cost = jax.nn.softplus(-s_pos) + \
        jnp.sum(jax.nn.softplus(s_neg), axis=1)
    if op.input('SampleWeight'):
        sw = ctx.get(op.single_input('SampleWeight')).reshape(-1)
        cost = cost * sw.astype(cost.dtype)
    ctx.set(op.single_output('Cost'), cost[:, None])


def _nce_infer(op, block):
    x = block.var_recursive(op.single_input('Input'))
    out = block.var_recursive(op.single_output('Cost'))
    out.shape = [x.shape[0], 1]
    out.dtype = x.dtype


register_op('nce', infer_shape=_nce_infer)
register_vjp_grad('nce', in_slots=('Input', 'Weight', 'Bias'),
                  out_slots=('Cost',),
                  nondiff_slots=('Label', 'SampleWeight'))


# ---------------------------------------------------------------------------
# hierarchical_sigmoid (reference hierarchical_sigmoid_op.cc +
# operators/math/matrix_bit_code.*): complete-binary-heap code tree
# ---------------------------------------------------------------------------

def _heap_path(label, num_classes, depth):
    """Ancestor internal-node ids and branch bits for leaf
    `label + num_classes` in the complete binary heap. Returns
    (nodes [.., depth] int32 0-based internal ids, bits, valid)."""
    code = label + num_classes                     # heap leaf index
    ks = jnp.arange(1, depth + 1)                  # shift counts
    anc = code[..., None] >> ks                    # ancestors, root=1
    bits = (code[..., None] >> (ks - 1)) & 1       # child side taken
    # ancestors of leaves in [C, 2C) at shift>=1 are always < C, so the
    # only invalid entries are the shifted-past-the-root zeros
    valid = anc >= 1
    nodes = jnp.clip(anc - 1, 0, num_classes - 2)
    return nodes, bits, valid


@op_emitter('hierarchical_sigmoid')
def _hsigmoid_emit(ctx, op):
    x = ctx.get(op.single_input('X'))             # [B, D]
    label = ctx.get(op.single_input('Label'))     # [B] / [B,1]
    w = ctx.get(op.single_input('W'))             # [C-1, D]
    bias = ctx.get(op.single_input('Bias')) if op.input('Bias') else None
    num_classes = op.attr('num_classes')
    label = label.reshape(label.shape[0]).astype(jnp.int32)
    depth = max(1, int(math.ceil(math.log2(num_classes))) + 1)

    nodes, bits, valid = _heap_path(label, num_classes, depth)
    rows = w[nodes]                                # [B, depth, D]
    s = jnp.einsum('bd,bkd->bk', x, rows)
    if bias is not None:
        s = s + bias.reshape(-1)[nodes]
    # binary logistic per node with target = bit
    t = bits.astype(s.dtype)
    losses = jax.nn.softplus(s) - t * s
    cost = jnp.sum(jnp.where(valid, losses, 0.0), axis=1)
    ctx.set(op.single_output('Out'), cost[:, None])


def _hsigmoid_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = [x.shape[0], 1]
    out.dtype = x.dtype


register_op('hierarchical_sigmoid', infer_shape=_hsigmoid_infer)
register_vjp_grad('hierarchical_sigmoid',
                  in_slots=('X', 'W', 'Bias'), out_slots=('Out',),
                  nondiff_slots=('Label',))
