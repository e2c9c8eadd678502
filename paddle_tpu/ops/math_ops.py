"""Math / elementwise / activation / reduction ops.

TPU-native re-design of reference paddle/fluid/operators/{activation_op.cc,
elementwise_*_op.cc, mul_op.cc, matmul_op.cc, reduce_*_op.cc, sum_op.cc,
scale_op.cc, clip_op.cc, top_k_op.cc, compare_op.cc, logical_op.cc}.

Every op is a pure JAX emitter; gradients come from jax.vjp over the forward
emitter (registry.register_vjp_grad) instead of hand-written CUDA grad kernels
-- XLA derives the transpose and fuses it with neighbours.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import (register_op, op_emitter, same_shape_infer,
                        register_vjp_grad, amp_cast)

# ---------------------------------------------------------------------------
# elementwise binary family with Paddle's `axis` broadcast contract
# (reference elementwise_op_function.h): Y's shape must match a contiguous
# window of X's shape starting at `axis`; axis==-1 aligns trailing dims.
# ---------------------------------------------------------------------------


def _declared_rank(ctx, op, slot):
    """Rank recorded by shape inference for an input var, or None."""
    try:
        v = ctx.var(op.single_input(slot))
    except (KeyError, AttributeError):
        return None
    return len(v.shape) if v.shape is not None else None


def _broadcast_y(x, y, axis, x_declared_rank=None):
    if x.ndim == y.ndim:
        return y
    if axis != -1:
        # padded-sequence runtime inserts the time axis at position 1
        # (runtime rank = declared rank + 1), shifting alignment targets
        # at positions >= 1 right by one. Decided from DECLARED rank, not
        # runtime-shape guessing (a T that equals a bias dim must not
        # change semantics).
        if x_declared_rank is not None and x.ndim == x_declared_rank + 1 \
                and axis >= 1:
            axis += 1
        new_shape = [1] * axis + list(y.shape) + \
            [1] * (x.ndim - axis - y.ndim)
        if len(new_shape) == x.ndim:
            return y.reshape(new_shape)
    axis = x.ndim - y.ndim
    return y.reshape([1] * axis + list(y.shape))


def _register_elementwise(name, fn):
    op_type = 'elementwise_' + name

    def emit(ctx, op):
        from ..selected_rows import SelectedRows
        x = ctx.get(op.single_input('X'))
        y = ctx.get(op.single_input('Y'))
        axis = op.attr('axis', -1)
        if isinstance(y, SelectedRows):
            y = y.to_dense()
        if isinstance(x, SelectedRows):
            # mul/div by a scalar are linear per-row, so the sparse format
            # survives (the grad-clip scale path); anything else needs the
            # merged dense view (reference elementwise ops merge first).
            if name in ('mul', 'div') and jnp.ndim(y) == 0:
                ctx.set(op.single_output('Out'),
                        SelectedRows(fn(x.values, y), x.rows, x.height))
                return
            x = x.to_dense()
        # AMP: a bf16 activation +/* an fp32 PARAM (bias add, LN-style
        # scale) must not promote the stream back to fp32 — that leak
        # turns every downstream activation AND its gradient fp32
        # (measured: the whole transformer residual path reverted to
        # fp32 through fc bias adds). Cast the param side down instead.
        # Gated on persistable so an fp32-by-design tensor (a loss, a
        # user accumulator) meeting a bf16 one keeps fp32 promotion.
        if getattr(ctx, 'amp', False):
            def _is_param(slot):
                try:
                    return bool(ctx.var(op.single_input(slot)).persistable)
                except Exception:
                    return False
            xd = getattr(x, 'dtype', None)
            yd = getattr(y, 'dtype', None)
            if xd == jnp.bfloat16 and yd == jnp.float32 \
                    and _is_param('Y'):
                y = y.astype(jnp.bfloat16)
            elif yd == jnp.bfloat16 and xd == jnp.float32 \
                    and _is_param('X'):
                x = x.astype(jnp.bfloat16)
        res = fn(x, _broadcast_y(x, y, axis,
                                 _declared_rank(ctx, op, 'X')))
        # Paddle's elementwise contract is X-major: the IR declares
        # Out.shape = X.shape. When Y has MORE dims than x but only
        # size-1 extras (a [] mean meeting a [1] scale), numpy
        # broadcasting widens the value past the declared shape and the
        # vjp later rejects the cotangent — fold the pure-1 padding
        # back to x's shape so declared == actual.
        if jnp.shape(res) != jnp.shape(x) and \
                int(np.prod(jnp.shape(res))) == int(np.prod(jnp.shape(x))):
            res = res.reshape(jnp.shape(x))
        ctx.set(op.single_output('Out'), res)

    def infer(op, block):
        x = block.var_recursive(op.single_input('X'))
        out = block.var_recursive(op.single_output('Out'))
        out.shape = x.shape
        out.dtype = x.dtype if out.dtype is None else out.dtype
        out.lod_level = x.lod_level

    register_op(op_type, emit=emit, infer_shape=infer)
    register_vjp_grad(op_type, in_slots=('X', 'Y'))


_register_elementwise('add', jnp.add)
_register_elementwise('sub', jnp.subtract)
_register_elementwise('mul', jnp.multiply)
_register_elementwise('div', jnp.divide)
_register_elementwise('max', jnp.maximum)
_register_elementwise('min', jnp.minimum)
_register_elementwise('pow', jnp.power)
_register_elementwise('mod', jnp.mod)
_register_elementwise('floordiv', jnp.floor_divide)


# ---------------------------------------------------------------------------
# mul: the FC matmul with dim-flattening (reference mul_op.cc: x_num_col_dims)
# ---------------------------------------------------------------------------

@op_emitter('mul')
def _mul_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    y = ctx.get(op.single_input('Y'))
    xnc = op.attr('x_num_col_dims', 1)
    ync = op.attr('y_num_col_dims', 1)
    y2 = y.reshape(int(np.prod(y.shape[:ync])), -1)
    k = y2.shape[0]
    # number of contracted trailing dims comes from the DECLARED rank:
    # the padded-sequence runtime inserts a time axis at position 1, so
    # the trailing (declared_rank - xnc) feature dims are unchanged.
    # ([B,T,D] built as [B,D]@[D,H] contracts 1 dim -> [B,T,H]; a batch
    # whose max length is 1 must NOT collapse to [B,H].)
    declared = _declared_rank(ctx, op, 'X')
    if declared is not None and x.ndim == declared + 1 and xnc >= 1:
        nd = declared - xnc
    else:
        nd = x.ndim - xnc
    if int(np.prod(x.shape[x.ndim - nd:])) != k:
        raise ValueError(
            'mul: cannot align x shape %s (declared rank %s, '
            'x_num_col_dims %d) with contraction size %d'
            % (x.shape, declared, xnc, k))
    from ..flags import get_flag
    out_shape = x.shape[:x.ndim - nd] + y.shape[ync:]
    if nd == 1 and x.ndim > 2 and get_flag('mul_dotgen'):
        # single contracted dim on a batched x: contract directly with
        # dot_general instead of flattening to 2D. Same forward HLO
        # after XLA's reshape folding, but the vjp-derived dW becomes a
        # batch-dims contraction over the ORIGINAL shape rather than
        # d/d(reshape) — giving layout assignment the un-flattened view
        # of the activation.
        xq, y2 = amp_cast(ctx, x, y2)
        out = jax.lax.dot_general(
            xq, y2, (((xq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
            if xq.dtype == jnp.bfloat16 else xq.dtype).astype(xq.dtype)
        ctx.set(op.single_output('Out'), out.reshape(out_shape))
        return
    x2 = x.reshape(-1, int(np.prod(x.shape[x.ndim - nd:])))
    x2, y2 = amp_cast(ctx, x2, y2)
    out2 = jnp.matmul(
        x2, y2,
        preferred_element_type=jnp.float32
        if x2.dtype == jnp.bfloat16 else x2.dtype).astype(x2.dtype)
    ctx.set(op.single_output('Out'), out2.reshape(out_shape))


def _mul_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    y = block.var_recursive(op.single_input('Y'))
    xnc = op.attr('x_num_col_dims', 1)
    ync = op.attr('y_num_col_dims', 1)
    out = block.var_recursive(op.single_output('Out'))
    out.shape = tuple(x.shape[:xnc]) + tuple(y.shape[ync:])
    out.dtype = x.dtype
    out.lod_level = x.lod_level


register_op('mul', infer_shape=_mul_infer)
register_vjp_grad('mul', in_slots=('X', 'Y'))


@op_emitter('matmul')
def _matmul_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    y = ctx.get(op.single_input('Y'))
    if op.attr('transpose_X', False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if op.attr('transpose_Y', False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    x, y = amp_cast(ctx, x, y)
    out = jnp.matmul(
        x, y,
        preferred_element_type=jnp.float32
        if x.dtype == jnp.bfloat16 else None).astype(x.dtype)
    alpha = op.attr('alpha', 1.0)
    if alpha != 1.0:
        out = out * alpha
    ctx.set(op.single_output('Out'), out)


def _matmul_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    y = block.var_recursive(op.single_input('Y'))
    xs = list(x.shape)
    ys = list(y.shape)
    if op.attr('transpose_X', False) and len(xs) > 1:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if op.attr('transpose_Y', False) and len(ys) > 1:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if len(xs) == 1:
        xs = [1] + xs
    if len(ys) == 1:
        ys = ys + [1]
    batch = xs[:-2] if len(xs) > 2 else ys[:-2]
    out = block.var_recursive(op.single_output('Out'))
    out.shape = tuple(batch) + (xs[-2], ys[-1])
    out.dtype = x.dtype


register_op('matmul', infer_shape=_matmul_infer)
register_vjp_grad('matmul', in_slots=('X', 'Y'))


# ---------------------------------------------------------------------------
# activations (reference activation_op.cc registers ~25 of these)
# ---------------------------------------------------------------------------

def _register_unary(op_type, fn, attrs_fn=None):
    def emit(ctx, op):
        x = ctx.get(op.single_input('X'))
        if attrs_fn is not None:
            ctx.set(op.single_output('Out'), attrs_fn(x, op))
        else:
            ctx.set(op.single_output('Out'), fn(x))

    register_op(op_type, emit=emit, infer_shape=same_shape_infer())
    register_vjp_grad(op_type)


_register_unary('relu', jax.nn.relu)
_register_unary('sigmoid', jax.nn.sigmoid)
_register_unary('logsigmoid', jax.nn.log_sigmoid)
_register_unary('tanh', jnp.tanh)
_register_unary('tanh_shrink', lambda x: x - jnp.tanh(x))
_register_unary('exp', jnp.exp)
_register_unary('log', jnp.log)
_register_unary('square', jnp.square)
_register_unary('sqrt', jnp.sqrt)
_register_unary('rsqrt', lambda x: 1.0 / jnp.sqrt(x))
_register_unary('abs', jnp.abs)
_register_unary('ceil', jnp.ceil)
_register_unary('floor', jnp.floor)
_register_unary('round', jnp.round)
_register_unary('reciprocal', lambda x: 1.0 / x)
_register_unary('sin', jnp.sin)
_register_unary('cos', jnp.cos)
_register_unary('softplus', jax.nn.softplus)
_register_unary('softsign', lambda x: x / (1 + jnp.abs(x)))
_register_unary('relu6', lambda x, op=None: jnp.clip(x, 0, 6),)
_register_unary('softshrink', None,
                lambda x, op: jnp.where(x > op.attr('lambda', 0.5),
                                        x - op.attr('lambda', 0.5),
                                        jnp.where(x < -op.attr('lambda', 0.5),
                                                  x + op.attr('lambda', 0.5), 0.0)))
_register_unary('leaky_relu', None,
                lambda x, op: jnp.where(x >= 0, x, x * op.attr('alpha', 0.02)))
_register_unary('elu', None,
                lambda x, op: jnp.where(x >= 0, x,
                                        op.attr('alpha', 1.0) * (jnp.exp(x) - 1)))
_register_unary('pow', None, lambda x, op: jnp.power(x, op.attr('factor', 1.0)))
_register_unary('hard_sigmoid', None,
                lambda x, op: jnp.clip(x * op.attr('slope', 0.2)
                                       + op.attr('offset', 0.5), 0.0, 1.0))
_register_unary('brelu', None,
                lambda x, op: jnp.clip(x, op.attr('t_min', 0.0),
                                       op.attr('t_max', 24.0)))
_register_unary('swish', None,
                lambda x, op: x * jax.nn.sigmoid(op.attr('beta', 1.0) * x))
_register_unary('gelu', jax.nn.gelu)
_register_unary('stanh', None,
                lambda x, op: op.attr('scale_b', 1.7159) *
                jnp.tanh(op.attr('scale_a', 2.0 / 3.0) * x))
_register_unary('thresholded_relu', None,
                lambda x, op: jnp.where(x > op.attr('threshold', 1.0), x, 0.0))
_register_unary('hard_shrink', None,
                lambda x, op: jnp.where(jnp.abs(x) > op.attr('threshold', 0.5),
                                        x, 0.0))
_register_unary('logit', None,
                lambda x, op: jnp.log(x / (1.0 - x)))


@op_emitter('scale')
def _scale_emit(ctx, op):
    from ..selected_rows import SelectedRows
    x = ctx.get(op.single_input('X'))
    scale = op.attr('scale', 1.0)
    bias = op.attr('bias', 0.0)
    if isinstance(x, SelectedRows):
        # scale on SelectedRows scales the rows (bias must be 0 — a bias
        # would densify; the reference scale kernel is dense-only and the
        # DP loss-scale path only ever multiplies).
        if bias != 0.0:
            raise NotImplementedError(
                'scale with nonzero bias on a SelectedRows grad')
        ctx.set(op.single_output('Out'),
                SelectedRows(x.values * scale, x.rows, x.height))
        return
    if op.attr('bias_after_scale', True):
        out = x * scale + bias
    else:
        out = (x + bias) * scale
    ctx.set(op.single_output('Out'), out)


register_op('scale', infer_shape=same_shape_infer())
register_vjp_grad('scale')


@op_emitter('clip')
def _clip_emit(ctx, op):
    from ..selected_rows import SelectedRows
    x = ctx.get(op.single_input('X'))
    if isinstance(x, SelectedRows):
        # clip is nonlinear, so duplicate rows must be merged before
        # clipping (reference clip_op.h SelectedRows path merges first);
        # densify = merge with static shapes.
        x = x.to_dense()
    ctx.set(op.single_output('Out'),
            jnp.clip(x, op.attr('min'), op.attr('max')))


register_op('clip', infer_shape=same_shape_infer())
register_vjp_grad('clip')


@op_emitter('clip_by_norm')
def _clip_by_norm_emit(ctx, op):
    from ..selected_rows import SelectedRows
    x = ctx.get(op.single_input('X'))
    max_norm = op.attr('max_norm')
    if isinstance(x, SelectedRows):
        # norm must be taken over the MERGED rows (reference
        # clip_by_norm_op.h merges first), but the rescale itself is
        # linear, so the output stays sparse.
        norm = jnp.sqrt(jnp.sum(jnp.square(x.to_dense())))
        scale = jnp.where(norm > max_norm,
                          max_norm / jnp.maximum(norm, 1e-12), 1.0)
        ctx.set(op.single_output('Out'),
                SelectedRows(x.values * scale, x.rows, x.height))
        return
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    ctx.set(op.single_output('Out'), x * scale)


register_op('clip_by_norm', infer_shape=same_shape_infer())
register_vjp_grad('clip_by_norm')


# ---------------------------------------------------------------------------
# sum (n-ary add, the backward dedup op) / mean / reductions
# ---------------------------------------------------------------------------

@op_emitter('sum')
def _sum_emit(ctx, op):
    from ..selected_rows import SelectedRows
    xs = [ctx.get(n) for n in op.input('X')]
    if any(isinstance(x, SelectedRows) for x in xs):
        # Reference sum_op SelectedRows path (math/selected_rows_functor.cc):
        # all-sparse inputs concatenate rows (dedup deferred to the
        # consumer's scatter-add); mixed dense+sparse densifies.
        if all(isinstance(x, SelectedRows) for x in xs):
            vals = jnp.concatenate([x.values for x in xs], axis=0)
            rows = jnp.concatenate(
                [jnp.asarray(x.rows, jnp.int32) for x in xs], axis=0)
            ctx.set(op.single_output('Out'),
                    SelectedRows(vals, rows, xs[0].height))
            return
        xs = [x.to_dense() if isinstance(x, SelectedRows) else x for x in xs]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    ctx.set(op.single_output('Out'), out)


def _sum_infer(op, block):
    x = block.var_recursive(op.input('X')[0])
    out = block.var_recursive(op.single_output('Out'))
    out.shape = x.shape
    out.dtype = x.dtype
    out.lod_level = x.lod_level


register_op('sum', infer_shape=_sum_infer)
register_vjp_grad('sum', in_slots=('X',))


@op_emitter('mean')
def _mean_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    ctx.set(op.single_output('Out'), jnp.mean(x))


def _scalar_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = ()
    out.dtype = x.dtype


register_op('mean', infer_shape=_scalar_infer)
register_vjp_grad('mean')


def _register_reduce(name, fn):
    op_type = 'reduce_' + name

    def emit(ctx, op):
        x = ctx.get(op.single_input('X'))
        if op.attr('reduce_all', False):
            dims = tuple(range(x.ndim))
        else:
            dims = tuple(d % x.ndim for d in op.attr('dim', [0]))
        keep = op.attr('keep_dim', False)
        ctx.set(op.single_output('Out'), fn(x, axis=dims, keepdims=keep))

    def infer(op, block):
        x = block.var_recursive(op.single_input('X'))
        out = block.var_recursive(op.single_output('Out'))
        if x.shape is None:
            return
        nd = len(x.shape)
        if op.attr('reduce_all', False):
            dims = set(range(nd))
        else:
            dims = set(d % nd for d in op.attr('dim', [0]))
        keep = op.attr('keep_dim', False)
        shape = []
        for i, s in enumerate(x.shape):
            if i in dims:
                if keep:
                    shape.append(1)
            else:
                shape.append(s)
        out.shape = tuple(shape)
        out.dtype = x.dtype

    register_op(op_type, infer_shape=infer, emit=emit)
    register_vjp_grad(op_type)


_register_reduce('sum', jnp.sum)
_register_reduce('mean', jnp.mean)
_register_reduce('max', jnp.max)
_register_reduce('min', jnp.min)
_register_reduce('prod', jnp.prod)


# ---------------------------------------------------------------------------
# comparisons / logical ops (no grad)
# ---------------------------------------------------------------------------

def _register_compare(op_type, fn):
    def emit(ctx, op):
        x = ctx.get(op.single_input('X'))
        y = ctx.get(op.single_input('Y'))
        ctx.set(op.single_output('Out'), fn(x, y))

    def infer(op, block):
        x = block.var_recursive(op.single_input('X'))
        out = block.var_recursive(op.single_output('Out'))
        out.shape = x.shape
        out.dtype = 'bool'

    register_op(op_type, emit=emit, infer_shape=infer, no_grad=True)


_register_compare('less_than', jnp.less)
_register_compare('less_equal', jnp.less_equal)
_register_compare('greater_than', jnp.greater)
_register_compare('greater_equal', jnp.greater_equal)
_register_compare('equal', jnp.equal)
_register_compare('not_equal', jnp.not_equal)
_register_compare('logical_and', jnp.logical_and)
_register_compare('logical_or', jnp.logical_or)
_register_compare('logical_xor', jnp.logical_xor)


@op_emitter('logical_not')
def _logical_not_emit(ctx, op):
    ctx.set(op.single_output('Out'),
            jnp.logical_not(ctx.get(op.single_input('X'))))


register_op('logical_not', infer_shape=same_shape_infer(), no_grad=True)


@op_emitter('isfinite')
def _isfinite_emit(ctx, op):
    xs = [ctx.get(n) for n in op.input('X')]
    finite = jnp.array(True)
    for x in xs:
        finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(x)))
    ctx.set(op.single_output('Out'), finite)


def _isfinite_infer(op, block):
    out = block.var_recursive(op.single_output('Out'))
    out.shape = ()
    out.dtype = 'bool'


register_op('isfinite', infer_shape=_isfinite_infer, no_grad=True)


# ---------------------------------------------------------------------------
# top_k / argsort / cumsum
# ---------------------------------------------------------------------------

@op_emitter('top_k')
def _top_k_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    k = op.attr('k', 1)
    values, indices = jax.lax.top_k(x, k)
    ctx.set(op.single_output('Out'), values)
    ctx.set(op.single_output('Indices'), indices.astype(jnp.int64))


def _top_k_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    k = op.attr('k', 1)
    shape = tuple(x.shape[:-1]) + (k,)
    out = block.var_recursive(op.single_output('Out'))
    out.shape = shape
    out.dtype = x.dtype
    idx = block.var_recursive(op.single_output('Indices'))
    idx.shape = shape
    idx.dtype = 'int64'


register_op('top_k', infer_shape=_top_k_infer, no_grad=True)


@op_emitter('argsort')
def _argsort_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    axis = op.attr('axis', -1)
    idx = jnp.argsort(x, axis=axis)
    ctx.set(op.single_output('Out'), jnp.sort(x, axis=axis))
    ctx.set(op.single_output('Indices'), idx.astype(jnp.int64))


def _argsort_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    for slot, dt in (('Out', x.dtype), ('Indices', 'int64')):
        v = block.var_recursive(op.single_output(slot))
        v.shape = x.shape
        v.dtype = dt


register_op('argsort', infer_shape=_argsort_infer, no_grad=True)


@op_emitter('argmax')
def _argmax_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    axis = op.attr('axis', -1)
    ctx.set(op.single_output('Out'), jnp.argmax(x, axis=axis).astype(jnp.int64))


def _argmax_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    axis = op.attr('axis', -1)
    if x.shape is None:
        return
    nd = len(x.shape)
    axis = axis % nd
    out = block.var_recursive(op.single_output('Out'))
    out.shape = tuple(s for i, s in enumerate(x.shape) if i != axis)
    out.dtype = 'int64'


register_op('argmax', infer_shape=_argmax_infer, no_grad=True)


@op_emitter('cumsum')
def _cumsum_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    axis = op.attr('axis', -1)
    out = jnp.cumsum(jnp.flip(x, axis) if op.attr('reverse', False) else x,
                     axis=axis)
    if op.attr('reverse', False):
        out = jnp.flip(out, axis)
    if op.attr('exclusive', False):
        out = out - (ctx.get(op.single_input('X')))
    ctx.set(op.single_output('Out'), out)


register_op('cumsum', infer_shape=same_shape_infer())
register_vjp_grad('cumsum')


@op_emitter('increment')
def _increment_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    step = jnp.asarray(op.attr('step', 1.0)).astype(x.dtype)
    ctx.set(op.single_output('Out'), x + step)


register_op('increment', infer_shape=same_shape_infer(), no_grad=True)


# ---------------------------------------------------------------------------
# maximum-norm helpers used by grad clipping (reference clip.py)
# ---------------------------------------------------------------------------

@op_emitter('squared_l2_norm')
def _squared_l2_norm_emit(ctx, op):
    from ..selected_rows import SelectedRows
    x = ctx.get(op.single_input('X'))
    if isinstance(x, SelectedRows):
        # duplicate rows sum before the square (merge semantics)
        x = x.to_dense()
    ctx.set(op.single_output('Out'), jnp.sum(jnp.square(x)))


register_op('squared_l2_norm', infer_shape=_scalar_infer)
register_vjp_grad('squared_l2_norm')


# ---------------------------------------------------------------------------
# where: elementwise/row-wise select (backs layers.where_select / IfElse)
# ---------------------------------------------------------------------------

def _where_emit(ctx, op):
    cond = ctx.get(op.single_input('Cond'))
    x = ctx.get(op.single_input('X'))
    y = ctx.get(op.single_input('Y'))
    # align cond's rank to x's: drop size-1 trailing axes (e.g. [B,1] cond
    # vs [B] operands), then pad with size-1 trailing axes for row-wise
    # broadcast -- result shape must equal x's
    while cond.ndim > x.ndim and cond.shape[-1] == 1:
        cond = cond.reshape(cond.shape[:-1])
    if cond.ndim > x.ndim:
        raise ValueError(
            'where: cond rank %d not broadcastable to operand rank %d'
            % (cond.ndim, x.ndim))
    if cond.ndim < x.ndim:
        cond = cond.reshape(cond.shape + (1,) * (x.ndim - cond.ndim))
    ctx.set(op.single_output('Out'), jnp.where(cond, x, y))


def _where_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = x.shape
    out.dtype = x.dtype


register_op('where', emit=_where_emit, infer_shape=_where_infer)
register_vjp_grad('where', in_slots=('X', 'Y'), nondiff_slots=('Cond',))
