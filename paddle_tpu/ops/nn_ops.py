"""Neural-network ops: conv, pool, normalization, losses, embedding, dropout.

TPU-native re-design of reference paddle/fluid/operators/{conv_op.cc,
conv_cudnn_op.cu, pool_op.cc, batch_norm_op.cc, layer_norm_op.cc, softmax_op.cc,
cross_entropy_op.cc, softmax_with_cross_entropy_op.cc, dropout_op.cc,
lookup_table_op.cc, accuracy_op.cc, sigmoid_cross_entropy_with_logits_op.cc}.

All convs/matmuls carry `preferred_element_type` so the MXU accumulates in
fp32 even when activations are bf16. Layout is per-op: NCHW (Paddle's
default contract) or data_format='NHWC' (channels-last, the TPU lane-native
layout) on conv2d/pool2d and data_layout on batch_norm; filters stay OIHW
in the IR/checkpoint contract in both modes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import (register_op, op_emitter, same_shape_infer,
                        register_vjp_grad, amp_cast)


# ---------------------------------------------------------------------------
# conv2d / depthwise_conv2d (reference conv_op.cc:187)
# ---------------------------------------------------------------------------

def _conv2d_common_emit(ctx, op):
    x = ctx.get(op.single_input('Input'))
    w = ctx.get(op.single_input('Filter'))
    x, w = amp_cast(ctx, x, w)
    strides = op.attr('strides', [1, 1])
    paddings = op.attr('paddings', [0, 0])
    dilations = op.attr('dilations', [1, 1])
    groups = op.attr('groups', 1) or 1
    # data_format NHWC puts channels on the TPU lane dimension end to end
    # (the layout XLA's own assignment picks physically); filters stay
    # OIHW in the IR/checkpoint contract and are relaid here
    nhwc = op.attr('data_format', 'NCHW') == 'NHWC'
    ch_axis = 3 if nhwc else 1
    if op.type == 'depthwise_conv2d':
        groups = x.shape[ch_axis]
    # bf16 operands on TPU: no explicit accumulator upcast -- the MXU
    # accumulates bf16 convs in fp32 internally, and JAX's conv transpose
    # rule rejects mixed-dtype operands that preferred_element_type would
    # create. Off-TPU (CPU tests, GPU) there is no such hardware guarantee,
    # so keep fp32 accumulation by upcasting the operands.
    out_dtype = x.dtype
    if x.dtype == jnp.bfloat16 and jax.default_backend() != 'tpu':
        x = x.astype(jnp.float32)
        w = w.astype(jnp.float32)
    out = jax.lax.conv_general_dilated(
        x, w,
        window_strides=tuple(strides),
        padding=[(paddings[0], paddings[0]), (paddings[1], paddings[1])],
        rhs_dilation=tuple(dilations),
        dimension_numbers=(('NHWC', 'OIHW', 'NHWC') if nhwc
                           else ('NCHW', 'OIHW', 'NCHW')),
        feature_group_count=groups)
    ctx.set(op.single_output('Output'), out.astype(out_dtype))


def _conv_out_size(in_size, k, pad, stride, dilation):
    if in_size < 0:
        return -1
    eff_k = dilation * (k - 1) + 1
    return (in_size + 2 * pad - eff_k) // stride + 1


def _conv2d_infer(op, block):
    x = block.var_recursive(op.single_input('Input'))
    w = block.var_recursive(op.single_input('Filter'))
    strides = op.attr('strides', [1, 1])
    paddings = op.attr('paddings', [0, 0])
    dilations = op.attr('dilations', [1, 1])
    nhwc = op.attr('data_format', 'NCHW') == 'NHWC'
    if nhwc:
        n, h, wd, _ = x.shape
    else:
        n, _, h, wd = x.shape
    oc, _, kh, kw = w.shape
    oh = _conv_out_size(h, kh, paddings[0], strides[0], dilations[0])
    ow = _conv_out_size(wd, kw, paddings[1], strides[1], dilations[1])
    out = block.var_recursive(op.single_output('Output'))
    out.shape = (n, oh, ow, oc) if nhwc else (n, oc, oh, ow)
    out.dtype = x.dtype


for _conv_type in ('conv2d', 'depthwise_conv2d'):
    register_op(_conv_type, emit=_conv2d_common_emit, infer_shape=_conv2d_infer)
    register_vjp_grad(_conv_type, in_slots=('Input', 'Filter'),
                      out_slots=('Output',))


def conv_transpose_nd(x, w, strides, paddings, dilations, groups, nd):
    """Transpose conv as an lhs-dilated forward conv — the formulation XLA
    itself uses for conv input-gradients, with exact control of the
    reference's output-size contract out = (i-1)*s - 2p + d*(k-1) + 1.

    w comes in the reference/torch transpose-conv layout [in_c, out_c/g,
    k...]; it is regrouped to a forward kernel [out_c, in_c/g, k...] and
    spatially flipped.
    """
    in_c = x.shape[1]
    ws = jnp.reshape(w, (groups, in_c // groups) + w.shape[1:])
    ws = jnp.swapaxes(ws, 1, 2)                    # [g, oc/g, in/g, k...]
    ws = jnp.reshape(ws, (-1,) + ws.shape[2:])     # [out_c, in/g, k...]
    ws = jnp.flip(ws, axis=tuple(range(2, 2 + nd)))
    pads = [(dilations[i] * (w.shape[2 + i] - 1) - paddings[i],) * 2
            for i in range(nd)]
    dn = (('NCHW', 'OIHW', 'NCHW') if nd == 2
          else ('NCDHW', 'OIDHW', 'NCDHW'))
    return jax.lax.conv_general_dilated(
        x, ws, window_strides=(1,) * nd, padding=pads,
        lhs_dilation=tuple(strides), rhs_dilation=tuple(dilations),
        dimension_numbers=dn, feature_group_count=groups)


@op_emitter('conv2d_transpose')
def _conv2d_transpose_emit(ctx, op):
    x = ctx.get(op.single_input('Input'))
    w = ctx.get(op.single_input('Filter'))   # [in_c, out_c/g, kh, kw]
    x, w = amp_cast(ctx, x, w)
    strides = op.attr('strides', [1, 1])
    paddings = op.attr('paddings', [0, 0])
    dilations = op.attr('dilations', [1, 1])
    groups = op.attr('groups', 1) or 1
    out = conv_transpose_nd(x, w, strides, paddings, dilations, groups, 2)
    ctx.set(op.single_output('Output'), out)


def _conv2d_transpose_infer(op, block):
    x = block.var_recursive(op.single_input('Input'))
    w = block.var_recursive(op.single_input('Filter'))
    strides = op.attr('strides', [1, 1])
    paddings = op.attr('paddings', [0, 0])
    dilations = op.attr('dilations', [1, 1])
    n, _, h, wd = x.shape
    _, oc, kh, kw = w.shape
    def osz(i, k, p, s, d):
        if i < 0:
            return -1
        return (i - 1) * s - 2 * p + d * (k - 1) + 1
    out = block.var_recursive(op.single_output('Output'))
    out.shape = (n, oc * (op.attr('groups', 1) or 1),
                 osz(h, kh, paddings[0], strides[0], dilations[0]),
                 osz(wd, kw, paddings[1], strides[1], dilations[1]))
    out.dtype = x.dtype


register_op('conv2d_transpose', infer_shape=_conv2d_transpose_infer)
register_vjp_grad('conv2d_transpose', in_slots=('Input', 'Filter'),
                  out_slots=('Output',))


# ---------------------------------------------------------------------------
# pool2d (reference pool_op.cc)
# ---------------------------------------------------------------------------

def _pool_spatial_pads(in_sizes, ksize, strides, paddings, ceil_mode):
    """(lo, hi) pads per spatial dim; ceil_mode adds asymmetric right
    padding so reduce_window produces the ceil-formula output size the
    shape inference promises (reference pool_op.cc ceil semantics)."""
    pads = []
    for i, n in enumerate(in_sizes):
        if ceil_mode:
            out = (n - ksize[i] + 2 * paddings[i] + strides[i] - 1) \
                // strides[i] + 1
        else:
            out = (n - ksize[i] + 2 * paddings[i]) // strides[i] + 1
        extra = (out - 1) * strides[i] + ksize[i] - (n + 2 * paddings[i])
        pads.append((paddings[i], paddings[i] + max(extra, 0)))
    return pads


@op_emitter('pool2d')
def _pool2d_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    ptype = op.attr('pooling_type', 'max')
    ksize = list(op.attr('ksize'))
    strides = list(op.attr('strides', [1, 1]))
    paddings = list(op.attr('paddings', [0, 0]))
    nhwc = op.attr('data_format', 'NCHW') == 'NHWC'
    hw = (1, 2) if nhwc else (2, 3)
    if op.attr('global_pooling', False):
        ksize = [x.shape[hw[0]], x.shape[hw[1]]]
        strides = [1, 1]
        paddings = [0, 0]
    if nhwc:
        window = (1, ksize[0], ksize[1], 1)
        strides4 = (1, strides[0], strides[1], 1)
    else:
        window = (1, 1, ksize[0], ksize[1])
        strides4 = (1, 1, strides[0], strides[1])
    sp = _pool_spatial_pads([x.shape[hw[0]], x.shape[hw[1]]], ksize, strides,
                            paddings, op.attr('ceil_mode', False))
    pads = (((0, 0),) + tuple(sp) + ((0, 0),)) if nhwc \
        else ((0, 0), (0, 0)) + tuple(sp)
    padded = any(lo or hi for lo, hi in sp)
    if ptype == 'max':
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides4, pads)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides4, pads)
        if op.attr('exclusive', True) and padded:
            ones = jnp.ones_like(x)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                           strides4, pads)
            out = summed / counts
        else:
            out = summed / (ksize[0] * ksize[1])
    ctx.set(op.single_output('Out'), out.astype(x.dtype))


def _pool2d_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    nhwc = op.attr('data_format', 'NCHW') == 'NHWC'
    if nhwc:
        n, h, w, c = x.shape
    else:
        n, c, h, w = x.shape
    out = block.var_recursive(op.single_output('Out'))
    if op.attr('global_pooling', False):
        out.shape = (n, 1, 1, c) if nhwc else (n, c, 1, 1)
    else:
        ksize = op.attr('ksize')
        strides = op.attr('strides', [1, 1])
        paddings = op.attr('paddings', [0, 0])

        def osz(i, k, p, s):
            if i < 0:
                return -1
            if op.attr('ceil_mode', False):
                return (i - k + 2 * p + s - 1) // s + 1
            return (i - k + 2 * p) // s + 1
        oh = osz(h, ksize[0], paddings[0], strides[0])
        ow = osz(w, ksize[1], paddings[1], strides[1])
        out.shape = (n, oh, ow, c) if nhwc else (n, c, oh, ow)
    out.dtype = x.dtype


register_op('pool2d', infer_shape=_pool2d_infer)
register_vjp_grad('pool2d')


# ---------------------------------------------------------------------------
# batch_norm (reference batch_norm_op.cc) -- functional running stats:
# MeanOut/VarianceOut are new values the executor writes back to the same
# persistable vars (the reference mutates them in place on GPU).
# ---------------------------------------------------------------------------

def _bn_batch_stats(x, axes):
    """Single-pass batch statistics: sum and sum-of-squares fuse into ONE
    read of x (multi-output reduction fusion), where mean-then-var costs
    two. fp32 accumulation; clamp guards E[x^2]-E[x]^2 cancellation."""
    xf = x.astype(jnp.float32)
    m = 1
    for i in axes:
        m *= x.shape[i]
    sum_x = jnp.sum(xf, axis=axes)
    sum_x2 = jnp.sum(xf * xf, axis=axes)
    mean = sum_x / m
    var = jnp.maximum(sum_x2 / m - mean * mean, 0.0)
    return mean, var


def _bn_local_mode(ctx, op):
    """True when this batch_norm should use per-device local statistics
    (reference multi_devices_graph_pass.cc semantics: batch_norm is
    replicated per device, stats never cross devices). Requires a mesh
    with a 'dp' axis; training mode only. Per-executor BuildStrategy
    override (ctx.bn_local_stats) wins over the global flag."""
    from ..flags import get_flag
    local = getattr(ctx, 'bn_local_stats', None)
    if local is None:
        local = get_flag('bn_local_stats')
    return bool(local) and ctx.mesh is not None \
        and 'dp' in ctx.mesh.axis_names


def _bn_shard_map(ctx, fn, n_big, n_small, out_specs):
    """shard_map wrapper for the local-stats paths: the first n_big args
    are batch-dim-sharded activations, the rest are replicated channel
    vectors. check_vma=False because per-device statistics outputs are
    deliberately divergent across devices (reference per-device BN
    state)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    in_specs = tuple([P('dp')] * n_big + [P()] * n_small)
    return shard_map(fn, mesh=ctx.mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


@op_emitter('batch_norm')
def _batch_norm_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    scale = ctx.get(op.single_input('Scale'))
    bias = ctx.get(op.single_input('Bias'))
    mean = ctx.get(op.single_input('Mean'))
    var = ctx.get(op.single_input('Variance'))
    eps = op.attr('epsilon', 1e-5)
    momentum = op.attr('momentum', 0.9)
    is_test = op.attr('is_test', False) or ctx.is_test
    layout = op.attr('data_layout', 'NCHW')
    axes = tuple(i for i in range(x.ndim)
                 if i != (1 if layout == 'NCHW' else x.ndim - 1))
    ch_shape = [1] * x.ndim
    ch_shape[1 if layout == 'NCHW' else -1] = -1

    def _affine(x_, mean_, var_, scale_, bias_):
        # Fold (mean, inv_std, scale, bias) into one per-channel (a, b) so
        # the normalize pass is a single fused multiply-add over the
        # bf16 stream.
        inv_std = jax.lax.rsqrt(var_.astype(jnp.float32) + eps)
        a = scale_.astype(jnp.float32) * inv_std
        b = bias_.astype(jnp.float32) - mean_.astype(jnp.float32) * a
        y_ = x_.astype(jnp.float32) * a.reshape(ch_shape) + b.reshape(ch_shape)
        return y_.astype(x_.dtype)

    if not is_test and _bn_local_mode(ctx, op):
        # per-device statistics (reference replicated-batch_norm
        # semantics): zero collectives; running stats diverge per device
        from jax.sharding import PartitionSpec as P

        def fwd(x_s, scale_s, bias_s, mean_s, var_s):
            lm, lv = _bn_batch_stats(x_s, axes)
            y_s = _affine(x_s, lm, lv, scale_s, bias_s)
            mo = mean_s * momentum + lm * (1 - momentum)
            vo = var_s * momentum + lv * (1 - momentum)
            return y_s, mo, vo, lm, lv

        y, mean_out, var_out, saved_mean, saved_var = _bn_shard_map(
            ctx, fwd, 1, 4, (P('dp'), P(), P(), P(), P()))(
                x, scale, bias, mean, var)
        ctx.set(op.single_output('Y'), y)
    else:
        if is_test:
            use_mean, use_var = mean, var
            saved_mean = mean
            saved_var = var
            mean_out, var_out = mean, var
        else:
            use_mean, use_var = _bn_batch_stats(x, axes)
            saved_mean = use_mean
            saved_var = use_var
            mean_out = mean * momentum + use_mean * (1 - momentum)
            var_out = var * momentum + use_var * (1 - momentum)
        ctx.set(op.single_output('Y'),
                _affine(x, use_mean, use_var, scale, bias))
    if op.output('MeanOut'):
        ctx.set(op.single_output('MeanOut'), mean_out)
    if op.output('VarianceOut'):
        ctx.set(op.single_output('VarianceOut'), var_out)
    if op.output('SavedMean'):
        ctx.set(op.single_output('SavedMean'), saved_mean)
    if op.output('SavedVariance'):
        ctx.set(op.single_output('SavedVariance'), saved_var)


def _batch_norm_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    layout = op.attr('data_layout', 'NCHW')
    c = x.shape[1] if layout == 'NCHW' else x.shape[-1]
    y = block.var_recursive(op.single_output('Y'))
    y.shape = x.shape
    y.dtype = x.dtype
    for slot in ('MeanOut', 'VarianceOut', 'SavedMean', 'SavedVariance'):
        if op.output(slot):
            v = block.var_recursive(op.single_output(slot))
            v.shape = (c,)
            v.dtype = 'float32'


def _batch_norm_grad(op, block):
    """Differentiate w.r.t. X, Scale, Bias only (running stats are state,
    not parameters) -- matches reference batch_norm_op.cc grad."""
    from ..framework import grad_var_name
    attrs = dict(op.attrs)
    attrs['__fwd_inputs__'] = {k: list(v) for k, v in op.inputs.items()}
    attrs['__fwd_outputs__'] = {k: list(v) for k, v in op.outputs.items()}
    inputs = {'X': list(op.input('X')), 'Scale': list(op.input('Scale')),
              'Bias': list(op.input('Bias')), 'Mean': list(op.input('Mean')),
              'Variance': list(op.input('Variance')),
              'Y@GRAD': [grad_var_name(op.single_output('Y'))]}
    # Reference batch_norm_grad consumes the saved batch statistics
    # (batch_norm_op.cc grad op's SavedMean/SavedVariance inputs) rather
    # than recomputing them; wiring them through lets the emitter use the
    # closed-form backward (two fused passes over x/dy instead of a
    # vjp-through-recomputed-statistics chain).
    if op.output('SavedMean'):
        inputs['SavedMean'] = list(op.output('SavedMean'))
    if op.output('SavedVariance'):
        inputs['SavedVariance'] = list(op.output('SavedVariance'))
    outputs = {'X@GRAD': [grad_var_name(op.single_input('X'))],
               'Scale@GRAD': [grad_var_name(op.single_input('Scale'))],
               'Bias@GRAD': [grad_var_name(op.single_input('Bias'))]}
    return [dict(type='batch_norm_grad', inputs=inputs, outputs=outputs,
                 attrs=attrs)]


@op_emitter('batch_norm_grad')
def _batch_norm_grad_emit(ctx, op):
    """Closed-form BN backward (reference batch_norm_op.cc grad kernel).

    Training mode, stats = batch stats (gradients flow through them):
        dxhat   = dy * scale
        dx      = inv_std/m * (m*dxhat - sum(dxhat) - xhat*sum(dxhat*xhat))
        dscale  = sum(dy * xhat),  dbias = sum(dy)
    Written so XLA lowers it to exactly two fused passes over (x, dy):
    one multi-output reduction pass for the three channel sums, one
    elementwise pass producing dx — the vjp-through-recomputed-statistics
    form this replaces materialized fp32 activation-sized residuals
    between extra reduction passes (the round-4 ResNet ladder's
    bandwidth-bound backward regions).
    """
    fwd_inputs = op.attr('__fwd_inputs__')
    x = ctx.get(fwd_inputs['X'][0])
    scale = ctx.get(fwd_inputs['Scale'][0])
    gy = ctx.get(op.single_input('Y@GRAD'))
    eps = op.attr('epsilon', 1e-5)
    is_test = op.attr('is_test', False) or ctx.is_test
    layout = op.attr('data_layout', 'NCHW')
    axes = tuple(i for i in range(x.ndim)
                 if i != (1 if layout == 'NCHW' else x.ndim - 1))
    ch_shape = [1] * x.ndim
    ch_shape[1 if layout == 'NCHW' else -1] = -1
    m = 1
    for i in axes:
        m *= x.shape[i]

    if not is_test and _bn_local_mode(ctx, op):
        # per-device backward: local statistics recomputed per shard
        # (deterministic, identical to the forward's local stats); dx is
        # fully local; scale/bias grads are psum'd so GSPMD's collective
        # combiner folds them into the ONE coalesced gradient all-reduce
        from jax.sharding import PartitionSpec as P

        def bwd(x_s, gy_s, scale_s):
            m_l = 1
            for i in axes:
                m_l *= x_s.shape[i]
            lm, lv = _bn_batch_stats(x_s, axes)
            inv_std = jax.lax.rsqrt(lv + eps)
            xf_s = x_s.astype(jnp.float32)
            gyf_s = gy_s.astype(jnp.float32)
            xhat = (xf_s - lm.reshape(ch_shape)) * inv_std.reshape(ch_shape)
            sum_dy = jnp.sum(gyf_s, axis=axes)
            sum_dy_xhat = jnp.sum(gyf_s * xhat, axis=axes)
            coef = (scale_s.astype(jnp.float32) * inv_std) / m_l
            gx_s = (coef.reshape(ch_shape)
                    * (m_l * gyf_s - sum_dy.reshape(ch_shape)
                       - xhat * sum_dy_xhat.reshape(ch_shape)))
            gs = jax.lax.psum(sum_dy_xhat, 'dp')
            gb = jax.lax.psum(sum_dy, 'dp')
            return gx_s.astype(x_s.dtype), gs, gb

        gx, gscale, gbias = _bn_shard_map(
            ctx, bwd, 2, 1, (P('dp'), P(), P()))(x, gy, scale)
        bias = ctx.get(fwd_inputs['Bias'][0])
        ctx.set(op.single_output('X@GRAD'), gx)
        ctx.set(op.single_output('Scale@GRAD'), gscale.astype(scale.dtype))
        ctx.set(op.single_output('Bias@GRAD'), gbias.astype(bias.dtype))
        return

    xf = x.astype(jnp.float32)
    gyf = gy.astype(jnp.float32)
    scale_f = scale.astype(jnp.float32)

    if is_test:
        # Stats are constants (running mean/var): dx is a pure rescale.
        mean = ctx.get(fwd_inputs['Mean'][0]).astype(jnp.float32)
        var = ctx.get(fwd_inputs['Variance'][0]).astype(jnp.float32)
        inv_std = jax.lax.rsqrt(var + eps)
        xhat = (xf - mean.reshape(ch_shape)) * inv_std.reshape(ch_shape)
        gx = gyf * (scale_f * inv_std).reshape(ch_shape)
        gscale = jnp.sum(gyf * xhat, axis=axes)
        gbias = jnp.sum(gyf, axis=axes)
    else:
        if op.input('SavedMean') and op.input('SavedVariance'):
            mean = ctx.get(op.single_input('SavedMean')).astype(jnp.float32)
            var = ctx.get(op.single_input('SavedVariance')).astype(jnp.float32)
        else:
            # Caller did not thread saved stats: recompute, single pass.
            mean, var = _bn_batch_stats(x, axes)
        inv_std = jax.lax.rsqrt(var + eps)
        xhat = (xf - mean.reshape(ch_shape)) * inv_std.reshape(ch_shape)
        sum_dy = jnp.sum(gyf, axis=axes)
        sum_dy_xhat = jnp.sum(gyf * xhat, axis=axes)
        coef = (scale_f * inv_std) / m
        gx = (coef.reshape(ch_shape)
              * (m * gyf - sum_dy.reshape(ch_shape)
                 - xhat * sum_dy_xhat.reshape(ch_shape)))
        gscale = sum_dy_xhat
        gbias = sum_dy

    bias = ctx.get(fwd_inputs['Bias'][0])
    ctx.set(op.single_output('X@GRAD'), gx.astype(x.dtype))
    ctx.set(op.single_output('Scale@GRAD'), gscale.astype(scale.dtype))
    ctx.set(op.single_output('Bias@GRAD'), gbias.astype(bias.dtype))


register_op('batch_norm', infer_shape=_batch_norm_infer, grad=_batch_norm_grad)


# ---------------------------------------------------------------------------
# layer_norm (reference layer_norm_op.cc)
# ---------------------------------------------------------------------------

@op_emitter('layer_norm')
def _layer_norm_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    eps = op.attr('epsilon', 1e-5)
    begin = op.attr('begin_norm_axis', 1)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = (xf - mean) * inv
    norm_shape = [1] * begin + list(x.shape[begin:])
    if op.input('Scale'):
        y = y * ctx.get(op.single_input('Scale')).reshape(norm_shape)
    if op.input('Bias'):
        y = y + ctx.get(op.single_input('Bias')).reshape(norm_shape)
    ctx.set(op.single_output('Y'), y.astype(x.dtype))
    if op.output('Mean'):
        ctx.set(op.single_output('Mean'), mean.reshape(x.shape[:begin]))
    if op.output('Variance'):
        ctx.set(op.single_output('Variance'), var.reshape(x.shape[:begin]))


def _layer_norm_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    begin = op.attr('begin_norm_axis', 1)
    y = block.var_recursive(op.single_output('Y'))
    y.shape = x.shape
    y.dtype = x.dtype
    for slot in ('Mean', 'Variance'):
        if op.output(slot):
            v = block.var_recursive(op.single_output(slot))
            v.shape = tuple(x.shape[:begin])
            v.dtype = 'float32'


register_op('layer_norm', infer_shape=_layer_norm_infer)
register_vjp_grad('layer_norm', in_slots=('X', 'Scale', 'Bias'),
                  out_slots=('Y',))


# ---------------------------------------------------------------------------
# softmax / cross entropy family
# ---------------------------------------------------------------------------

@op_emitter('softmax')
def _softmax_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    # always reduce in fp32: bf16 exp/sum loses too much for wide vocabs
    out = jax.nn.softmax(x.astype(jnp.float32), axis=-1)
    if not getattr(ctx, 'amp', False):
        out = out.astype(x.dtype)
    ctx.set(op.single_output('Out'), out)


register_op('softmax', infer_shape=same_shape_infer())
register_vjp_grad('softmax')


@op_emitter('cross_entropy')
def _cross_entropy_emit(ctx, op):
    x = ctx.get(op.single_input('X'))          # probabilities
    label = ctx.get(op.single_input('Label'))
    eps = 1e-8
    if op.attr('soft_label', False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, eps)),
                        axis=-1, keepdims=True)
    else:
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        picked = jnp.take_along_axis(x, lbl[..., None].astype(jnp.int32),
                                     axis=-1)
        loss = -jnp.log(jnp.maximum(picked, eps))
        ignore = op.attr('ignore_index', -100)
        loss = jnp.where(lbl[..., None] == ignore, 0.0, loss)
    ctx.set(op.single_output('Y'), loss)


def _cross_entropy_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    y = block.var_recursive(op.single_output('Y'))
    y.shape = tuple(x.shape[:-1]) + (1,)
    y.dtype = x.dtype


register_op('cross_entropy', infer_shape=_cross_entropy_infer)
register_vjp_grad('cross_entropy', in_slots=('X',), out_slots=('Y',),
                  nondiff_slots=('Label',))


@op_emitter('softmax_with_cross_entropy')
def _swce_emit(ctx, op):
    logits = ctx.get(op.single_input('Logits'))
    label = ctx.get(op.single_input('Label'))
    # normalize in fp32 regardless of the (possibly bf16) stream dtype:
    # a 32k-way logsumexp loses precision in bf16
    log_sm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ctx.set(op.single_output('Softmax'),
            jnp.exp(log_sm).astype(logits.dtype))
    if op.attr('soft_label', False):
        loss = -jnp.sum(label * log_sm, axis=-1, keepdims=True)
    else:
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        picked = jnp.take_along_axis(log_sm, lbl[..., None].astype(jnp.int32),
                                     axis=-1)
        loss = -picked
        ignore = op.attr('ignore_index', -100)
        loss = jnp.where(lbl[..., None] == ignore, 0.0, loss)
    ctx.set(op.single_output('Loss'), loss)


def _swce_infer(op, block):
    x = block.var_recursive(op.single_input('Logits'))
    loss = block.var_recursive(op.single_output('Loss'))
    loss.shape = tuple(x.shape[:-1]) + (1,)
    loss.dtype = x.dtype
    sm = block.var_recursive(op.single_output('Softmax'))
    sm.shape = x.shape
    sm.dtype = x.dtype


register_op('softmax_with_cross_entropy', infer_shape=_swce_infer)
register_vjp_grad('softmax_with_cross_entropy', in_slots=('Logits',),
                  out_slots=('Loss',), nondiff_slots=('Label',))


@op_emitter('sigmoid_cross_entropy_with_logits')
def _sce_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    label = ctx.get(op.single_input('Label'))
    # numerically-stable bce-with-logits
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = op.attr('ignore_index', -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    ctx.set(op.single_output('Out'), loss)


register_op('sigmoid_cross_entropy_with_logits',
            infer_shape=same_shape_infer())
register_vjp_grad('sigmoid_cross_entropy_with_logits', in_slots=('X',),
                  nondiff_slots=('Label',))


@op_emitter('huber_loss')
def _huber_loss_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    y = ctx.get(op.single_input('Y'))
    delta = op.attr('delta', 1.0)
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= delta, 0.5 * r * r, delta * (a - 0.5 * delta))
    ctx.set(op.single_output('Out'), loss)
    if op.output('Residual'):
        ctx.set(op.single_output('Residual'), r)


def _huber_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = x.shape
    out.dtype = x.dtype
    if op.output('Residual'):
        r = block.var_recursive(op.single_output('Residual'))
        r.shape = x.shape
        r.dtype = x.dtype


register_op('huber_loss', infer_shape=_huber_infer)
register_vjp_grad('huber_loss', in_slots=('X', 'Y'), out_slots=('Out',))


@op_emitter('square_error_cost')
def _square_error_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    y = ctx.get(op.single_input('Y'))
    ctx.set(op.single_output('Out'), jnp.square(x - y))


register_op('square_error_cost', infer_shape=same_shape_infer())
register_vjp_grad('square_error_cost', in_slots=('X', 'Y'))


@op_emitter('smooth_l1_loss')
def _smooth_l1_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    y = ctx.get(op.single_input('Y'))
    sigma = op.attr('sigma', 1.0)
    s2 = sigma * sigma
    diff = x - y
    if op.input('InsideWeight'):
        diff = diff * ctx.get(op.single_input('InsideWeight'))
    a = jnp.abs(diff)
    val = jnp.where(a < 1.0 / s2, 0.5 * s2 * diff * diff, a - 0.5 / s2)
    if op.input('OutsideWeight'):
        val = val * ctx.get(op.single_input('OutsideWeight'))
    out = jnp.sum(val.reshape(val.shape[0], -1), axis=1, keepdims=True)
    ctx.set(op.single_output('Out'), out)
    if op.output('Diff'):
        ctx.set(op.single_output('Diff'), diff)


def _smooth_l1_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = (x.shape[0], 1)
    out.dtype = x.dtype
    if op.output('Diff'):
        d = block.var_recursive(op.single_output('Diff'))
        d.shape = x.shape
        d.dtype = x.dtype


register_op('smooth_l1_loss', infer_shape=_smooth_l1_infer)
register_vjp_grad('smooth_l1_loss', in_slots=('X',), out_slots=('Out',))


# ---------------------------------------------------------------------------
# dropout (reference dropout_op.cc)
# ---------------------------------------------------------------------------

@op_emitter('dropout', stateful=True)
def _dropout_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    p = op.attr('dropout_prob', 0.5)
    is_test = op.attr('is_test', False) or ctx.is_test
    impl = op.attr('dropout_implementation', 'downgrade_in_infer')
    if is_test:
        out = x * (1.0 - p) if impl == 'downgrade_in_infer' else x
        mask = jnp.ones_like(x)
    else:
        key = ctx.rng(op)
        keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
        if impl == 'upscale_in_train':
            out = jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
            mask = keep.astype(x.dtype) / (1.0 - p)
        else:
            out = jnp.where(keep, x, 0.0).astype(x.dtype)
            mask = keep.astype(x.dtype)
    ctx.set(op.single_output('Out'), out)
    if op.output('Mask'):
        ctx.set(op.single_output('Mask'), mask)


def _dropout_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = x.shape
    out.dtype = x.dtype
    if op.output('Mask'):
        m = block.var_recursive(op.single_output('Mask'))
        m.shape = x.shape
        m.dtype = x.dtype


def _dropout_grad(op, block):
    from ..framework import grad_var_name
    return [dict(type='dropout_grad',
                 inputs={'Mask': list(op.output('Mask')),
                         'Out@GRAD': [grad_var_name(op.single_output('Out'))]},
                 outputs={'X@GRAD': [grad_var_name(op.single_input('X'))]},
                 attrs=dict(op.attrs))]


@op_emitter('dropout_grad')
def _dropout_grad_emit(ctx, op):
    g = ctx.get(op.single_input('Out@GRAD'))
    mask = ctx.get(op.single_input('Mask'))
    ctx.set(op.single_output('X@GRAD'), g * mask)


register_op('dropout', infer_shape=_dropout_infer, grad=_dropout_grad)


# ---------------------------------------------------------------------------
# lookup_table / embedding (reference lookup_table_op.cc)
# ---------------------------------------------------------------------------

@op_emitter('lookup_table')
def _lookup_table_emit(ctx, op):
    w = ctx.get(op.single_input('W'))
    ids = ctx.get(op.single_input('Ids'))
    squeeze_last = ids.ndim > 1 and ids.shape[-1] == 1
    flat = ids.reshape(ids.shape[:-1]) if squeeze_last else ids
    out = jnp.take(w, flat.astype(jnp.int32), axis=0)
    if op.attr('padding_idx', -1) != -1:
        pad = op.attr('padding_idx')
        out = jnp.where((flat == pad)[..., None], 0.0, out)
    if squeeze_last:
        out = out.reshape(ids.shape[:-1] + (w.shape[-1],))
    # under AMP the embedding activation starts the bf16 stream: without
    # this the residual path (and every activation GRADIENT flowing back
    # through it) stays fp32 — measured 2x HBM traffic + mixed-dtype
    # backward dots on the transformer bench
    out = amp_cast(ctx, out)
    ctx.set(op.single_output('Out'), out)


def _lookup_table_infer(op, block):
    w = block.var_recursive(op.single_input('W'))
    ids = block.var_recursive(op.single_input('Ids'))
    out = block.var_recursive(op.single_output('Out'))
    ids_shape = tuple(ids.shape)
    if ids_shape and ids_shape[-1] == 1:
        ids_shape = ids_shape[:-1]
    out.shape = ids_shape + (w.shape[-1],)
    out.dtype = w.dtype
    out.lod_level = ids.lod_level


def _lookup_table_grad_maker(op, block):
    from ..framework import grad_var_name
    attrs = dict(op.attrs)
    inputs = {'Ids': list(op.input('Ids')), 'W': list(op.input('W')),
              'Out@GRAD': [grad_var_name(op.single_output('Out'))]}
    outputs = {'W@GRAD': [grad_var_name(op.single_input('W'))]}
    return [dict(type='lookup_table_grad', inputs=inputs, outputs=outputs,
                 attrs=attrs)]


@op_emitter('lookup_table_grad')
def _lookup_table_grad_emit(ctx, op):
    """is_sparse=True: gradient as SelectedRows (rows = the step's ids,
    values = upstream grad rows) with STATIC row count — the TPU shape of
    the reference's dynamically-sized SelectedRows grad
    (lookup_table_op.cc grad kernel). Dense path: scatter-add."""
    from ..selected_rows import SelectedRows
    if op.input('W'):
        w = ctx.get(op.single_input('W'))
        w_shape, w_dtype = tuple(w.shape), w.dtype
    else:
        # distributed lookup table: the trainer never holds W — the
        # transpiler removed the input and recorded the table geometry
        w = None
        w_shape = tuple(op.attr('__table_shape__'))
        w_dtype = jnp.dtype(op.attr('__table_dtype__', 'float32'))
    ids = ctx.get(op.single_input('Ids'))
    gout = ctx.get(op.single_input('Out@GRAD'))
    squeeze_last = ids.ndim > 1 and ids.shape[-1] == 1
    flat = (ids.reshape(ids.shape[:-1]) if squeeze_last else ids)
    flat = flat.reshape(-1).astype(jnp.int32)
    rows_g = gout.reshape((len(flat),) + w_shape[1:])
    pad = op.attr('padding_idx', -1)
    if pad != -1:
        rows_g = jnp.where((flat == pad)[..., None], 0.0, rows_g)
    if op.attr('is_sparse', False):
        ctx.set(op.single_output('W@GRAD'),
                SelectedRows(rows_g.astype(w_dtype), flat, w_shape[0]))
    else:
        gw = jnp.zeros((w_shape), w_dtype).at[flat].add(
            rows_g.astype(w_dtype))
        ctx.set(op.single_output('W@GRAD'), gw)


register_op('lookup_table', infer_shape=_lookup_table_infer,
            grad=_lookup_table_grad_maker)


# ---------------------------------------------------------------------------
# metric ops (reference accuracy_op.cc, auc_op.cc)
# ---------------------------------------------------------------------------

@op_emitter('accuracy')
def _accuracy_emit(ctx, op):
    pred_idx = ctx.get(op.single_input('Indices'))   # [N, k] topk indices
    label = ctx.get(op.single_input('Label'))        # [N, 1]
    n = pred_idx.shape[0]
    correct = jnp.sum(jnp.any(pred_idx == label.reshape(-1, 1), axis=1))
    ctx.set(op.single_output('Accuracy'),
            (correct / n).astype(jnp.float32))
    if op.output('Correct'):
        ctx.set(op.single_output('Correct'), correct.astype(jnp.int32))
    if op.output('Total'):
        ctx.set(op.single_output('Total'), jnp.array(n, dtype=jnp.int32))


def _accuracy_infer(op, block):
    acc = block.var_recursive(op.single_output('Accuracy'))
    acc.shape = ()
    acc.dtype = 'float32'
    for slot, dt in (('Correct', 'int32'), ('Total', 'int32')):
        if op.output(slot):
            v = block.var_recursive(op.single_output(slot))
            v.shape = ()
            v.dtype = dt


register_op('accuracy', infer_shape=_accuracy_infer, no_grad=True)


# ---------------------------------------------------------------------------
# lrn / prelu / maxout -- secondary NN ops
# ---------------------------------------------------------------------------

@op_emitter('prelu')
def _prelu_emit(ctx, op):
    x = ctx.get(op.single_input('X'))
    alpha = ctx.get(op.single_input('Alpha'))
    mode = op.attr('mode', 'all')
    if mode == 'all':
        a = alpha.reshape(())
    elif mode == 'channel':
        a = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    else:
        a = alpha.reshape((1,) + x.shape[1:])
    ctx.set(op.single_output('Out'), jnp.where(x >= 0, x, a * x))


register_op('prelu', infer_shape=same_shape_infer())
register_vjp_grad('prelu', in_slots=('X', 'Alpha'))


@op_emitter('lrn')
def _lrn_emit(ctx, op):
    x = ctx.get(op.single_input('Out') if False else op.single_input('X'))
    n = op.attr('n', 5)
    k = op.attr('k', 2.0)
    alpha = op.attr('alpha', 1e-4)
    beta = op.attr('beta', 0.75)
    half = n // 2
    sq = jnp.square(x)
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    ctx.set(op.single_output('Out'), x / jnp.power(mid, beta))
    if op.output('MidOut'):
        ctx.set(op.single_output('MidOut'), mid)


def _lrn_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = x.shape
    out.dtype = x.dtype
    if op.output('MidOut'):
        m = block.var_recursive(op.single_output('MidOut'))
        m.shape = x.shape
        m.dtype = x.dtype


register_op('lrn', infer_shape=_lrn_infer)
register_vjp_grad('lrn', in_slots=('X',))


# ---------------------------------------------------------------------------
# causal_mask: add a -inf upper-triangular bias to attention scores
# (decoder-only transformer; no reference analog -- 2018 codebase)
# ---------------------------------------------------------------------------

@op_emitter('causal_mask')
def _causal_mask_emit(ctx, op):
    s = ctx.get(op.single_input('X'))          # [..., Tq, Tk]
    Tq, Tk = s.shape[-2], s.shape[-1]
    mask = jnp.tril(jnp.ones((Tq, Tk), dtype=bool))
    neg = jnp.asarray(-1e9, dtype=s.dtype)
    ctx.set(op.single_output('Out'), jnp.where(mask, s, neg))


register_op('causal_mask', infer_shape=same_shape_infer())
register_vjp_grad('causal_mask')


# ---------------------------------------------------------------------------
# position_embedding: learned positions [max_len, D] added per time step
# ---------------------------------------------------------------------------

@op_emitter('position_embedding')
def _position_embedding_emit(ctx, op):
    x = ctx.get(op.single_input('X'))          # [B, T, D]
    pos = ctx.get(op.single_input('Pos'))      # [max_len, D]
    T = x.shape[1]
    # follow the (possibly bf16-under-AMP) activation stream dtype so
    # the downstream residual add does not promote back to fp32
    ctx.set(op.single_output('Out'),
            jnp.broadcast_to(pos[None, :T, :], x.shape).astype(x.dtype))


def _position_embedding_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = x.shape
    out.dtype = x.dtype


register_op('position_embedding', infer_shape=_position_embedding_infer)
register_vjp_grad('position_embedding', in_slots=('Pos',),
                  nondiff_slots=('X',))
