"""Probe: hand-written JAX ResNet-50 train step to find the XLA ceiling on
this chip, NCHW vs NHWC — tells us how much of the bench gap is framework
overhead vs layout/compiler. Not part of the framework."""
import time
import sys

import jax
import jax.numpy as jnp
import numpy as np

BATCH, HW, CLASSES = 512, 224, 1000


def make_params(layout, key):
    rng = np.random.RandomState(0)
    params = []

    def conv_w(cin, cout, k):
        w = rng.randn(cout, cin, k, k).astype('float32') * (1.0 / np.sqrt(cin * k * k))
        if layout == 'NHWC':
            w = w.transpose(2, 3, 1, 0)  # HWIO
        return jnp.asarray(w)

    # stem
    params.append(conv_w(3, 64, 7))
    cin = 64
    for ch, count, stride in [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]:
        for i in range(count):
            blk = {
                'c1': conv_w(cin, ch, 1), 'c2': conv_w(ch, ch, 3),
                'c3': conv_w(ch, ch * 4, 1),
                'bn1': (jnp.ones(ch), jnp.zeros(ch)),
                'bn2': (jnp.ones(ch), jnp.zeros(ch)),
                'bn3': (jnp.ones(ch * 4), jnp.zeros(ch * 4)),
            }
            if i == 0:
                blk['proj'] = conv_w(cin, ch * 4, 1)
                blk['bnp'] = (jnp.ones(ch * 4), jnp.zeros(ch * 4))
            params.append(blk)
            cin = ch * 4
    params.append(jnp.asarray(rng.randn(2048, CLASSES).astype('float32') * 0.02))
    return params


def conv(x, w, stride, layout):
    dn = ('NCHW', 'OIHW', 'NCHW') if layout == 'NCHW' else ('NHWC', 'HWIO', 'NHWC')
    k = w.shape[2] if layout == 'NCHW' else w.shape[0]
    pad = (k - 1) // 2
    return jax.lax.conv_general_dilated(
        x, w.astype(jnp.bfloat16), (stride, stride),
        [(pad, pad), (pad, pad)], dimension_numbers=dn)


def bn_relu(x, sb, layout, relu=True):
    s, b = sb
    axes = (0, 2, 3) if layout == 'NCHW' else (0, 1, 2)
    shape = (1, -1, 1, 1) if layout == 'NCHW' else (1, 1, 1, -1)
    xf = x.astype(jnp.float32)
    m = xf.mean(axes)
    v = xf.var(axes)
    y = (xf - m.reshape(shape)) * jax.lax.rsqrt(v.reshape(shape) + 1e-5)
    y = y * s.reshape(shape) + b.reshape(shape)
    if relu:
        y = jax.nn.relu(y)
    return y.astype(jnp.bfloat16)


def forward(params, x, labels, layout):
    x = x.astype(jnp.bfloat16)
    x = conv(x, params[0], 2, layout)
    x = bn_relu(x, (jnp.ones(64), jnp.zeros(64)), layout)
    window = (1, 1, 3, 3) if layout == 'NCHW' else (1, 3, 3, 1)
    strides = (1, 1, 2, 2) if layout == 'NCHW' else (1, 2, 2, 1)
    pads = ((0, 0), (0, 0), (1, 1), (1, 1)) if layout == 'NCHW' else \
        ((0, 0), (1, 1), (1, 1), (0, 0))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window, strides, pads)
    i = 1
    cin = 64
    for ch, count, stride in [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]:
        for j in range(count):
            blk = params[i]; i += 1
            s = stride if j == 0 else 1
            short = x
            if j == 0:
                short = bn_relu(conv(x, blk['proj'], s, layout), blk['bnp'],
                                layout, relu=False)
            y = bn_relu(conv(x, blk['c1'], s, layout), blk['bn1'], layout)
            y = bn_relu(conv(y, blk['c2'], 1, layout), blk['bn2'], layout)
            y = bn_relu(conv(y, blk['c3'], 1, layout), blk['bn3'], layout,
                        relu=False)
            x = jax.nn.relu(short + y)
    axes = (2, 3) if layout == 'NCHW' else (1, 2)
    x = x.mean(axes)
    logits = (x @ params[-1].astype(jnp.bfloat16)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -logp[jnp.arange(logits.shape[0]), labels].mean()


def flatten(p):
    leaves, treedef = jax.tree_util.tree_flatten(p)
    return leaves, treedef


def main():
    layout = sys.argv[1] if len(sys.argv) > 1 else 'NCHW'
    key = jax.random.PRNGKey(0)
    params = make_params(layout, key)

    @jax.jit
    def step(params, x, labels):
        loss, grads = jax.value_and_grad(
            lambda p: forward(p, x, labels, layout))(params)
        new = jax.tree_util.tree_map(lambda p, g: p - 0.01 * g, params, grads)
        return loss, new

    rng = np.random.RandomState(0)
    shape = (BATCH, 3, HW, HW) if layout == 'NCHW' else (BATCH, HW, HW, 3)
    x = jnp.asarray(rng.rand(*shape).astype('float32'))
    labels = jnp.asarray(rng.randint(0, CLASSES, BATCH))

    # the host fetch (float()) is the device sync
    loss, params = step(params, x, labels)
    float(loss)
    iters = 30
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, params = step(params, x, labels)
    float(loss)
    dt = time.perf_counter() - t0
    ips = BATCH * iters / dt
    print(layout, 'img/s:', round(ips, 1), ' mfu:',
          round(ips * 12.3e9 / 197e12, 4))


if __name__ == '__main__':
    main()
