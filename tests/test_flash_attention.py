"""Flash attention kernel (paddle_tpu/pallas/flash_attention.py):
numerics vs the naive contraction in interpreter mode (CPU CI), plus
the op/layer path through the executor."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.pallas.flash_attention import _flash, _naive


INTERPRET = jax.default_backend() != 'tpu'


@pytest.mark.parametrize('causal', [False, True])
def test_kernel_matches_naive(causal):
    rng = np.random.RandomState(0)
    BH, T, d = 3, 256, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    scale = d ** -0.5
    o_k = _flash(q, k, v, causal, scale, INTERPRET)
    o_n = _naive(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_n),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize('causal', [False, True])
def test_kernel_grads_match_naive(causal):
    rng = np.random.RandomState(1)
    BH, T, d = 2, 256, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    scale = d ** -0.5

    def loss_k(q, k, v):
        return jnp.sum(_flash(q, k, v, causal, scale, INTERPRET) ** 2)

    def loss_n(q, k, v):
        return jnp.sum(_naive(q, k, v, causal, scale) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_n, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip('qkv', gk, gn):
        scale_ref = float(jnp.abs(b).max()) + 1e-9
        rel = float(jnp.abs(a - b).max()) / scale_ref
        assert rel < 5e-2, 'd%s rel err %.3e' % (name, rel)


@pytest.mark.parametrize('causal', [False, True])
def test_kernel_grads_match_naive_asymmetric_blocks(causal):
    """The tuned-table shape: bk > bq (the round-5 autotune winner at
    T=8192 is (512, 1024)). Exercised at a CI-size T with the same
    bq < bk asymmetry and a q-block that spans multiple k-blocks."""
    from paddle_tpu import flags
    rng = np.random.RandomState(2)
    BH, T, d = 2, 512, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    scale = d ** -0.5
    flags.set_flags({'FLAGS_flash_block_q': 128,
                     'FLAGS_flash_block_k': 256})
    try:
        from paddle_tpu.pallas import flash_attention as fa
        fa._fwd.clear_cache()
        fa._bwd.clear_cache()

        def loss_k(q, k, v):
            return jnp.sum(_flash(q, k, v, causal, scale, INTERPRET) ** 2)

        def loss_n(q, k, v):
            return jnp.sum(_naive(q, k, v, causal, scale) ** 2)

        o_k = _flash(q, k, v, causal, scale, INTERPRET)
        np.testing.assert_allclose(
            np.asarray(o_k), np.asarray(_naive(q, k, v, causal, scale)),
            rtol=2e-2, atol=2e-2)
        gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        gn = jax.grad(loss_n, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip('qkv', gk, gn):
            scale_ref = float(jnp.abs(b).max()) + 1e-9
            rel = float(jnp.abs(a - b).max()) / scale_ref
            assert rel < 5e-2, 'd%s rel err %.3e' % (name, rel)
    finally:
        flags.set_flags({'FLAGS_flash_block_q': 0,
                         'FLAGS_flash_block_k': 0})
        from paddle_tpu.pallas import flash_attention as fa
        fa._fwd.clear_cache()
        fa._bwd.clear_cache()


def test_flash_attention_op_through_executor():
    fluid.set_flags({'pallas_interpret': True})
    try:
        rng = np.random.RandomState(2)
        B, H, T, d = 2, 2, 256, 128
        qv = rng.randn(B, H, T, d).astype('float32') * 0.3
        kv = rng.randn(B, H, T, d).astype('float32') * 0.3
        vv = rng.randn(B, H, T, d).astype('float32')

        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            q = fluid.layers.data(name='q', shape=[H, T, d],
                                  dtype='float32')
            k = fluid.layers.data(name='k', shape=[H, T, d],
                                  dtype='float32')
            v = fluid.layers.data(name='v', shape=[H, T, d],
                                  dtype='float32')
            out = fluid.layers.flash_attention(q, k, v, causal=True)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got, = exe.run(prog, feed={'q': qv, 'k': kv, 'v': vv},
                       fetch_list=[out])
        want = _naive(jnp.asarray(qv.reshape(B * H, T, d)),
                      jnp.asarray(kv.reshape(B * H, T, d)),
                      jnp.asarray(vv.reshape(B * H, T, d)),
                      True, d ** -0.5).reshape(B, H, T, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)
    finally:
        fluid.set_flags({'pallas_interpret': False})


def test_unsupported_shape_falls_back():
    # T=100 not lane-aligned: wrapper must fall back to naive, same
    # numbers, no error
    from paddle_tpu.pallas.flash_attention import flash_attention
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 100, 64).astype('float32'))
    k = jnp.asarray(rng.randn(2, 100, 64).astype('float32'))
    v = jnp.asarray(rng.randn(2, 100, 64).astype('float32'))
    out = flash_attention(q, k, v, causal=True)
    want = _naive(q, k, v, True, 64 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_transformer_model_flash_config_trains():
    from paddle_tpu.models.transformer import TransformerConfig, \
        train_network
    fluid.set_flags({'pallas_interpret': True})
    try:
        cfg = TransformerConfig(vocab=64, dim=128, heads=1, layers=1,
                                ffn=128, max_len=128, use_tp=False,
                                use_sp=False, flash_attention=True)
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            tokens = fluid.layers.data(name='tokens', shape=[128, 1],
                                       dtype='int64')
            labels = fluid.layers.data(name='labels', shape=[128, 1],
                                       dtype='int64')
            _probs, loss = train_network(tokens, labels, cfg)
            fluid.optimizer.Adam(1e-3).minimize(loss)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 64, (2, 128, 1)).astype('int64')
        labs = rng.randint(0, 64, (2, 128, 1)).astype('int64')
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            first = None
            for i in range(12):
                l, = exe.run(prog, feed={'tokens': ids, 'labels': labs},
                             fetch_list=[loss])
                if first is None:
                    first = float(np.asarray(l))
            assert float(np.asarray(l)) < first
    finally:
        fluid.set_flags({'pallas_interpret': False})


@pytest.mark.parametrize('arm,T,bq,bk',
                         [('split', 896, 128, 128),
                          ('split', 897, 128, 128),
                          ('onepass', 640, 128, 128),
                          ('onepass', 641, 128, 128),
                          ('kvmajor', 768, 128, 128),
                          ('kvmajor', 769, 128, 128),
                          # the tuned-table shape class (bk > bq, cf.
                          # _BLOCK_TABLE's (512, 1024)): pins kvmajor's
                          # causal qmap clamp + first_qi arithmetic
                          ('kvmajor', 1024, 128, 256)])
@pytest.mark.parametrize('causal', [False, True])
def test_alt_backward_arms_grads_match_naive(causal, arm, T, bq, bk):
    """The kv-major backward is the measured-default arm (covered by
    every other grad test); split and one-pass stay available via
    PADDLE_FLASH_BWD (split is also the automatic fallback when the
    kv-major dq accumulator would not fit) — force each via the
    _FORCE_ARM hook so all arms keep grad parity coverage. A UNIQUE T
    per arm is used because _bwd's jit cache keys on shapes+static
    args, not on the hook/flag state at trace time (the odd-T cases
    fall back to the naive path end to end, pinning that the hook does
    not break unsupported shapes)."""
    import paddle_tpu as fluid
    from paddle_tpu.pallas import flash_attention as fa
    from paddle_tpu.pallas.flash_attention import flash_attention
    rng = np.random.RandomState(2)
    BH, d = 2, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    scale = d ** -0.5
    fluid.set_flags({'flash_block_q': bq, 'flash_block_k': bk,
                     'pallas_interpret': INTERPRET})
    fa._FORCE_ARM = arm
    try:
        def loss_k(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal, scale) ** 2)

        def loss_n(q, k, v):
            return jnp.sum(_naive(q, k, v, causal, scale) ** 2)

        gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        gn = jax.grad(loss_n, argnums=(0, 1, 2))(q, k, v)
    finally:
        fa._FORCE_ARM = ''
        fluid.set_flags({'flash_block_q': 0, 'flash_block_k': 0,
                         'pallas_interpret': False})
    for name, a, b in zip('qkv', gk, gn):
        scale_ref = float(jnp.abs(b).max()) + 1e-9
        rel = float(jnp.abs(a - b).max()) / scale_ref
        assert rel < 5e-2, 'd%s rel err %.3e' % (name, rel)


# --- forward arms (online vs stored-lse twopass) --------------------

def _force_fwd_arm(fa, arm):
    """Force a forward arm AND drop stale traces: the arm binds at
    trace time, and _fwd's jit cache keys on shapes+static args, not
    on the hook state."""
    fa._FORCE_FWD_ARM = arm
    fa._fwd.clear_cache()


@pytest.mark.parametrize('arm', ['online', 'twopass'])
@pytest.mark.parametrize('causal', [False, True])
def test_fwd_arms_output_and_lse_match_naive(causal, arm):
    """Both forward arms must honor the exact (o, lse) contract: o vs
    the naive contraction, lse vs a directly-computed logsumexp of the
    masked scores (the backward arms and ring attention's global-lse
    merge both consume lse, so output parity alone is not enough)."""
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(4)
    BH, T, d = 2, 256, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    scale = d ** -0.5
    _force_fwd_arm(fa, arm)
    try:
        o, lse = fa._fwd(q, k, v, causal, scale, INTERPRET)
        assert fa._RESOLVED_FWD_ARM == arm
    finally:
        _force_fwd_arm(fa, '')
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(_naive(q, k, v, causal, scale)),
        rtol=2e-2, atol=2e-2)
    s = jnp.einsum('bqd,bkd->bqk', q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None], s, -jnp.inf)
    want_lse = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse[..., 0]),
                               np.asarray(want_lse),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize('causal', [False, True])
def test_fwd_arms_agree_bitwise_on_lse(causal):
    """lse is a pure function of (q, k, mask); both arms compute it
    with the same running-max recurrence, so it must agree to fp32
    rounding — an lse drift here would silently skew every backward."""
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(5)
    BH, T, d = 2, 256, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    out = {}
    try:
        for arm in ('online', 'twopass'):
            _force_fwd_arm(fa, arm)
            out[arm] = fa._fwd(q, k, v, causal, d ** -0.5, INTERPRET)
    finally:
        _force_fwd_arm(fa, '')
    np.testing.assert_allclose(np.asarray(out['online'][1]),
                               np.asarray(out['twopass'][1]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out['online'][0]),
                               np.asarray(out['twopass'][0]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize('bwd_arm', ['split', 'onepass', 'kvmajor'])
@pytest.mark.parametrize('fwd_arm', ['online', 'twopass'])
@pytest.mark.parametrize('causal', [False, True])
def test_fwd_bwd_arm_matrix_grads_match_naive(causal, fwd_arm,
                                              bwd_arm):
    """Full 2 fwd x 3 bwd arm matrix: every backward consumes (o, lse)
    from either forward unchanged. Blocks forced to (64, 128) so the
    bk > bq tuned-table shape class (kvmajor lesson) and causal
    diagonal-straddling q-blocks are both in play at CI size."""
    import paddle_tpu as fluid
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(6)
    BH, T, d = 2, 256, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    scale = d ** -0.5
    fluid.set_flags({'flash_block_q': 64, 'flash_block_k': 128})
    fa._FORCE_ARM = bwd_arm
    _force_fwd_arm(fa, fwd_arm)
    fa._bwd.clear_cache()
    try:
        def loss_k(q, k, v):
            return jnp.sum(_flash(q, k, v, causal, scale,
                                  INTERPRET) ** 2)

        def loss_n(q, k, v):
            return jnp.sum(_naive(q, k, v, causal, scale) ** 2)

        gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        assert fa._RESOLVED_FWD_ARM == fwd_arm
        assert fa._RESOLVED_ARM == bwd_arm
        gn = jax.grad(loss_n, argnums=(0, 1, 2))(q, k, v)
    finally:
        fa._FORCE_ARM = ''
        _force_fwd_arm(fa, '')
        fa._bwd.clear_cache()
        fluid.set_flags({'flash_block_q': 0, 'flash_block_k': 0})
    for name, a, b in zip('qkv', gk, gn):
        scale_ref = float(jnp.abs(b).max()) + 1e-9
        rel = float(jnp.abs(a - b).max()) / scale_ref
        assert rel < 5e-2, 'd%s rel err %.3e' % (name, rel)


def test_twopass_vmem_guard_falls_back_to_online():
    """A forced twopass whose residency estimate exceeds the ceiling
    must silently dispatch online — introspectable via
    _RESOLVED_FWD_ARM (the A/B tools cross-check exactly this), with
    the numbers still correct."""
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(7)
    BH, T, d = 2, 256, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    saved = fa._TWOPASS_VMEM_CEILING
    fa._TWOPASS_VMEM_CEILING = 1   # every estimate exceeds this
    _force_fwd_arm(fa, 'twopass')
    try:
        o, lse = fa._fwd(q, k, v, True, d ** -0.5, INTERPRET)
        assert fa._RESOLVED_FWD_ARM == 'online'
    finally:
        fa._TWOPASS_VMEM_CEILING = saved
        _force_fwd_arm(fa, '')
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(_naive(q, k, v, True, d ** -0.5)),
        rtol=2e-2, atol=2e-2)


def test_twopass_vmem_estimate_sane():
    """The residency estimate must include the 6 MB Mosaic stack
    margin (the round-5 OOM lesson) and grow with the block sizes."""
    from paddle_tpu.pallas import flash_attention as fa
    small = fa._twopass_vmem_bytes(8192, 128, 256, 256, 2)
    big = fa._twopass_vmem_bytes(8192, 128, 1024, 1024, 2)
    assert small > 6 * 1024 * 1024
    assert big > small
    assert big <= fa._TWOPASS_VMEM_CEILING   # tuned sizes stay legal


def test_unknown_fwd_arm_env_raises_at_import():
    """Loud-config hygiene: a typo'd PADDLE_FLASH_FWD must fail the
    import, not silently benchmark the default arm (mirrors
    PADDLE_FLASH_BWD). A valid value must bind the forcing hook."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               PADDLE_FLASH_FWD='twopas')
    r = subprocess.run(
        [sys.executable, '-c',
         'import paddle_tpu.pallas.flash_attention'],
        capture_output=True, text=True, env=env)
    assert r.returncode != 0
    assert 'PADDLE_FLASH_FWD' in (r.stderr or '')
    env['PADDLE_FLASH_FWD'] = 'twopass'
    r = subprocess.run(
        [sys.executable, '-c',
         'from paddle_tpu.pallas import flash_attention as fa; '
         'assert fa._FORCE_FWD_ARM == "twopass"'],
        capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize('causal', [False, True])
def test_twopass_block_table_is_per_arm(causal):
    """The lane-parallel bk sweep tunes the twopass arm separately:
    an entry in _BLOCK_TABLE_FWD_TWOPASS must bind ONLY the twopass
    dispatch (online keeps _BLOCK_TABLE_FWD), and the twopass kernels
    must stay correct under the re-tabled (bk > bq) blocks."""
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(9)
    BH, T, d = 2, 256, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    fa._BLOCK_TABLE_FWD_TWOPASS[(T, d)] = (64, 256)
    try:
        assert fa._block_sizes(T, d, fwd=True, arm='twopass') \
            == (64, 256)
        assert fa._block_sizes(T, d, fwd=True, arm='online') \
            != (64, 256)
        _force_fwd_arm(fa, 'twopass')
        o, _lse = fa._fwd(q, k, v, causal, d ** -0.5, INTERPRET)
        assert fa._RESOLVED_FWD_ARM == 'twopass'
    finally:
        del fa._BLOCK_TABLE_FWD_TWOPASS[(T, d)]
        _force_fwd_arm(fa, '')
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(_naive(q, k, v, causal, d ** -0.5)),
        rtol=2e-2, atol=2e-2)


def test_flash_fwd_arms_quick_smoke():
    """tools/flash_fwd_arms.py --quick is the tier-1 wiring for the
    A/B harness: forcing, cache-clearing, resolved-arm cross-check and
    ranking all run end to end on the interpret backend."""
    import os
    import sys
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'tools')
    sys.path.insert(0, tools)
    try:
        import flash_fwd_arms
        flash_fwd_arms.main(['--quick'])
    finally:
        sys.path.remove(tools)


@pytest.mark.parametrize('causal', [False, True])
def test_per_direction_block_tables_independent(causal):
    """The fwd and bwd kernels share only (o, lse), which are
    block-size independent — so each direction keeps its own tuned
    table (_BLOCK_TABLE_FWD vs _BLOCK_TABLE; at T=8192 they differ in
    production). Pin the mixed-table contract at a CI size by forcing
    DIFFERENT fwd/bwd blocks through the tables (the flag override
    path binds both directions, so it cannot cover this)."""
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(3)
    BH, T, d = 2, 384, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    scale = d ** -0.5
    fa._BLOCK_TABLE_FWD[(T, d)] = (384, 192)
    fa._BLOCK_TABLE[(T, d)] = (128, 384)
    fa._fwd.clear_cache()
    fa._bwd.clear_cache()
    try:
        def loss_k(q, k, v):
            return jnp.sum(_flash(q, k, v, causal, scale,
                                  INTERPRET) ** 2)

        def loss_n(q, k, v):
            return jnp.sum(_naive(q, k, v, causal, scale) ** 2)

        o_k = _flash(q, k, v, causal, scale, INTERPRET)
        np.testing.assert_allclose(
            np.asarray(o_k), np.asarray(_naive(q, k, v, causal, scale)),
            rtol=2e-2, atol=2e-2)
        gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        gn = jax.grad(loss_n, argnums=(0, 1, 2))(q, k, v)
    finally:
        del fa._BLOCK_TABLE_FWD[(T, d)]
        del fa._BLOCK_TABLE[(T, d)]
        fa._fwd.clear_cache()
        fa._bwd.clear_cache()
    for name, a, b in zip('qkv', gk, gn):
        scale_ref = float(jnp.abs(b).max()) + 1e-9
        rel = float(jnp.abs(a - b).max()) / scale_ref
        assert rel < 5e-2, 'd%s rel err %.3e' % (name, rel)


# --- the schedule a shape gets (PR 37) -------------------------------

@pytest.fixture
def flash_counters():
    from paddle_tpu.obs import telemetry
    was = telemetry.enabled()
    telemetry.enable()

    def read(direction='fwd'):
        prefix = 'pallas.flash.%s.' % direction
        return {k[len(prefix):]: n
                for k, n in telemetry.snapshot()['counters'].items()
                if k.startswith(prefix)}
    yield read
    if not was:
        telemetry.disable()


def _trace_fwd(fa, T, d=128, dtype=jnp.bfloat16):
    """Trace _fwd at [2, T, d] without running it: the arm and blocks
    bind while tracing."""
    x = jax.ShapeDtypeStruct((2, T, d), dtype)
    fa._fwd.clear_cache()
    fa._fwd.lower(x, x, x, True, d ** -0.5, True)
    return fa._RESOLVED_FWD_ARM, fa._RESOLVED_FWD_BLOCKS


def test_training_cell_shape_gets_its_table_entry():
    """(2048, 128) is the shape both training cells run: the online
    sweep at the blocks the chip A/B of PR 37 ranked first for the
    forward, and for the backward the whole head in one block (PR 41);
    (8192, 128) and an unlisted T are what they were."""
    from paddle_tpu.pallas import flash_attention as fa
    assert fa._BLOCK_TABLE_FWD[(2048, 128)] == (1024, 1024)
    assert fa._block_sizes(2048, 128, fwd=True) == (1024, 1024)
    assert fa._block_sizes(2048, 128) == (2048, 2048)
    assert fa._block_sizes(8192, 128, fwd=True) == (1024, 1024)
    assert fa._block_sizes(8192, 128) == (512, 1024)
    assert fa._block_sizes(4096, 128, fwd=True) == (512, 512)
    assert fa._block_sizes(4096, 128) == (512, 512)
    assert fa._block_sizes(384, 128, fwd=True) == (384, 384)
    try:
        assert _trace_fwd(fa, 2048) == ('online', (1024, 1024))
        assert _trace_fwd(fa, 4096) == ('online', (512, 512))
    finally:
        fa._fwd.clear_cache()


@pytest.mark.parametrize('arm', ['online', 'twopass'])
def test_fwd_schedule_counter_counts_one_a_trace(arm, flash_counters):
    """`pallas.flash.fwd.<schedule>` says which forward a run compiled:
    one increment a trace of _fwd, none for a call its cache answers,
    and the blocks of that trace beside the resolved arm."""
    from paddle_tpu.pallas import flash_attention as fa
    other = {'online': 'twopass', 'twopass': 'online'}[arm]
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(2, 256, 128).astype('float32')) * 0.3
    _force_fwd_arm(fa, arm)
    try:
        before = flash_counters()
        fa._fwd(q, q, q, True, 128 ** -0.5, INTERPRET)
        fa._fwd(q, q, q, True, 128 ** -0.5, INTERPRET)
        after = flash_counters()
        assert fa._RESOLVED_FWD_ARM == arm
        assert fa._RESOLVED_FWD_BLOCKS == (256, 256)
    finally:
        _force_fwd_arm(fa, '')
    assert after[arm] - before[arm] == 1
    assert after[other] == before[other]


@pytest.mark.parametrize('bq,bk,chunk', [(256, 256, 128),    # the winner's
                                         (128, 256, 128),    # bq != bk
                                         (256, 128, 512)])   # no chunks
@pytest.mark.parametrize('causal', [False, True])
def test_chunked_online_schedule_matches_naive(causal, bq, bk, chunk,
                                               monkeypatch):
    """The structure (2048, 128) runs -- (1024, 1024) blocks walked in
    chunks of 512 keys: two q blocks, two K blocks of two chunks each,
    pairs that straddle the diagonal, lse leaving as rows -- at a CPU
    size: (o, lse) and the gradients against the naive contraction."""
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(12)
    BH, T, d = 2, 512, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    scale = d ** -0.5
    monkeypatch.setattr(fa, '_FWD_CHUNK_K', chunk)
    monkeypatch.setitem(fa._BLOCK_TABLE_FWD, (T, d), (bq, bk))
    fa._fwd.clear_cache()
    try:
        o, lse = fa._fwd(q, k, v, causal, scale, INTERPRET)
        assert (fa._RESOLVED_FWD_ARM, fa._RESOLVED_FWD_BLOCKS) \
            == ('online', (bq, bk))

        def loss_k(q, k, v):
            return jnp.sum(_flash(q, k, v, causal, scale, INTERPRET) ** 2)

        def loss_n(q, k, v):
            return jnp.sum(_naive(q, k, v, causal, scale) ** 2)

        gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        gn = jax.grad(loss_n, argnums=(0, 1, 2))(q, k, v)
    finally:
        fa._fwd.clear_cache()
    assert lse.shape == (BH, T, 1) and lse.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(_naive(q, k, v, causal, scale)),
        rtol=2e-2, atol=2e-2)
    s = jnp.einsum('bqd,bkd->bqk', q, k) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    np.testing.assert_allclose(
        np.asarray(lse[..., 0]),
        np.asarray(jax.scipy.special.logsumexp(s, axis=-1)),
        rtol=1e-4, atol=1e-4)
    for name, a, b in zip('qkv', gk, gn):
        scale_ref = float(jnp.abs(b).max()) + 1e-9
        rel = float(jnp.abs(a - b).max()) / scale_ref
        assert rel < 5e-2, 'd%s rel err %.3e' % (name, rel)


def test_online_forward_in_bf16_keeps_the_contract():
    """bf16 in, as the cells run it: o in the input dtype, lse float32
    [BH, T, 1] and exact to float32 rounding of the bf16 products."""
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(13)
    BH, T, d = 2, 256, 128
    q, k, v = (jnp.asarray(rng.randn(BH, T, d), jnp.bfloat16)
               for _ in range(3))
    scale = d ** -0.5
    fa._fwd.clear_cache()
    o, lse = fa._fwd(q, k, v, True, scale, INTERPRET)
    assert o.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    assert o.shape == (BH, T, d) and lse.shape == (BH, T, 1)
    s = jnp.einsum('bqd,bkd->bqk', q * jnp.asarray(scale, q.dtype), k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    np.testing.assert_allclose(
        np.asarray(lse[..., 0]),
        np.asarray(jax.scipy.special.logsumexp(s, axis=-1)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(o, np.float32),
        np.asarray(_naive(q, k, v, True, scale), np.float32),
        rtol=3e-2, atol=3e-2)


def test_flash_autotune_quick_smoke():
    """tools/flash_autotune.py --quick walks the block sweep (forcing by
    flag, cache clearing, ranking, the peak share from the benchmark's
    count) on the interpret backend."""
    import os
    import sys
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'tools')
    sys.path.insert(0, tools)
    try:
        import flash_autotune
        flash_autotune.main(['--quick'])
        # 4 x 16 heads of T=2048, d=128 at 0.349 ms are the whole peak
        assert abs(flash_autotune.peak_share(0.349, 64, 2048, 128)
                   - 100.0) < 0.5
        assert abs(flash_autotune.peak_share(3 * 0.349, 64, 2048, 128,
                                             fwd_only=False)
                   - 100.0) < 0.5
    finally:
        sys.path.remove(tools)


# --- the backward's rows, orientation and chunk walk (PR 41) ---------

def _bwd_blocks(fa, monkeypatch, T, d, bq, bk, chunk):
    monkeypatch.setattr(fa, '_BWD_CHUNK', chunk)
    monkeypatch.setitem(fa._BLOCK_TABLE, (T, d), (bq, bk))
    fa._bwd.clear_cache()


@pytest.mark.parametrize('bq,bk,chunk', [
    (512, 512, 128),    # the cells' structure: the whole sequence one
                        # block, its 4 x 4 chunk pairs fixed when tracing
    (256, 512, 128),    # bq != bk, chunks walked under predicates
    (512, 256, 256),    # bq != bk the other way, one row of chunks
    (128, 256, 512)])   # no chunk walk: the block is the pair
@pytest.mark.parametrize('causal', [False, True])
def test_backward_body_matches_naive(causal, bq, bk, chunk, monkeypatch):
    """The transposed pair function under the kv-major grid, chunk walk
    on and off: (dq, dk, dv) against the naive contraction's."""
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(21)
    BH, T, d = 2, 512, 128
    q = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    k = jnp.asarray(rng.randn(BH, T, d).astype('float32')) * 0.3
    v = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    scale = d ** -0.5
    _bwd_blocks(fa, monkeypatch, T, d, bq, bk, chunk)
    try:
        gk = jax.grad(lambda *a: jnp.sum(
            _flash(*a, causal, scale, INTERPRET) ** 2), (0, 1, 2))(q, k, v)
        assert (fa._RESOLVED_ARM, fa._RESOLVED_BWD_BLOCKS) \
            == ('kvmajor', (bq, bk))
    finally:
        fa._bwd.clear_cache()
    gn = jax.grad(lambda *a: jnp.sum(
        _naive(*a, causal, scale) ** 2), (0, 1, 2))(q, k, v)
    for name, a, b in zip('qkv', gk, gn):
        rel = float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-9)
        assert rel < 2e-2, 'd%s rel err %.3e' % (name, rel)


@pytest.mark.parametrize('arm', ['kvmajor', 'split', 'onepass'])
def test_backward_takes_a_global_lse_larger_than_the_blocks_own(
        arm, monkeypatch):
    """The ring's case: the second half of a causal sequence's queries
    against the earlier keys (no mask) and its own (causal), each call
    handed the lse over ALL keys, so exp(s - lse) sums to less than one
    a block; the two calls' dq add up to the whole's and each block's
    dk, dv are its own."""
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(22)
    BH, T, d = 2, 256, 128
    q, k, v = (jnp.asarray(rng.randn(BH, 2 * T, d).astype('float32')) * s
               for s in (0.3, 0.3, 1.0))
    do = jnp.asarray(rng.randn(BH, T, d).astype('float32'))
    scale = d ** -0.5

    def late_rows(q, k, v):
        return _naive(q, k, v, True, scale)[:, T:]
    o, vjp = jax.vjp(late_rows, q, k, v)
    dq_n, dk_n, dv_n = vjp(do)
    s = jnp.einsum('bqd,bkd->bqk', q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((2 * T, 2 * T), bool))[None], s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)[:, T:].reshape(BH, 1, T)
    _, own = fa._fwd(q[:, T:], k[:, T:], v[:, T:], True, scale, INTERPRET,
                     lse_rows=True)
    assert float((lse - own).min()) > 0        # larger on every row

    monkeypatch.setattr(fa, '_FORCE_ARM', arm)
    _bwd_blocks(fa, monkeypatch, T, d, 128, 256, 128)
    try:
        early = fa._bwd(q[:, T:], k[:, :T], v[:, :T], o, lse, do, False,
                        scale, INTERPRET)
        home = fa._bwd(q[:, T:], k[:, T:], v[:, T:], o, lse, do, True,
                       scale, INTERPRET)
        assert fa._RESOLVED_ARM == arm
    finally:
        fa._bwd.clear_cache()
    for name, got, want in (
            ('dq', early[0] + home[0], dq_n[:, T:]),
            ('dk early', early[1], dk_n[:, :T]), ('dk', home[1], dk_n[:, T:]),
            ('dv early', early[2], dv_n[:, :T]), ('dv', home[2], dv_n[:, T:])):
        rel = float(jnp.abs(got - want).max()) \
            / (float(jnp.abs(want).max()) + 1e-9)
        assert rel < 2e-2, '%s rel err %.3e' % (name, rel)


def test_backward_in_bf16_keeps_the_contract(monkeypatch):
    """bf16 in, as the cells run it: gradients in the input dtype, and
    no further from a float32 reference than the arithmetic of the
    kernel this one replaced -- q scaled in bf16, float32 scores and
    accumulation, p and ds cast to bf16 before their products -- which
    it keeps, transposed."""
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(23)
    BH, T, d = 2, 512, 128
    q, k, v, do = (jnp.asarray(rng.randn(BH, T, d), jnp.bfloat16)
                   for _ in range(4))
    scale = d ** -0.5
    f32 = jnp.float32
    _, vjp = jax.vjp(lambda *a: _naive(*a, True, scale),
                     q.astype(f32), k.astype(f32), v.astype(f32))
    want = vjp(do.astype(f32))

    _bwd_blocks(fa, monkeypatch, T, d, 512, 512, 256)
    try:
        o, lse = fa._fwd(q, k, v, True, scale, INTERPRET, lse_rows=True)
        got = fa._bwd(q, k, v, o, lse, do, True, scale, INTERPRET)
    finally:
        fa._bwd.clear_cache()

    def dot(eq, a, b):
        return jnp.einsum(eq, a, b, preferred_element_type=f32)
    qs = q * jnp.asarray(scale, q.dtype)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None],
                  dot('bqd,bkd->bqk', qs, k), -1e30)
    p = jnp.exp(s - lse.reshape(BH, T, 1))
    delta = jnp.sum(do.astype(f32) * o.astype(f32), -1, keepdims=True)
    ds = (p * (dot('bqd,bkd->bqk', do, v) - delta)).astype(q.dtype)
    old = ((dot('bqk,bkd->bqd', ds, k) * scale).astype(q.dtype),
           dot('bqk,bqd->bkd', ds, qs).astype(k.dtype),
           dot('bqk,bqd->bkd', p.astype(do.dtype), do).astype(v.dtype))

    for name, g, o_, w in zip(('dq', 'dk', 'dv'), got, old, want):
        assert g.dtype == jnp.bfloat16 and g.shape == (BH, T, d)
        ref = float(jnp.abs(w).max())
        err = float(jnp.abs(g.astype(f32) - w).max()) / ref
        err_old = float(jnp.abs(o_.astype(f32) - w).max()) / ref
        assert err <= 1.05 * err_old + 1e-4, (name, err, err_old)
        assert err < 2e-2, (name, err)


@pytest.mark.parametrize('arm', ['kvmajor', 'split', 'onepass'])
def test_bwd_schedule_counter_counts_one_a_trace(arm, monkeypatch,
                                                 flash_counters):
    """`pallas.flash.bwd.<arm>` says which backward a run compiled: one
    increment a trace of _bwd, none for a call its cache answers, and
    the blocks of that trace beside the resolved arm."""
    from paddle_tpu.pallas import flash_attention as fa
    rng = np.random.RandomState(24)
    q = jnp.asarray(rng.randn(2, 256, 128).astype('float32')) * 0.3
    monkeypatch.setattr(fa, '_FORCE_ARM', arm)
    fa._bwd.clear_cache()
    try:
        o, lse = fa._fwd(q, q, q, True, 128 ** -0.5, INTERPRET,
                         lse_rows=True)
        before = flash_counters('bwd')
        fa._bwd(q, q, q, o, lse, q, True, 128 ** -0.5, INTERPRET)
        fa._bwd(q, q, q, o, lse, q, True, 128 ** -0.5, INTERPRET)
        after = flash_counters('bwd')
        assert fa._RESOLVED_ARM == arm
        assert fa._RESOLVED_BWD_BLOCKS == (256, 256)
    finally:
        fa._bwd.clear_cache()
    assert {a: after[a] - before[a] for a in after} \
        == {a: int(a == arm) for a in fa._BWD_SCHEDULE}


def _stat_blocks(fa, T, bq, bk, monkeypatch):
    """(array shape, block shape) of lse and delta as the kv-major
    pallas_call of a traced _bwd at [2, T, 128] fetches them."""
    _bwd_blocks(fa, monkeypatch, T, 128, bq, bk, 512)
    x = jax.ShapeDtypeStruct((2, T, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((2, 1, T), jnp.float32)
    try:
        jaxpr = jax.make_jaxpr(lambda *a: fa._bwd(
            *a, True, 128 ** -0.5, True))(x, x, x, x, lse, x)
    finally:
        fa._bwd.clear_cache()
    calls = [e for e in jaxpr.jaxpr.eqns[-1].params['jaxpr'].eqns
             if e.primitive.name == 'pallas_call']
    assert len(calls) == 1
    return [(tuple(m.array_aval.shape),
             tuple(int(getattr(b, 'block_size', b)) for b in m.block_shape))
            for m in calls[0].params['grid_mapping'].block_mappings[4:6]]


@pytest.mark.parametrize('T,bq,bk', [(2048, 2048, 2048), (512, 128, 256)])
def test_lse_and_delta_stay_rows_from_forward_to_backward(T, bq, bk,
                                                          monkeypatch):
    """Where the q block tiles 128 lanes: the residual _flash_fwd keeps
    is the [BH, 1, T] the online kernel writes, and _bwd fetches it and
    delta as (1, 1, bq) blocks -- no [BH, T, 1] column anywhere."""
    from paddle_tpu.pallas import flash_attention as fa
    x = jax.ShapeDtypeStruct((2, T, 128), jnp.bfloat16)
    _, res = jax.eval_shape(
        lambda *a: fa._flash_fwd(*a, True, 128 ** -0.5, True), x, x, x)
    assert res[4].shape == (2, 1, T) and res[4].dtype == jnp.float32
    assert _stat_blocks(fa, T, bq, bk, monkeypatch) \
        == [((2, 1, T), (1, 1, bq))] * 2
