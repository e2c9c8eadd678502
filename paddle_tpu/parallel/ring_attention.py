"""Ring attention: context parallelism for long sequences.

No reference analog — the reference caps sequence length at what one
GPU's memory holds (its Transformer configs top out at T=256,
ref:benchmark/fluid/models/transformer.py). This is the TPU-native
long-context mechanism the SURVEY's scale goals require: the sequence
axis is sharded over the 'sp' mesh axis, every device keeps only its
own Q/K/V blocks, and K/V blocks rotate around the ring via
`lax.ppermute` over ICI while each device folds one block per step into
an online-softmax accumulator (the flash-attention recurrence, applied
ring-step-wise). Peak per-device score memory drops from O(T²) to
O(T²/n²) and K/V memory to O(T/n) — sequence length scales linearly
with ring size at constant memory — while the ppermute traffic
overlaps compute on the ICI torus.

Causality is handled at block granularity: a key block strictly ahead
of the query block contributes nothing (its scores are fully masked,
and the online-softmax max is guarded so all-masked steps are exact
no-ops, not NaNs); the diagonal block gets the elementwise triangular
mask.

`ring_attention(...)` is the inside-shard_map recurrence;
`ring_attention_global(...)` wraps it in `shard_map` over the current
mesh so op emitters (ops/attention_ops.py 'ring_attention') can call it
on GSPMD-global arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ['ring_attention', 'ring_attention_global',
           'ring_flash_attention', 'ring_flash_attention_global']

_NEG_INF = -1e30


def ring_attention(q, k, v, axis_name='sp', causal=True, sm_scale=None):
    """Inside-shard_map ring attention.

    q, k, v: [B, H, Tl, dh] — this device's sequence block (Tl = T/n).
    Returns [B, H, Tl, dh], exactly softmax(QK^T·scale [+mask]) V over
    the FULL sequence.
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, H, Tl, dh = q.shape
    scale = sm_scale if sm_scale is not None else dh ** -0.5
    # keep operands in their own dtype (bf16 under AMP runs the MXU at
    # full rate); accumulate in fp32 via preferred_element_type
    qs = q * jnp.asarray(scale, q.dtype)

    q_pos = my * Tl + jnp.arange(Tl)                 # global query rows

    # remat: without it, scan saves every step's [Tl, Tl] probability
    # block for backward — O(Tl·T) residents, re-creating the memory
    # wall ring attention exists to remove. Recomputing the fold in the
    # backward pass keeps residuals at O(Tl·dh) per step (the standard
    # flash/ring backward trade).
    @jax.checkpoint
    def fold(acc, kb, vb, src):
        """One online-softmax update of acc=(o, m, l) with block src."""
        o, m, l = acc
        s = jnp.einsum('bhqd,bhkd->bhqk', qs, kb,
                       preferred_element_type=jnp.float32)  # [B,H,Tl,Tl]
        if causal:
            k_pos = src * Tl + jnp.arange(Tl)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        blk_max = jnp.max(s, axis=-1)                # [B,H,Tl]
        m_new = jnp.maximum(m, blk_max)
        # all-masked step: m_new stays _NEG_INF; freeze it so the
        # correction exp(m - m_new) is exp(0), an exact no-op
        safe_m = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        corr = jnp.exp(jnp.where(m <= _NEG_INF / 2, safe_m, m) - safe_m)
        p = jnp.exp(s - safe_m[..., None])
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            'bhqk,bhkd->bhqd', p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    perm = [(j, (j - 1) % n) for j in range(n)]

    def step(carry, i):
        o, m, l, kb, vb = carry
        # rotate FIRST (blocks arrive from the next ring neighbour), so
        # the scan runs n-1 rotations instead of n — the local block is
        # folded in before the scan and a final rotation would be
        # discarded (XLA cannot DCE a collective inside scan)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        o, m, l = fold((o, m, l), kb, vb, (my + i) % n)
        return (o, m, l, kb, vb), None

    # derive initial carries FROM q so they inherit its varying-manual-
    # axes type: newer shard_map rejects scan carries whose input is a
    # plain constant but whose output varies over mesh axes
    zq = qs.astype(jnp.float32) * 0.0
    acc0 = fold((zq, zq[..., 0] + _NEG_INF, zq[..., 0]), k, v, my)
    (o, m, l, _, _), _ = jax.lax.scan(
        step, acc0 + (k, v), jnp.arange(1, n))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _ring_spec(mesh, q, seq_axis, batch_axis, head_axis):
    """PartitionSpec for the [B, H, T, dh] operands, mapping each mesh
    axis only when it exists, is >1, and divides the dim (shard_map
    hard-errors on non-divisible dims where GSPMD would pad). Returns
    (spec, seq_ok)."""
    def axis(name, dim):
        if name and mesh is not None and name in mesh.axis_names \
                and mesh.shape[name] > 1 and dim % mesh.shape[name] == 0:
            return name
        return None
    seq_ok = axis(seq_axis, q.shape[2]) is not None
    spec = P(axis(batch_axis, q.shape[0]), axis(head_axis, q.shape[1]),
             seq_axis if seq_ok else None, None)
    return spec, seq_ok


def ring_attention_global(q, k, v, mesh, causal=True, sm_scale=None,
                          seq_axis='sp', batch_axis='dp',
                          head_axis='tp'):
    """GSPMD-global entry: q/k/v are [B, H, T, dh] global arrays; the
    sequence axis is sharded over `seq_axis`, batch over `batch_axis`,
    heads over `head_axis` (each only if present in the mesh).
    mesh=None (no mesh in scope) lowers to plain fused attention; so do
    meshes whose sp size does not divide T (shard_map cannot pad the way
    GSPMD constraints can)."""
    spec, seq_ok = _ring_spec(mesh, q, seq_axis, batch_axis, head_axis)
    if mesh is None or not seq_ok:
        # no ring: plain attention, operand dtype preserved (bf16 under
        # AMP runs the MXU at full rate), fp32 accumulation
        scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
        s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            T = q.shape[2]
            mask = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(mask[None, None], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum('bhqk,bhkd->bhqd', p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)
    fn = functools.partial(ring_attention, axis_name=seq_axis,
                           causal=causal, sm_scale=sm_scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


# ---------------------------------------------------------------------------
# ring x flash composition: the multi-chip long-context path.
# ---------------------------------------------------------------------------

def _kernel_enabled():
    """Real kernel on TPU; interpreter mode only when the
    pallas_interpret flag opts in (CPU tests) — same gate as the
    single-chip flash_attention wrapper."""
    from ..flags import get_flag
    return jax.default_backend() == 'tpu' or bool(
        get_flag('pallas_interpret'))


def _flash_block(q, kb, vb, causal, sm_scale):
    """Run the Pallas flash kernel over one KV block, returning the
    attention PARTIAL (o, lse) for later merging. q/kb/vb: [B,H,Tl,dh]."""
    from ..pallas.flash_attention import _fwd, _supported
    B, H, Tl, dh = q.shape
    qf = q.reshape(B * H, Tl, dh)
    kf = kb.reshape(B * H, Tl, dh)
    vf = vb.reshape(B * H, Tl, dh)
    scale = sm_scale if sm_scale is not None else dh ** -0.5
    if _supported(Tl, dh) and _kernel_enabled():
        o, lse = _fwd(qf, kf, vf, causal, scale,
                      jax.default_backend() != 'tpu')
        lse = lse[..., 0]
    else:
        # small/unaligned blocks: same partial computed with XLA ops
        s = jnp.einsum('btd,bsd->bts', qf * jnp.asarray(scale, qf.dtype),
                       kf, preferred_element_type=jnp.float32)
        if causal:
            mask = jnp.tril(jnp.ones((Tl, Tl), bool))
            s = jnp.where(mask[None], s, _NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = (jnp.einsum('bts,bsd->btd', p.astype(vf.dtype), vf,
                        preferred_element_type=jnp.float32)
             / jnp.maximum(l, 1e-30)[..., None]).astype(qf.dtype)
        lse = jnp.where(m <= _NEG_INF / 2, _NEG_INF, m + jnp.log(
            jnp.maximum(l, 1e-30)))
    return (o.reshape(B, H, Tl, dh), lse.reshape(B, H, Tl))


def _merge_partials(o1, lse1, o2, lse2):
    """Combine two attention partials over disjoint key sets: the
    standard log-sum-exp merge (o_i are softmax-normalized within their
    own key set, lse_i the log partition)."""
    m = jnp.maximum(lse1, lse2)
    safe_m = jnp.where(m <= _NEG_INF / 2, 0.0, m)
    w1 = jnp.exp(jnp.where(lse1 <= _NEG_INF / 2, _NEG_INF, lse1) - safe_m)
    w2 = jnp.exp(jnp.where(lse2 <= _NEG_INF / 2, _NEG_INF, lse2) - safe_m)
    denom = jnp.maximum(w1 + w2, 1e-30)
    o = (o1.astype(jnp.float32) * w1[..., None] +
         o2.astype(jnp.float32) * w2[..., None]) / denom[..., None]
    lse = safe_m + jnp.log(denom)
    lse = jnp.where((lse1 <= _NEG_INF / 2) & (lse2 <= _NEG_INF / 2),
                    _NEG_INF, lse)
    return o, lse                  # fp32: the ring carries fp32 until
                                   # the final cast


def ring_flash_attention(q, k, v, axis_name='sp', causal=True,
                         sm_scale=None):
    """Ring attention whose per-block work runs through the Pallas
    flash kernel: K/V blocks rotate the 'sp' ring (ppermute) and each
    arriving block is consumed as a flash partial (o, lse), merged with
    the running partial by log-sum-exp. Per-device memory stays
    O(Tl·dh) — the [Tl, Tl] score block of the plain ring fold never
    exists either — and the MXU work inside each step is the tiled
    flash kernel, so the composition scales T across chips (ring) and
    within a chip (flash) at once.

    Gradients: pallas kernels have no JVP rule, so the ring carries its
    own custom_vjp — the backward re-runs the ring, feeding each block
    through the flash dq/dkv kernels with the GLOBAL lse (the flash
    backward identity P = exp(S − lse_global) makes per-block grads
    additive), and each block's (dk, dv) travels the ring with it until
    it arrives back home on the final rotation.

    Exact: equals softmax(QKᵀ·scale [+causal])·V over the full ring
    sequence (parity-tested against ring_attention/naive)."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    o, _lse = _ring_flash(q, k, v, axis_name, causal, scale)
    return o.astype(q.dtype)


def _flash_bwd_block(q, kb, vb, o, lse, g, causal, scale):
    """Per-block flash backward with the global lse (fully-masked
    future blocks are skipped by the caller's lax.cond)."""
    from ..pallas.flash_attention import _bwd, _supported
    B, H, Tl, dh = q.shape

    def flat(x):
        return x.reshape(B * H, Tl, -1)
    if _supported(Tl, dh) and _kernel_enabled():
        dq, dk, dv = _bwd(flat(q), flat(kb), flat(vb), flat(o),
                          lse.reshape(B * H, 1, Tl), flat(g),
                          causal, scale,
                          jax.default_backend() != 'tpu')
    else:
        qf, kf, vf, of, gf = (flat(q), flat(kb), flat(vb), flat(o),
                              flat(g))
        s = jnp.einsum('btd,bsd->bts', qf * jnp.asarray(scale, qf.dtype),
                       kf, preferred_element_type=jnp.float32)
        if causal:
            mask = jnp.tril(jnp.ones((Tl, Tl), bool))
            s = jnp.where(mask[None], s, _NEG_INF)
        p = jnp.exp(s - lse.reshape(B * H, Tl, 1))
        delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                        axis=-1, keepdims=True)
        dp = jnp.einsum('btd,bsd->bts', gf, vf,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq = jnp.einsum('bts,bsd->btd', ds.astype(kf.dtype), kf,
                        preferred_element_type=jnp.float32) * scale
        dk = jnp.einsum('bts,btd->bsd',
                        ds.astype(qf.dtype),
                        qf * jnp.asarray(scale, qf.dtype),
                        preferred_element_type=jnp.float32)
        dv = jnp.einsum('bts,btd->bsd', p.astype(gf.dtype), gf,
                        preferred_element_type=jnp.float32)
    shp = q.shape
    return (dq.reshape(shp).astype(q.dtype),
            dk.reshape(shp).astype(kb.dtype),
            dv.reshape(shp).astype(vb.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, axis_name, causal, scale):
    o, lse = _ring_flash_fwd_loop(q, k, v, axis_name, causal, scale)
    return o, lse


def _ring_flash_fwd_loop(q, k, v, axis_name, causal, scale):
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    o, lse = _flash_block(q, k, v, causal, scale)
    o = o.astype(jnp.float32)      # fp32 merge carry (like the exact
    perm = [(j, (j - 1) % n) for j in range(n)]   # ring's o/m/l)

    def step(carry, i):
        o, lse, kb, vb = carry
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        src = (my + i) % n

        def compute(kb, vb):
            return _flash_block(q, kb, vb, False, scale)

        def masked(kb, vb):
            # future block under causal: contributes nothing — skip the
            # kernel entirely (lse=-inf makes the merge a no-op)
            return (jnp.zeros_like(q),
                    jnp.full(q.shape[:3], _NEG_INF, jnp.float32))

        if causal:
            o_b, lse_b = jax.lax.cond(src < my, compute, masked, kb, vb)
        else:
            o_b, lse_b = compute(kb, vb)
        o, lse = _merge_partials(o, lse, o_b, lse_b)
        return (o, lse, kb, vb), None

    (o, lse, _, _), _ = jax.lax.scan(step, (o, lse, k, v),
                                     jnp.arange(1, n))
    return o, lse


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, scale):
    o, lse = _ring_flash_fwd_loop(q, k, v, axis_name, causal, scale)
    return (o, lse), (q, k, v, o, lse)


def _ring_flash_vjp_bwd(axis_name, causal, scale, res, cots):
    q, k, v, o, lse = res
    g, _g_lse = cots       # lse is an internal byproduct; its cotangent
    # is zero in any loss built from o (asserted by usage)
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(j, (j - 1) % n) for j in range(n)]

    dq, dkb, dvb = _flash_bwd_block(q, k, v, o, lse, g, causal, scale)

    def step(carry, i):
        dq, kb, vb, dkb, dvb = carry
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        dkb = jax.lax.ppermute(dkb, axis_name, perm)
        dvb = jax.lax.ppermute(dvb, axis_name, perm)
        src = (my + i) % n

        def compute(kb, vb):
            return _flash_bwd_block(q, kb, vb, o, lse, g, False, scale)

        def masked(kb, vb):
            # future block under causal: all three grads are exactly
            # zero — skip both backward kernels
            return (jnp.zeros_like(q), jnp.zeros_like(kb),
                    jnp.zeros_like(vb))

        if causal:
            dq_b, dk_b, dv_b = jax.lax.cond(src < my, compute, masked,
                                            kb, vb)
        else:
            dq_b, dk_b, dv_b = compute(kb, vb)
        return (dq + dq_b, kb, vb, dkb + dk_b, dvb + dv_b), None

    (dq, _, _, dkb, dvb), _ = jax.lax.scan(
        step, (dq, k, v, dkb, dvb), jnp.arange(1, n))
    # one final rotation returns each block's accumulated grads home
    dk = jax.lax.ppermute(dkb, axis_name, perm)
    dv = jax.lax.ppermute(dvb, axis_name, perm)
    return dq, dk, dv


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_flash_attention_global(q, k, v, mesh, causal=True,
                                sm_scale=None, seq_axis='sp',
                                batch_axis='dp', head_axis='tp'):
    """GSPMD-global entry for ring_flash_attention (mirrors
    ring_attention_global's sharding contract and fallbacks)."""
    if mesh is None:
        from ..pallas.flash_attention import flash_attention as _fa
        return _fa(q, k, v, causal=causal, sm_scale=sm_scale)
    spec, seq_ok = _ring_spec(mesh, q, seq_axis, batch_axis, head_axis)
    if not seq_ok:
        # mesh present but no usable sp axis: JAX refuses to lower a
        # bare pallas_call on GSPMD-sharded globals (a Mosaic kernel has
        # no partitioning rule) — use the einsum fallback, which XLA
        # partitions over dp/tp like any other op
        return ring_attention_global(q, k, v, None, causal=causal,
                                     sm_scale=sm_scale)
    fn = functools.partial(ring_flash_attention, axis_name=seq_axis,
                           causal=causal, sm_scale=sm_scale)
    # pallas_call outputs carry no varying-mesh-axes annotation, which
    # shard_map's check_vma rejects — disable the check (the per-device
    # computation is manifestly per-shard)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
