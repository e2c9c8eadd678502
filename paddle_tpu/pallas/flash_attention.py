"""Flash attention for TPU in Pallas — the memory-wall kernel for long
context (single-device analog of parallel/ring_attention.py; compose
with the sp ring for multi-chip sequences).

What XLA does with naive attention at sequence length T: materialize
the [B, H, T, T] score tensor in HBM (forward AND backward), so HBM
traffic and footprint grow as T² — at T=8k, bf16, B=8, H=16 that is a
16 GiB intermediate, past v5e HBM. This kernel streams K/V blocks
through VMEM with the online-softmax recurrence (Dao et al.; same fold
as ring_attention's per-device step), keeping residency at
O(block_q · d) and saving only (O, LSE) for the backward, which
recomputes P blockwise. The MXU sees the same two matmuls per block;
the win is bandwidth and memory, which is exactly what long context is
bound by.

Layout: q, k, v are [BH, T, d] (batch×heads collapsed into the leading
grid dimension); T must divide by the block sizes (the op wrapper
guards and falls back to XLA otherwise); d should be a lane multiple
(128) for MXU alignment.

Forward grid (bh, qi, ki), ki innermost: the (m, l, o) accumulators for
one q block live in VMEM scratch across the ki sweep (m and l
lane-replicated [bq, 128], the layout a row reduction is born in);
causal q-blocks stop their sweep at the diagonal (pl.when skips both
compute and the write until the final valid ki), and a K block is
walked in chunks of _FWD_CHUNK_K keys. That is the `online` arm, what
every shape gets (blocks from _BLOCK_TABLE_FWD). A second `twopass`
arm (PADDLE_FLASH_FWD) splits the sweep into a stats pass (row max +
lse only, no V traffic) and a 1-exp rescale-free accumulation pass;
on this chip at BH=64, T=2048, d=128 it reads 3.2-3.7 ms a call
against the online arm's 0.79 (PERF.md section 6, PR 37) and stays
only as the tools' A/B hook -- see the forward-arm comment block below.

Backward: delta = rowsum(dO·O) in plain JAX, then the KV-MAJOR
single-pass kernel (grid (bh, ki, qi), both inner dims sequential;
S/P/dP/dS computed once per visited pair = the 5-matmul + 1-exp
minimum; dk/dv in small per-ki scratch, dq accumulated across the
whole sweep in a full-sequence fp32 scratch written once). lse and
delta stay [BH, 1, T] rows from the forward kernel to the backward's
(1, 1, bq) fetch, and the pair's tiles are computed transposed,
[keys, queries], where a row broadcasts along sublanes and two of the
three gradient products need no transpose; a block is walked in chunk
pairs of _BWD_CHUNK and a pair no query sees is skipped, while tracing
where the block spans the sequence. On this chip at BH=64, T=2048,
d=128 (PERF.md section 6, PR 41; ms a call, delta included): 2.07-2.13
as it was at (512, 512) blocks; 1.59 with the mask as two pl.when
bodies in place of a cond that yields the [512, 512] tile; 1.25 with
the whole head one grid step (1.34 of them with the statistics still
columns). Two alternates stay available via PADDLE_FLASH_BWD and carry
their own grad-parity tests, on the same pair function and chunk walk:
`split` (dq sweep + dk/dv sweep, 7 block-matmuls + 2 exp streams: 1.71
there — also the automatic fallback when the kv-major scoped-VMEM
request would pass the measured-safe 64 MB ceiling, i.e. from
T=64k/d=128) and `onepass` (the qi-major transpose with dk/dv as the
resident accumulators: 1.24 there, level with kv-major, where an
earlier chip read it 10-50 % behind at T=8192).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import telemetry as _tm

__all__ = ['flash_attention']

_NEG_INF = -1e30

# Which route flash_attention() took, bumped once per trace (the Python
# body of a jitted caller runs only while tracing): a shape that misses
# the kernel shows up in `pallas.flash.naive` instead of running the
# [T, T] contraction under a flash label.
_ROUTE_KERNEL = _tm.counter('pallas.flash.kernel')
_ROUTE_NAIVE = _tm.counter('pallas.flash.naive')
# ... and which forward schedule _fwd compiled, once per trace of _fwd:
# a call its cache answers (the next layer, the grad op's re-trace of
# the forward) does not count again
_FWD_SCHEDULE = {'online': _tm.counter('pallas.flash.fwd.online'),
                 'twopass': _tm.counter('pallas.flash.fwd.twopass')}
# ... and which backward arm _bwd compiled, once per trace of _bwd
_BWD_SCHEDULE = {arm: _tm.counter('pallas.flash.bwd.' + arm)
                 for arm in ('split', 'onepass', 'kvmajor')}

# Backward-arm selection. Three arms, all grad-parity-tested, one pair
# function (_pair_grads) and one chunk walk (_visible_pairs):
#   split    — dq kernel + dk/dv kernel (7 block-matmuls, 2 exp streams)
#   onepass  — grid (bh, qi, ki), dk/dv in full-sequence VMEM scratch
#              (5 matmuls, 1 exp; ~12 MB resident at 8k/128)
#   kvmajor  — grid (bh, ki, qi): the transpose of onepass. dk/dv live
#              in small per-ki scratch; dq accumulates in a
#              full-sequence fp32 scratch (T·d·4 = 4 MB at 8k/128 —
#              HALF the onepass residency) written once at the end.
#              Same 5-matmul + 1-exp minimum per visited pair.
# PADDLE_FLASH_BWD=split|onepass|kvmajor forces an arm;
# PADDLE_FLASH_ONEPASS=1 is the legacy spelling of onepass. Every shape
# gets kvmajor up to the VMEM ceiling in _bwd. Ranked on this chip at
# BH=64, T=2048, d=128, bf16, causal, (2048, 2048) blocks (PERF.md
# section 6, PR 41; tools/flash_bwd_arms.py, ms a call): kvmajor 1.25,
# onepass 1.24, split 1.71. (On an earlier chip onepass read 10-50 %
# behind kvmajor at T=8192, its residency starving Mosaic's double
# buffering; at 2048 both hold the whole head and differ by nothing.)
import os as _os
_BWD_ARMS = ('', 'split', 'onepass', 'kvmajor')
_FORCE_ARM = _os.environ.get('PADDLE_FLASH_BWD', '').strip().lower()
if _FORCE_ARM not in _BWD_ARMS:
    # a typo silently benchmarking the default arm is exactly the
    # sweep corruption _block_sizes already guards against
    raise ValueError('PADDLE_FLASH_BWD=%r: expected one of %s'
                     % (_FORCE_ARM, _BWD_ARMS[1:]))
if not _FORCE_ARM and _os.environ.get('PADDLE_FLASH_ONEPASS', '') in (
        '1', 'true', 'yes'):
    _FORCE_ARM = 'onepass'
# the arm _bwd actually dispatched at its last trace — the residency
# guards may silently swap a forced arm for 'split', so measurement
# tools must check this rather than trust the arm they requested; and
# the (block_q, block_k) that trace ran with
_RESOLVED_ARM = ''
_RESOLVED_BWD_BLOCKS = ()

# Forward-arm selection (round 6). Two arms, both parity-tested on
# (o, lse, grads):
#   online   — the classic one-sweep kernel above: running max +
#              correction + acc rescale per K block (1 QK matmul,
#              1 exp stream, the max/corr/rescale VPU chain that
#              round-5 attribution names as ~70% of the roofline gap)
#   twopass  — the backward's stored-lse trick ported forward: pass 1
#              sweeps K computing only row max and lse (no V traffic,
#              no output accumulator, [bq]-sized corr only); pass 2
#              recomputes S and accumulates exp(s − lse) @ v with ONE
#              exp per element, rescale-free and division-free. Trades
#              one extra QK matmul/read (the kernel is VPU-bound, and
#              the kvmajor clamp A/B proved skipped-block DMAs hide
#              under compute) for the whole [bq, d] corr/rescale chain.
# PADDLE_FLASH_FWD=online|twopass forces an arm; every shape gets
# online. Ranked on this chip at BH=64, T=2048, d=128 bf16 causal
# (PERF.md section 6, PR 37): twopass 3.67 ms a call at (512, 512)
# blocks, 3.20 at (1024, 1024) -- three products and two exp streams
# over 1-D statistics -- against 2.67 for the online kernel as it then
# was and 0.79 as it is. (The earlier round-5 'boundmax' fwd attempt
# was dropped for a 4x dq-parity loss; the stored-lse schedule has no
# such mantissa hazard because lse is exact, not a slack bound.)
_FWD_ARMS = ('', 'online', 'twopass')
_FORCE_FWD_ARM = _os.environ.get('PADDLE_FLASH_FWD', '').strip().lower()
if _FORCE_FWD_ARM not in _FWD_ARMS:
    # same loud-config contract as PADDLE_FLASH_BWD: a typo silently
    # benchmarking the default arm would corrupt an A/B sweep
    raise ValueError('PADDLE_FLASH_FWD=%r: expected one of %s'
                     % (_FORCE_FWD_ARM, _FWD_ARMS[1:]))
# the arm _fwd actually dispatched at its last trace — the twopass
# residency guard may silently swap a forced arm for 'online', so
# measurement tools must cross-check this before ranking; and the
# (block_q, block_k) that trace ran with
_RESOLVED_FWD_ARM = ''
_RESOLVED_FWD_BLOCKS = ()

# clamp block index maps during causally-skipped grid steps so the
# dead prefetch DMAs are elided (trace-time; off only for A/B)
_CLAMP_SKIPPED_DMA = True


def _mask_if_straddling(s, qi, ki, block_q, block_k):
    """Causal mask applied only when the (qi, ki) block straddles the
    diagonal: a visited block with max k_pos <= min q_pos is fully
    visible and skips the iota/compare/select VPU passes (the kernel's
    dominant cost — PERF.md round-4 flash ladder)."""

    def masked(s_):
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        return jnp.where(q_pos >= k_pos, s_, _NEG_INF)

    return jax.lax.cond(ki * block_k + block_k - 1 > qi * block_q,
                        masked, lambda s_: s_, s)


# The online kernel walks a K block in chunks of at most this many keys:
# a [bq, 512] score tile keeps the per-chunk work long enough to hide the
# statistics' [bq, 128] passes and short enough for large K blocks (few
# grid steps, few finalizations) to stay in VMEM. Chip A/B at BH=64,
# T=2048, d=128: (1024, 1024) blocks 0.885 ms whole, 0.796 ms in chunks
# of 512; chunks of 256 and 128 lose (PERF.md section 6, PR 37).
_FWD_CHUNK_K = 512


def _lanes(x, n):
    """A per-row statistic, lane-replicated [rows, 128], as [rows, n]."""
    if n == 128:
        return x
    if n % 128 == 0:
        return jnp.tile(x, (1, n // 128))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, q_scr, *, sm_scale, causal,
                block_q, block_k, nk):
    """One (q block, K block) pair of the online-softmax sweep.

    The running maximum `m` and sum `l` live lane-replicated in
    [bq, 128] scratch: a row reduction's result is born a column, and
    every use of it here (against the [bq, 128] scratch, against each
    128-lane slice of the scores, against the [bq, d] accumulator) takes
    it in that layout. (As 1-D `(bq,)` vectors each pair paid four
    sublane<->lane relayouts of bq values: 2.67 ms a call at BH=64,
    T=2048 against 1.17 ms, same blocks, same arithmetic.) No row is
    guarded inside the sweep: a row the mask hides entirely so far has
    m = -1e30 and garbage in l and acc, and the first pair that shows it
    a key multiplies both by exp(-1e30 - m_new) = 0; a row hidden to the
    end is zeroed in _finalize, as before."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    d = q_ref.shape[-1]
    last_ki = nk - 1
    if causal:
        last_ki = ((qi + 1) * block_q - 1) // block_k
    ck = _FWD_CHUNK_K if block_k % _FWD_CHUNK_K == 0 else block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        # once a q block, not once a pair (input dtype, as the backward
        # recomputes it)
        q_scr[:] = q_ref[0] * sm_scale

    def sweep(masked):
        q = q_scr[:]                                  # [bq, d]
        for c in range(block_k // ck):
            k = k_ref[0, c * ck:(c + 1) * ck, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [bq, ck]
            if masked:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, ck), 0)
                k_pos = ki * block_k + c * ck + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, ck), 1)
                s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
            m_prev = m_scr[:]                         # [bq, 128]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            # no second mask on p: masked s = -1e30, and exp(-1e30 - m)
            # underflows to exactly 0 for any finite m
            # (an MXU p@1 rewrite of this lane-axis sum was A/B'd and
            # LOSES ~10% — PERF.md round-5 fwd-kernel probe)
            p = jnp.exp(s - _lanes(m_new, ck))
            l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1,
                                                 keepdims=True)
            m_scr[:] = m_new
            acc_scr[:] = acc_scr[:] * _lanes(corr, d) + \
                jax.lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[0, c * ck:(c + 1) * ck, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    if causal:
        # the kernel is VPU-bound (PERF.md round-4 flash ladder): only
        # diagonal-straddling blocks pay for the iota mask. Two bodies
        # under pl.when, not a cond that yields the [bq, bk] tile.
        straddles = ki * block_k + block_k - 1 > qi * block_q
        pl.when((ki <= last_ki) & straddles)(
            functools.partial(sweep, True))
        pl.when((ki <= last_ki) & jnp.logical_not(straddles))(
            functools.partial(sweep, False))
    else:
        sweep(False)

    @pl.when(ki == last_ki)
    def _finalize():
        m = m_scr[:]
        safe_l = jnp.maximum(l_scr[:], 1e-30)
        hidden = m <= _NEG_INF / 2
        o = acc_scr[:] / _lanes(safe_l, d)
        o_ref[0] = jnp.where(_lanes(hidden, d), 0.0, o).astype(o_ref.dtype)
        lse = jnp.where(hidden, _NEG_INF, m + jnp.log(safe_l))
        if lse_ref.shape[-1] == 1:
            lse_ref[0] = lse[:, :1]
        else:
            # lse leaves as a [1, bq] row (see _fwd_online): the
            # transpose of a [128, 128] slice of the lane-replicated
            # value holds its 128 rows along the lanes of every row
            lse_ref[0] = jnp.concatenate(
                [lse[r:r + 128, :].T[:1, :]
                 for r in range(0, block_q, 128)], axis=1)


def _fwd_stats_kernel(q_ref, k_ref, lse_ref, m_scr, l_scr, *, sm_scale,
                      causal, block_q, block_k, nk):
    """Two-pass forward, pass 1: sweep K at streaming rate computing
    only the row max and lse. No V traffic, no [bq, d] output
    accumulator — residency is two [bq] vectors — so the only
    per-element VPU work is the exp feeding the l sum; the running
    max/corr chain survives here but operates on [bq] vectors, not the
    [bq, d] accumulator the online kernel rescales every block."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    last_ki = nk - 1
    if causal:
        last_ki = ((qi + 1) * block_q - 1) // block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    @pl.when(ki <= last_ki)
    def _step():
        q = q_ref[0] * sm_scale          # [bq, d] (input dtype)
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        if causal:
            s = _mask_if_straddling(s, qi, ki, block_q, block_k)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        safe_m = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        corr = jnp.exp(jnp.where(m_prev <= _NEG_INF / 2, safe_m, m_prev)
                       - safe_m)
        # masked s = -1e30 underflows to exactly 0 against any finite
        # (or zeroed) safe_m — same no-second-mask argument as online
        l_scr[:] = l_scr[:] * corr + jnp.sum(
            jnp.exp(s - safe_m[:, None]), axis=1)
        m_scr[:] = m_new

    @pl.when(ki == last_ki)
    def _finalize():
        m = m_scr[:]
        lse = jnp.where(m <= _NEG_INF / 2, _NEG_INF,
                        m + jnp.log(jnp.maximum(l_scr[:], 1e-30)))
        lse_ref[0] = lse[:, None]


def _fwd_acc_kernel(q_ref, k_ref, v_ref, lse_ref, o_ref, acc_scr, *,
                    sm_scale, causal, block_q, block_k, nk):
    """Two-pass forward, pass 2: recompute S and accumulate
    exp(s − lse) @ v. With lse = m + log l stored from pass 1,
    p = exp(s − lse) IS the softmax row exactly — one exp per element,
    no running max, no correction, no accumulator rescale, and no final
    division (the backward's stored-lse identity, applied forward)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    last_ki = nk - 1
    if causal:
        last_ki = ((qi + 1) * block_q - 1) // block_k

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki <= last_ki)
    def _step():
        q = q_ref[0] * sm_scale          # [bq, d] (input dtype)
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        if causal:
            s = _mask_if_straddling(s, qi, ki, block_q, block_k)
        lse = lse_ref[0]                              # [bq, 1] fp32
        # lse = -inf marks an all-masked row (cannot occur causally —
        # every row sees the diagonal — but the online kernel emits 0
        # there, so match it): zero the shift and rely on the masked
        # s = -1e30 to underflow p to exactly 0
        p = jnp.exp(s - jnp.where(lse <= _NEG_INF / 2, 0.0, lse))
        acc_scr[:] += jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == last_ki)
    def _finalize():
        o_ref[0] = acc_scr[:].astype(o_ref.dtype)


# Measured-safe scoped-VMEM ceiling shared with the kv-major backward
# guard; module-level so the guard unit test can pin it down without
# fabricating a shape that actually overflows VMEM.
_TWOPASS_VMEM_CEILING = 64 * 1024 * 1024


def _twopass_vmem_bytes(T, d, bq, bk, io_itemsize):
    """Scoped-VMEM request for the LARGER (second) pass of the twopass
    forward: fp32 acc scratch + streamed q/k/v/o blocks at the I/O
    dtype + fp32 lse blocks, triple-buffered as the worst case Mosaic
    schedules. Neither pass holds a full-sequence accumulator — that is
    the point of the arm — so this sits far below the ceiling for every
    tiled shape; the guard exists for forced-block extremes and keeps
    the forced-arm-can-be-swapped contract identical to the backward.
    The 6 MB margin absorbs Mosaic's stack accounting (the round-5 OOM
    lesson: measured stack runs MB above the component sum and drifts
    with libtpu)."""
    acc = bq * d * 4
    stream = (2 * bq * d + 2 * bk * d) * io_itemsize + bq * 4
    return int(acc + 3 * stream) + 6 * 1024 * 1024


# The backward walks a block in chunk pairs of at most this many rows a
# side: a [512, 512] score tile is what the parent's (512, 512) blocks
# held, so larger blocks cost grid steps and fetches, not VMEM
# temporaries. Chip A/B at BH=64, T=2048, d=128, the whole head one
# block: chunks of 512 1.25 ms a call, of 256 1.24, of 1024 1.59
# (PERF.md section 6, PR 41).
_BWD_CHUNK = 512

_NT = (((1,), (1,)), ((), ()))      # a @ b.T: the matrix unit's own form
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# A grid index is a Python int where its axis has one step, and then
# what depends on it alone is decided while tracing: these three take a
# bool or a traced predicate.
def _when(cond):
    if isinstance(cond, bool):
        return (lambda body: body()) if cond else (lambda body: None)
    return pl.when(cond)


def _and(a, b):
    if isinstance(a, bool):
        return b if a else False
    if isinstance(b, bool):
        return a if b else False
    return a & b


def _not(a):
    return (not a) if isinstance(a, bool) else jnp.logical_not(a)


def _grid_index(axis, steps):
    return 0 if steps == 1 else pl.program_id(axis)


def _chunk(block):
    return _BWD_CHUNK if block % _BWD_CHUNK == 0 else block


def _pair_grads(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs, ks,
                q0, k0, sm_scale, masked):
    """The backward's per-pair math, shared by the three arms, for
    queries `qs` and keys `ks` of the blocks in hand (q0 and k0 their
    places in the sequence): recompute the scores, P from the stored
    lse, dP, dS -- all TRANSPOSED, [keys, queries]. In that orientation
    lse and delta are [1, cq] rows that broadcast along sublanes (what
    the forward writes; as (bq, 1) columns each block was lane-padded to
    256 KB in VMEM and in HBM), `dv += pT do` and `dk += dsT q` contract
    as the matrix unit does and only `dq += dsT.T k` transposes. Returns
    (q scaled, k, do, pT, dsT), the tiles cast to the operands' type.
    lse may be larger than this block's own (the ring's global lse): p
    is then simply smaller."""
    q = q_ref[0, qs, :] * sm_scale                     # input dtype, as
    k = k_ref[0, ks, :]                                # the forward does
    do = do_ref[0, qs, :]
    sT = _dot(k, q, _NT)                               # [ck, cq]
    if masked:
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, sT.shape, 0)
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, sT.shape, 1)
        sT = jnp.where(q_pos >= k_pos, sT, _NEG_INF)
    pT = jnp.exp(sT - lse_ref[0, :, qs])
    dpT = _dot(v_ref[0, ks, :], do, _NT)
    dsT = pT * (dpT - delta_ref[0, :, qs])
    return q, k, do, pT.astype(do.dtype), dsT.astype(q.dtype)


def _visible_pairs(refs, qi, ki, accumulate, *, sm_scale, causal,
                   block_q, block_k):
    """accumulate(qs, ks, *_pair_grads(...)) for every chunk pair of the
    block pair (qi, ki) in which some query sees some key: masked where
    the pair straddles the diagonal, plain where every key is visible,
    not at all where none is. Two bodies under pl.when, not a cond that
    yields the score tile: at (512, 512) blocks that cond alone was 0.5
    of 2.1 ms a call (PERF.md section 6, PR 41)."""
    cq, ck = _chunk(block_q), _chunk(block_k)

    def pair(qs, ks, masked):
        accumulate(qs, ks, *_pair_grads(
            *refs, qs, ks, qi * block_q + qs.start,
            ki * block_k + ks.start, sm_scale, masked))

    for k0 in range(0, block_k, ck):
        for q0 in range(0, block_q, cq):
            qs, ks = slice(q0, q0 + cq), slice(k0, k0 + ck)
            if not causal:
                pair(qs, ks, False)
                continue
            q_first, k_first = qi * block_q + q0, ki * block_k + k0
            visible = q_first + cq - 1 >= k_first
            straddles = k_first + ck - 1 > q_first
            _when(_and(visible, straddles))(
                functools.partial(pair, qs, ks, True))
            _when(_and(visible, _not(straddles)))(
                functools.partial(pair, qs, ks, False))


def _dq_kernel(*refs, sm_scale, causal, block_q, block_k, nk):
    dq_ref, acc_scr = refs[6:]
    qi = pl.program_id(1)
    ki = _grid_index(2, nk)
    last_ki = nk - 1
    if causal:
        last_ki = ((qi + 1) * block_q - 1) // block_k

    @_when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def accumulate(qs, ks, q, k, do, pT, dsT):
        acc_scr[qs, :] += _dot(dsT, k, _TN)

    _visible_pairs(refs[:6], qi, ki, accumulate, sm_scale=sm_scale,
                   causal=causal, block_q=block_q, block_k=block_k)

    @_when(ki == last_ki)
    def _finalize():
        dq_ref[0] = (acc_scr[:] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, sm_scale, causal, block_q, block_k, nq):
    """dk/dv sweep (grid bh, ki, qi; VMEM-scratch accumulation over
    qi) — the large-T fallback arm of the split backward."""
    dk_ref, dv_ref, dk_scr, dv_scr = refs[6:]
    ki = pl.program_id(1)
    qi = _grid_index(2, nq)

    @_when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def accumulate(qs, ks, q, k, do, pT, dsT):
        dv_scr[ks, :] += _dot(pT, do, _NN)                   # [ck, d]
        dk_scr[ks, :] += _dot(dsT, q, _NN)                   # [ck, d]

    _visible_pairs(refs[:6], qi, ki, accumulate, sm_scale=sm_scale,
                   causal=causal, block_q=block_q, block_k=block_k)

    @_when(qi == nq - 1)
    def _finalize():
        # dk needs no extra sm_scale: the accumulation used the
        # already-scaled q, which carries the factor
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _onepass_vmem_bytes(T, d, bq, bk, out_itemsize):
    """Scoped-VMEM request for the one-pass backward: fp32 dk/dv
    accumulators + their resident output buffers (at the INPUT dtype —
    fp32 inputs double them) + dq scratch + double-buffered working
    blocks."""
    acc = 2 * T * d * 4
    outs = 2 * T * d * out_itemsize
    blocks = 2 * (3 * bq * d + 2 * bk * d) * 2 + bq * d * 4
    # Mosaic's own stack accounting runs ~1 MB above this estimate at
    # T=8192 (measured 17.75M vs 16.9M); the margin absorbs it (4 MB
    # sufficed when first measured; 6 MB after a libtpu stack-
    # accounting drift re-OOMed the 8k/BH=16 shape)
    return int(acc + outs + 3 * blocks) + 6 * 1024 * 1024


def _bwd_onepass_kernel(*refs, sm_scale, causal, block_q, block_k, nq,
                        nk):
    """Round-5 single-pass backward: grid (bh, qi, ki), BOTH inner dims
    sequential. Each visited pair computes S, P, dP, dS once and does
    exactly the 5 block-matmuls the gradients need. dq accumulates in a
    per-qi scratch (reset at ki==0, flushed at the diagonal/last ki);
    dk/dv accumulate in full-sequence (nk, bk, d) fp32 scratch across
    the WHOLE sweep — VMEM-resident because T·d elements is ≤ 4M for
    every supported long-context shape — and are written to HBM once at
    the final grid step (their output blocks span the whole sequence,
    index-mapped constant, so Pallas keeps one buffer live)."""
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = refs[6:]
    qi = _grid_index(1, nq)
    ki = _grid_index(2, nk)
    last_ki = nk - 1
    if causal:
        last_ki = ((qi + 1) * block_q - 1) // block_k

    @_when(_and(qi == 0, ki == 0))
    def _init_kv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @_when(ki == 0)
    def _init_q():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def accumulate(qs, ks, q, k, do, pT, dsT):
        dq_scr[qs, :] += _dot(dsT, k, _TN)                   # [cq, d]
        dv_scr[ki, ks, :] += _dot(pT, do, _NN)               # [ck, d]
        dk_scr[ki, ks, :] += _dot(dsT, q, _NN)               # [ck, d]

    _visible_pairs(refs[:6], qi, ki, accumulate, sm_scale=sm_scale,
                   causal=causal, block_q=block_q, block_k=block_k)

    @_when(ki == last_ki)
    def _fin_q():
        dq_ref[0] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)

    @_when(_and(qi == nq - 1, ki == nk - 1))
    def _fin_kv():
        # q carried sm_scale into dk's accumulation already
        dk_ref[0] = dk_scr[:].reshape(dk_ref.shape[1:]) \
            .astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].reshape(dv_ref.shape[1:]) \
            .astype(dv_ref.dtype)


def _kvmajor_vmem_bytes(T, d, bq, bk, out_itemsize):
    """Scoped-VMEM request for the kv-major backward: full-sequence
    fp32 dq accumulator + its resident output buffer + per-ki dk/dv
    scratch + double-buffered working blocks."""
    dq_acc = T * d * 4
    dq_out = T * d * out_itemsize
    kv_scr = 2 * bk * d * 4
    # streaming traffic at the I/O dtype: q/do (bq,d) + k/v (bk,d) +
    # dk/dv output blocks (bk,d), plus fp32 lse/delta (1,bq) rows —
    # triple-buffered as the worst case Mosaic schedules
    stream = (2 * bq * d + 4 * bk * d) * out_itemsize + 2 * bq * 4
    # Mosaic's stack accounting runs WELL above the component sum and
    # varies with the surrounding program: the isolated 8k/128/BH=16
    # kernel measured 15.94M of stack, the same kernel inside the full
    # longcontext program 16.94M — ~5.7 MB over the raw component sum
    # (est. 11.3M). The margin must absorb that whole class, not just
    # libtpu drift; 8 MB grants 19.3M at 8k/128 and scales with the
    # component terms at larger T. (The score tiles are in it: a chunk
    # pair holds what a (512, 512) block held.)
    # Past T=8k that is not enough with libtpu 0.0.34: compiled for a
    # described v5e the kernel asks 36.6M at 32k and 66.8M at 64k, 11.4
    # and 16.4 MB over the components (PR 41; no chip involved), so the
    # margin grows with the resident dq block and 64k now goes to split.
    return int(dq_acc + dq_out + kv_scr + 3 * stream + dq_out // 2) \
        + 8 * 1024 * 1024


def _bwd_kvmajor_kernel(*refs, sm_scale, causal, block_q, block_k, nq,
                        nk):
    """kv-major single-pass backward: grid (bh, ki, qi), both inner
    dims sequential. Each visible chunk pair of a (ki, qi) block pair
    computes S, P, dP, dS once — the 5-matmul + 1-exp minimum (the split
    arm pays 7 + 2) — and a hidden one is skipped outright; where a
    block spans the sequence (nq == nk == 1, the training cells' shape)
    which pairs those are is known while tracing, and a head is one grid
    step of ten pairs with no predicate. dk/dv accumulate in per-ki
    scratch flushed at each row's end (as in the split dkv kernel); dq
    accumulates across the WHOLE sweep in a full-sequence (nq, bq, d)
    fp32 scratch — T·d·4 = 4 MB at 8k/128, HALF the residency of the
    onepass arm whose 12 MB starved Mosaic's double-buffering — and is
    written to HBM once at the final grid step (dq's output block spans
    the sequence, index-mapped constant, so Pallas keeps one live
    buffer)."""
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = refs[6:]
    ki = _grid_index(1, nk)
    qi = _grid_index(2, nq)

    @_when(_and(ki == 0, qi == 0))
    def _init_dq():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @_when(qi == 0)
    def _init_kv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def accumulate(qs, ks, q, k, do, pT, dsT):
        dv_scr[ks, :] += _dot(pT, do, _NN)                   # [ck, d]
        dk_scr[ks, :] += _dot(dsT, q, _NN)                   # [ck, d]
        dq_scr[qi, qs, :] += _dot(dsT, k, _TN)               # [cq, d]

    _visible_pairs(refs[:6], qi, ki, accumulate, sm_scale=sm_scale,
                   causal=causal, block_q=block_q, block_k=block_k)

    @_when(qi == nq - 1)
    def _fin_kv():
        # dk needs no extra sm_scale: the accumulation used the
        # already-scaled q, which carries the factor
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @_when(_and(ki == nk - 1, qi == nq - 1))
    def _fin_dq():
        dq_ref[0] = (dq_scr[:] * sm_scale) \
            .reshape(dq_ref.shape[1:]).astype(dq_ref.dtype)


# (T, d) -> (block_q, block_k) of the backward. (8192, 128): an earlier
# chip's interleaved sweep (tools/flash_autotune.py) ranked bk=1024
# first at every bq; on this one that entry reads 6.60 ms a call at
# BH=16 as the kernel was and 4.60 as it is, (2048, 1024) 4.33 and
# (2048, 2048) 8.7 (sixteen chunk pairs under predicates in one step).
# (2048, 128), the training cells' shape (BH=64 a chip): the whole head
# one block, so a head is one grid step and which ten of its sixteen
# chunk pairs are visible is fixed while tracing -- 1.25 ms a call
# against (1024, 1024) 1.45, (2048, 1024) and (1024, 2048) 1.42,
# (2048, 512) 1.42, (512, 512) 1.58 (PERF.md section 6, PR 41).
_BLOCK_TABLE = {
    (8192, 128): (512, 1024),
    (2048, 128): (2048, 2048),
}

# The forward and backward only share (o, lse), which are block-size
# independent — so each direction keeps its own tuned table. The fwd's
# per-block corr/rescale chain amortizes with bigger blocks: fwd-only
# sweep at T=8192 ranks (1024, 1024) 5.26 ms vs the shared-table
# (512, 1024) 5.88 ms (~10%, 3 interleaved rounds; PERF.md round-5).
#
# (2048, 128), the training cells' shape (BH=64 a chip), on this chip
# (PERF.md section 6, PR 37; interleaved, ms a call): (1024, 1024)
# 0.79, (512, 1024) 0.83, (512, 512) 0.85, (1024, 512) 0.92,
# (2048, 1024) 0.96, (2048, 512) 1.09, (256, 512) 1.19. Larger blocks
# mask more of what they visit (6 of 8 chunk pairs hold a hidden
# quarter at (1024, 1024)) and still win: 3 grid steps and 2
# finalizations a head where (512, 512) has 10 and 4.
_BLOCK_TABLE_FWD = {
    (8192, 128): (1024, 1024),
    (2048, 128): (1024, 1024),
}

# The twopass arm shifts the balance again: it has no per-block
# corr/rescale to amortize, and bk=1024 keeps the pass-2 exp stream on
# full 1024-lane rows (lane-parallel exp scheduling). Populated by
# `tools/flash_autotune.py --fwd-only --fwd-arm twopass` so the tuned
# table stays per-arm honest; falls back to _BLOCK_TABLE_FWD until a
# chip sweep lands a twopass-specific winner.
_BLOCK_TABLE_FWD_TWOPASS = {}


def _block_sizes(T, d, fwd=False, arm=''):
    from ..flags import get_flag
    fq = int(get_flag('flash_block_q', 0) or 0)
    fk = int(get_flag('flash_block_k', 0) or 0)
    if fq or fk:
        # a half-set or non-dividing override silently benchmarking the
        # default kernel is exactly the sweep corruption to avoid
        # (the override binds BOTH directions so sweeps stay coherent)
        if not (fq and fk):
            raise ValueError('set BOTH FLAGS_flash_block_q and '
                             'FLAGS_flash_block_k (got q=%d k=%d)'
                             % (fq, fk))
        if T % fq or T % fk:
            raise ValueError('flash block override (%d, %d) does not '
                             'divide T=%d' % (fq, fk, T))
        return fq, fk
    if fwd and arm == 'twopass' and (T, d) in _BLOCK_TABLE_FWD_TWOPASS:
        return _BLOCK_TABLE_FWD_TWOPASS[(T, d)]
    if fwd and (T, d) in _BLOCK_TABLE_FWD:
        return _BLOCK_TABLE_FWD[(T, d)]
    if (T, d) in _BLOCK_TABLE:
        return _BLOCK_TABLE[(T, d)]
    bq = min(512, T)
    bk = min(512, T)
    while T % bq:
        bq //= 2
    while T % bk:
        bk //= 2
    return max(bq, 8), max(bk, 128 if T % 128 == 0 else bk)


def _fwd_kvmap(causal, bq, bk):
    """K/V-side block index map for the forward grids. During causally-
    skipped steps (j > last_ki(i)) clamp the fetch to the last visited
    block: the block index is then unchanged step-to-step, so Mosaic
    elides the dead DMA. (_CLAMP_SKIPPED_DMA is the trace-time A/B
    hook.)"""
    def kvmap(b, i, j):
        if causal and _CLAMP_SKIPPED_DMA:
            j = jnp.minimum(j, ((i + 1) * bq - 1) // bk)
        return (b, j, 0)
    return kvmap


@functools.partial(jax.jit, static_argnames=('causal', 'sm_scale',
                                             'interpret', 'lse_rows'))
def _fwd(q, k, v, causal, sm_scale, interpret=False, lse_rows=False):
    """(o, lse): lse float32 as [BH, T, 1] columns (what the ring's
    merge and the tools take), or with lse_rows as the [BH, 1, T] rows
    the online kernel writes and _bwd reads -- the layout _flash keeps
    between the two, so that no relayout stands in a training step."""
    BH, T, d = q.shape
    # Arm selection mirrors _bwd: forced via PADDLE_FLASH_FWD, else
    # online (see the arm comment block at the top). Block sizes
    # resolve per-arm first because the twopass table may differ; the
    # residency guard can then swap a forced twopass back to online, in
    # which case the blocks re-resolve under the online table.
    arm = _FORCE_FWD_ARM or 'online'
    bq, bk = _block_sizes(T, d, fwd=True, arm=arm)
    if arm == 'twopass' and _twopass_vmem_bytes(
            T, d, bq, bk, q.dtype.itemsize) > _TWOPASS_VMEM_CEILING:
        arm = 'online'
        bq, bk = _block_sizes(T, d, fwd=True, arm=arm)
    global _RESOLVED_FWD_ARM, _RESOLVED_FWD_BLOCKS
    _RESOLVED_FWD_ARM, _RESOLVED_FWD_BLOCKS = arm, (bq, bk)
    _FWD_SCHEDULE[arm].inc()
    nq, nk = T // bq, T // bk
    o, lse = (_fwd_twopass if arm == 'twopass' else _fwd_online)(
        q, k, v, causal, sm_scale, interpret, bq, bk, nq, nk)
    return o, lse.reshape((BH, 1, T) if lse_rows else (BH, T, 1))


def _fwd_online(q, k, v, causal, sm_scale, interpret, bq, bk, nq, nk):
    BH, T, d = q.shape
    kern = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                             causal=causal, block_q=bq, block_k=bk,
                             nk=nk)
    kvmap = _fwd_kvmap(causal, bq, bk)
    # lse leaves the kernel as [BH, 1, T] rows where the q block tiles
    # into lanes: a (bq, 1) column block is lane-padded to 256 KB at
    # bq=512, in VMEM and in HBM, and writing it cost 0.26 ms of a
    # 1.17 ms call at BH=64, T=2048 (PERF.md section 6, PR 37). _fwd
    # hands on the layout its caller asked for.
    lse_rows = bq % 128 == 0
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), kvmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), kvmap, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM) if lse_rows else
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, d), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, T) if lse_rows else (BH, T, 1),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, d), q.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(q, k, v)


def _fwd_twopass(q, k, v, causal, sm_scale, interpret, bq, bk, nq, nk):
    """Stored-lse two-pass forward (see _fwd_stats_kernel /
    _fwd_acc_kernel). Returns the same exact (o, lse) contract as the
    online kernel, so the backward arms and ring_attention's global-lse
    merge consume either forward unchanged. Neither pass holds a
    full-sequence accumulator, so no raised scoped-vmem request is
    needed for tiled shapes; forced-block extremes raise it via the
    _twopass_vmem_bytes estimate (the guard in _fwd already capped it
    at the 64 MB measured-safe ceiling)."""
    BH, T, d = q.shape
    kvmap = _fwd_kvmap(causal, bq, bk)
    qmap = lambda b, i, j: (b, i, 0)  # noqa: E731 — mirrors kvmap

    lse = pl.pallas_call(
        functools.partial(_fwd_stats_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=bq, block_k=bk,
                          nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), qmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), kvmap, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bq, 1), qmap,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, T, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(q, k)

    est = _twopass_vmem_bytes(T, d, bq, bk, q.dtype.itemsize)
    params = dict(
        dimension_semantics=('parallel', 'parallel', 'arbitrary'))
    if est > 16 * 1024 * 1024:
        # only raise the scoped-vmem request past the compiler default
        # when the estimate says we must (forced-block extremes);
        # shrinking Mosaic's budget below the default would be a
        # self-inflicted double-buffering starve
        params['vmem_limit_bytes'] = est
    o = pl.pallas_call(
        functools.partial(_fwd_acc_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=bq, block_k=bk,
                          nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), qmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), kvmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), kvmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), qmap, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bq, d), qmap,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(**params),
        interpret=interpret,
    )(q, k, v, lse)
    return o, lse


@functools.partial(jax.jit, static_argnames=('causal', 'sm_scale',
                                             'interpret'))
def _bwd(q, k, v, o, lse, do, causal, sm_scale, interpret=False):
    """(dq, dk, dv). lse is [BH, 1, T] rows (_fwd's lse_rows layout): a
    caller that holds columns reshapes at this boundary, as the ring
    does. delta is made as rows too, and every arm fetches both as
    (1, 1, bq) blocks: 2 KB where a (bq, 1) column block was lane-padded
    to 256 KB, and no [BH, T, 1] array (67 MB padded at BH=64, T=2048)
    is written or relaid out anywhere."""
    BH, T, d = q.shape
    bq, bk = _block_sizes(T, d)
    if bq % 128 and not interpret:
        raise ValueError('flash backward: block_q=%d does not tile the '
                         '128 lanes a row of lse is fetched by' % bq)
    nq, nk = T // bq, T // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(BH, 1, T)

    # Arm selection: forced via PADDLE_FLASH_BWD, else kv-major (the
    # ranking is in the arm comment at the top). Residency guards:
    # onepass needs its dk/dv full-sequence fp32 accumulators +
    # resident outputs to fit (T=8k/d=128 ~ 18 MB with the raised
    # scoped-vmem limit); kvmajor guards its whole scoped-VMEM request
    # (dq accumulator + resident output + blocks) against a 64 MB
    # ceiling — T=64k/d=128 (~57 MB) measured compile-able on v5e,
    # so single-chip shapes through 64k keep the fast arm and only
    # beyond does split take over.
    arm = _FORCE_ARM or 'kvmajor'
    kv_bytes = 2 * T * d * (4 + k.dtype.itemsize)
    if arm == 'onepass' and kv_bytes > 12 * 1024 * 1024:
        arm = 'split'
    if arm == 'kvmajor' and _kvmajor_vmem_bytes(
            T, d, bq, bk, q.dtype.itemsize) > 64 * 1024 * 1024:
        arm = 'split'
    global _RESOLVED_ARM, _RESOLVED_BWD_BLOCKS
    _RESOLVED_ARM, _RESOLVED_BWD_BLOCKS = arm, (bq, bk)
    _BWD_SCHEDULE[arm].inc()
    return {'kvmajor': _bwd_kvmajor, 'split': _bwd_split,
            'onepass': _bwd_onepass}[arm](
        q, k, v, do, lse, delta, causal, sm_scale, interpret,
        bq, bk, nq, nk)


def _vmem(block, index_map):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


def _bwd_onepass(q, k, v, do, lse, delta, causal, sm_scale, interpret,
                 bq, bk, nq, nk):
    BH, T, d = q.shape
    qspec = _vmem((1, bq, d), lambda b, i, j: (b, i, 0))
    kspec = _vmem((1, bk, d), lambda b, i, j: (b, j, 0))
    rowspec = _vmem((1, 1, bq), lambda b, i, j: (b, 0, i))
    # dk/dv blocks span the whole sequence, index-mapped constant: one
    # live buffer, flushed once at the end
    wholespec = _vmem((1, T, d), lambda b, i, j: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_onepass_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=bq, block_k=bk,
                          nq=nq, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=[qspec, wholespec, wholespec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, d), q.dtype),
            jax.ShapeDtypeStruct((BH, T, d), k.dtype),
            jax.ShapeDtypeStruct((BH, T, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((nk, bk, d), jnp.float32),
                        pltpu.VMEM((nk, bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary'),
            # T=8192/d=128 needs ~18 MB (8 MB fp32 accumulators + 4 MB
            # resident outputs + double-buffered blocks) — above the
            # compiler's 16 MB scoped-vmem default, within the
            # hardware's capacity
            vmem_limit_bytes=_onepass_vmem_bytes(
                T, d, bq, bk, k.dtype.itemsize)),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _bwd_split(q, k, v, do, lse, delta, causal, sm_scale, interpret,
               bq, bk, nq, nk):
    """Two-kernel backward (a dq sweep, a dk/dv sweep: every pair's
    tiles are computed twice): what kv-major falls back to when its
    resident dq block would not fit, and 1.71 ms against 1.25 at the
    training cells' shape (PERF.md section 6, PR 41)."""
    BH, T, d = q.shape
    qspec = _vmem((1, bq, d), lambda b, i, j: (b, i, 0))
    kspec = _vmem((1, bk, d), lambda b, i, j: (b, j, 0))
    rowspec = _vmem((1, 1, bq), lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    qspec = _vmem((1, bq, d), lambda b, j, i: (b, i, 0))
    kspec = _vmem((1, bk, d), lambda b, j, i: (b, j, 0))
    rowspec = _vmem((1, 1, bq), lambda b, j, i: (b, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, nq=nq),
        grid=(BH, nk, nq),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=[kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, d), k.dtype),
            jax.ShapeDtypeStruct((BH, T, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _bwd_kvmajor(q, k, v, do, lse, delta, causal, sm_scale, interpret,
                 bq, bk, nq, nk):
    """Single-launch 5-matmul backward with dq (not dk/dv) as the
    resident accumulator — see _bwd_kvmajor_kernel. k/v blocks are
    indexed by the middle grid dim, so Mosaic fetches them once per ki
    row; q-side blocks stream per step as in the split dkv kernel."""
    BH, T, d = q.shape

    def first_qi(j, i):
        # During causally-skipped steps (i < first_qi(j)) clamp the
        # q-side fetch to the first visited block: the block index is
        # then unchanged step-to-step, so Mosaic elides the dead DMA.
        # (_CLAMP_SKIPPED_DMA is the trace-time A/B hook.)
        if causal and _CLAMP_SKIPPED_DMA:
            i = jnp.maximum(i, (j * bk) // bq)
        return i

    qspec = _vmem((1, bq, d), lambda b, j, i: (b, first_qi(j, i), 0))
    kspec = _vmem((1, bk, d), lambda b, j, i: (b, j, 0))
    rowspec = _vmem((1, 1, bq), lambda b, j, i: (b, 0, first_qi(j, i)))
    # dq's block spans the whole sequence, index-mapped constant: one
    # live buffer, flushed once at the end
    wholespec = _vmem((1, T, d), lambda b, j, i: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kvmajor_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=bq, block_k=bk,
                          nq=nq, nk=nk),
        grid=(BH, nk, nq),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=[wholespec, kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, d), q.dtype),
            jax.ShapeDtypeStruct((BH, T, d), k.dtype),
            jax.ShapeDtypeStruct((BH, T, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((nq, bq, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary'),
            vmem_limit_bytes=_kvmajor_vmem_bytes(
                T, d, bq, bk, q.dtype.itemsize)),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, sm_scale, interpret):
    o, _ = _fwd(q, k, v, causal, sm_scale, interpret, lse_rows=True)
    return o


def _flash_fwd(q, k, v, causal, sm_scale, interpret):
    o, lse = _fwd(q, k, v, causal, sm_scale, interpret, lse_rows=True)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, sm_scale, interpret, res, g):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd(q, k, v, o, lse, g, causal, sm_scale, interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _supported(T, d):
    return T % 128 == 0 and d % 128 == 0 and T >= 128


def flash_attention(q, k, v, causal=True, sm_scale=None,
                    force_naive=False):
    """softmax(q·kᵀ·scale [+ causal mask])·v without materializing the
    [T, T] scores. q, k, v: [B, H, T, d] (or [BH, T, d]). The Pallas
    kernel runs on a TPU, or off-TPU in interpreter mode when
    FLAGS_pallas_interpret is set (CPU numerics tests). The naive XLA
    contraction takes every other call: shapes the kernel does not tile
    (T or d not lane-aligned), a non-TPU backend without the flag, and
    force_naive (the FLAGS_use_flash_attention=false path — same entry
    point so both flag states accept the same layouts). Each call bumps
    `pallas.flash.kernel` or `pallas.flash.naive` at trace time."""
    squeeze = False
    if q.ndim == 4:
        B, H, T, d = q.shape
        qf = q.reshape(B * H, T, d)
        kf = k.reshape(B * H, T, d)
        vf = v.reshape(B * H, T, d)
    else:
        qf, kf, vf = q, k, v
        T, d = q.shape[-2:]
        squeeze = True
    scale = float(sm_scale) if sm_scale is not None else d ** -0.5

    from ..flags import get_flag
    on_tpu = jax.default_backend() == 'tpu'
    if (not force_naive) and _supported(T, d) and (
            on_tpu or bool(get_flag('pallas_interpret'))):
        _ROUTE_KERNEL.inc()
        out = _flash(qf, kf, vf, causal, scale, not on_tpu)
    else:
        _ROUTE_NAIVE.inc()
        out = _naive(qf, kf, vf, causal, scale)
    if not squeeze:
        out = out.reshape(q.shape)
    return out


def _naive(q, k, v, causal, scale):
    s = jnp.einsum('btd,bsd->bts', q * jnp.asarray(scale, q.dtype), k,
                   preferred_element_type=jnp.float32)
    if causal:
        T = q.shape[-2]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bts,bsd->btd', p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
