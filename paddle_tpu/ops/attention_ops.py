"""Attention ops. ring_attention: context-parallel attention over the
'sp' mesh axis (parallel/ring_attention.py design notes). Under a plain
single-device Executor (no mesh) it lowers to ordinary fused attention,
so programs are portable between local debugging and sp meshes.

KV-cache ops (serving/): static-shape page-pool primitives for the
paged prefill/decode/verify programs (models/transformer.py builders).
Every shape is fixed at build time — slots, pages, heads — so the
decode step compiles once for the life of the server; per-slot
positions and page tables are feeds, and validity is expressed as
masking (the beam-search lattice idiom), never as a dynamic shape."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..registry import register_op, op_emitter, register_vjp_grad, \
    amp_cast


@op_emitter('ring_attention')
def _ring_attention_emit(ctx, op):
    from ..parallel.ring_attention import (ring_attention_global,
                                           ring_flash_attention_global)
    from ..flags import get_flag
    q = ctx.get(op.single_input('Q'))
    k = ctx.get(op.single_input('K'))
    v = ctx.get(op.single_input('V'))
    q, k, v = amp_cast(ctx, q, k, v)
    causal = op.attr('causal', True)
    sm_scale = op.attr('sm_scale', None)
    if get_flag('use_flash_attention'):
        # ring x flash: per-block work through the Pallas kernel —
        # the [Tl, Tl] score block never exists (parity-tested in
        # tests/test_ring_flash.py; falls back per-block to XLA math
        # for lane-unaligned shard shapes)
        out = ring_flash_attention_global(
            q, k, v, getattr(ctx, 'mesh', None), causal=causal,
            sm_scale=sm_scale)
    else:
        out = ring_attention_global(q, k, v, getattr(ctx, 'mesh', None),
                                    causal=causal, sm_scale=sm_scale)
    ctx.set(op.single_output('Out'), out)


def _ring_infer(op, block):
    q = block.var_recursive(op.single_input('Q'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = q.shape
    out.dtype = q.dtype
    out.lod_level = q.lod_level


register_op('ring_attention', infer_shape=_ring_infer)
register_vjp_grad('ring_attention', in_slots=('Q', 'K', 'V'))


@op_emitter('flash_attention')
def _flash_attention_emit(ctx, op):
    """Flash attention over the full sequence on each device
    (paddle_tpu/pallas/flash_attention — blockwise online-softmax
    kernel; measured on v5e: 2.1x over the naive XLA contraction at
    T=4k and the only path that runs at T>=8k, where the [T, T] score
    tensor exceeds HBM).

    Under a multi-device mesh the kernel runs per shard: JAX refuses to
    lower a bare Mosaic call there ("Mosaic kernels cannot be
    automatically partitioned"), and attention is independent per
    (batch, head), so the batch splits over dp and the heads over tp
    exactly as the ring op's operands do. A sequence sharded over sp is
    gathered to full length here; ring_attention is the op for that."""
    from ..pallas.flash_attention import flash_attention as _fa
    from ..flags import get_flag
    q = ctx.get(op.single_input('Q'))
    k = ctx.get(op.single_input('K'))
    v = ctx.get(op.single_input('V'))
    q, k, v = amp_cast(ctx, q, k, v)
    fa = functools.partial(
        _fa, causal=op.attr('causal', True),
        sm_scale=op.attr('sm_scale', None),
        force_naive=not get_flag('use_flash_attention'))
    mesh = getattr(ctx, 'mesh', None)
    if mesh is not None and mesh.size > 1:
        from jax import shard_map
        from ..parallel.ring_attention import _ring_spec
        spec, _ = _ring_spec(mesh, q, None, 'dp', 'tp')
        # check_vma=False: pallas_call outputs carry no varying-mesh-
        # axes annotation (as in ring_flash_attention_global)
        fa = shard_map(fa, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    ctx.set(op.single_output('Out'), fa(q, k, v))


register_op('flash_attention', infer_shape=_ring_infer)
register_vjp_grad('flash_attention', in_slots=('Q', 'K', 'V'))


# ---------------------------------------------------------------------------
# KV-cache primitives (paddle_tpu/serving/)
# ---------------------------------------------------------------------------

@op_emitter('position_embedding_at')
def _position_embedding_at_emit(ctx, op):
    """Gather one positional-embedding row per slot: Pos [max_len, D],
    Index [slots] int32 -> [slots, 1, D] (table row Index % T_pos). A
    2-D Index
    [slots, R] gathers a row per (slot, row) -> [slots, R, D] — the
    verify program's per-proposal positions."""
    pos = ctx.get(op.single_input('Pos'))
    idx = ctx.get(op.single_input('Index')).astype(jnp.int32)
    out = jnp.take(pos, idx % pos.shape[0], axis=0)
    if idx.ndim == 1:
        out = out[:, None, :]
    ctx.set(op.single_output('Out'), out)


@op_emitter('gather_time')
def _gather_time_emit(ctx, op):
    """Per-row gather along the time axis: X [B, T, ...], Index [B]
    int32 -> [B, ...] (row b keeps X[b, Index[b]]). Prefill uses this to
    pick each prompt's last real position before the lm_head, so padded
    tail positions never reach the logits."""
    x = ctx.get(op.single_input('X'))
    idx = ctx.get(op.single_input('Index')).astype(jnp.int32)
    rows = jnp.arange(x.shape[0], dtype=jnp.int32)
    ctx.set(op.single_output('Out'), x[rows, jnp.clip(idx, 0, x.shape[1] - 1)])


# ---------------------------------------------------------------------------
# Paged KV-cache primitives (serving/paging.py + serving/paged.py)
#
# A page-indexed address space: one [num_pages, page_tokens, H, dk]
# pool per layer, a per-slot page TABLE (a feed) mapping logical
# position j to pool[table[j // pt], j % pt]. Physical page 0 is
# RESERVED as the null
# page: never allocated, the redirect target for dead rows and
# unpopulated table entries, always masked on read — so every slot can
# be written every step (the static-shape contract) without liveness
# ever becoming a shape question. Validity is absolute (j <= position):
# pages are allocated on demand rather than wrapped, which is what lets
# exhaustion surface as a typed host-side error instead of a silent
# slide (COVERAGE divergence 8).
#
# Who copies a forked page. The PREFILL and VERIFY programs hold one
# kv_page_cow in front of every pool's write, fed a pair (or a null
# pair) every call. The DECODE program holds none: the host dispatches
# the page copy program (models/transformer.build_page_copy_program:
# this op alone, once a pool) in front of a decode step whose table
# forked a page, and in front of no other step (serving/paged.py).
# ---------------------------------------------------------------------------

@op_emitter('kv_page_cow')
def _kv_page_cow_emit(ctx, op):
    """Copy-on-write page copies: Pool [N, pt, H, dk], Src [n] int32,
    Dst [n] int32 -> pool with pool[dst[i]] = pool[src[i]]. All sources
    are read before any destination is written (functional scatter), so
    a page freed and reallocated within the same step still donates its
    pre-step contents. (0, 0) pairs are the no-op padding — the null
    page copied onto itself: in the prefill and verify programs it
    keeps COW inside the ONE compiled program whether or not any fork
    happened in the call; in the page copy program it fills the pairs
    a forking decode step does not use.

    attr `page_rows` (the page copy program sets it): where a page's
    [H, dk] face is not whole (8, 128) tiles, the page moves as
    [pt * H, dk] rows, the same bytes in the same order. As [pt, H, dk]
    pages the TPU compiler relays a pool of few K/V heads out and back
    around the gather and the scatter ([8192, 16, 2, 128]: 134 MB three
    times a call, compiled for a v5e); as rows it updates the donated
    pool in place. A pool of 16 or 32 heads is updated in place as it
    is, and that form compiles in half the time (48 pools: 0.47 s
    against 0.88). Not on a mesh, where the heads axis is sharded and
    stays an axis of its own."""
    pool = ctx.get(op.single_input('Pool'))
    src = ctx.get(op.single_input('Src')).astype(jnp.int32)
    dst = ctx.get(op.single_input('Dst')).astype(jnp.int32)
    if op.attr('page_rows') and ctx.mesh is None and pool.ndim == 4 \
            and pool.shape[2] % 8:
        rows = pool.reshape(pool.shape[0], -1, pool.shape[-1])
        ctx.set(op.single_output('Out'),
                rows.at[dst].set(rows[src]).reshape(pool.shape))
        return
    ctx.set(op.single_output('Out'), pool.at[dst].set(pool[src]))


@op_emitter('state_row_copy')
def _state_row_copy_emit(ctx, op):
    """One row from one array into another: Src [A, ...], Pool [B, ...]
    (rows of the same shape), From [1], To [1] int32 -> Pool with
    Pool[To] = Src[From]. What moves a slot's recurrent state to a
    snapshot row and back (models/transformer.build_state_copy_programs):
    Pool is donated and updated where it lies, Src is only read, so a
    copy moves one row's bytes each way and nothing else."""
    src = ctx.get(op.single_input('Src'))
    pool = ctx.get(op.single_input('Pool'))
    at = ctx.get(op.single_input('From')).astype(jnp.int32).reshape(())
    to = ctx.get(op.single_input('To')).astype(jnp.int32).reshape(())
    row = jax.lax.dynamic_index_in_dim(src, at, axis=0, keepdims=True)
    ctx.set(op.single_output('Out'),
            jax.lax.dynamic_update_index_in_dim(pool, row.astype(pool.dtype),
                                                to, axis=0))


@op_emitter('kv_page_write')
def _kv_page_write_emit(ctx, op):
    """Chunked prefill: scatter a chunk's K or V rows through one page
    table. Pool [N, pt, H, dk], X [1, C, H, dk], Table [1, P] int32,
    Positions [C] int32 (absolute position of each chunk row), Len [1]
    int32 (live rows; rows >= Len are padding). Row i lands at
    pool[table[positions[i] // pt], positions[i] % pt]; dead rows are
    redirected to the null page at offset 0, where their identical
    duplicate scatters are deterministic and never read unmasked."""
    pool = ctx.get(op.single_input('Pool'))
    x = ctx.get(op.single_input('X'))
    table = ctx.get(op.single_input('Table')).astype(jnp.int32).reshape(-1)
    positions = ctx.get(op.single_input('Positions')).astype(jnp.int32)
    length = ctx.get(op.single_input('Len')).astype(jnp.int32).reshape(-1)
    pt, P = pool.shape[1], table.shape[0]
    live = jnp.arange(positions.shape[0], dtype=jnp.int32) < length[0]
    page = jnp.where(live, table[jnp.clip(positions // pt, 0, P - 1)], 0)
    off = jnp.where(live, positions % pt, 0)
    rows = x.reshape((-1,) + x.shape[2:]).astype(pool.dtype)
    ctx.set(op.single_output('Out'), pool.at[page, off].set(rows))


@op_emitter('kv_page_append')
def _kv_page_append_emit(ctx, op):
    """Decode: append one K or V row per slot through its page table.
    Pool [N, pt, H, dk], X [slots, 1, H, dk], Table [slots, P] int32,
    Positions [slots] int32 (absolute position of the incoming token).
    Every slot writes every step — idle or mid-prefill slots are fed an
    all-zero table row and position 0, so their writes land in the null
    page. With 2-D
    Positions [slots, R] and X [slots, R, H, dk], R rows are appended
    per slot in one shot — the speculative verify pass's multi-token
    append."""
    pool = ctx.get(op.single_input('Pool'))
    x = ctx.get(op.single_input('X'))
    table = ctx.get(op.single_input('Table')).astype(jnp.int32)
    positions = ctx.get(op.single_input('Positions')).astype(jnp.int32)
    pt, P = pool.shape[1], table.shape[1]
    if positions.ndim == 2:
        # verify: R rows per slot in one append — X [slots, R, H, dk],
        # Positions [slots, R]. Distinct live positions never collide;
        # padding rows carry an out-of-range position (>= P * pt) and
        # are redirected to the always-masked null page, so a slot
        # proposing fewer than R tokens never scribbles on real pages.
        srow = jnp.arange(table.shape[0], dtype=jnp.int32)[:, None]
        idx = positions // pt
        live = idx < P
        page = jnp.where(live, table[srow, jnp.clip(idx, 0, P - 1)], 0)
        off = jnp.where(live, positions % pt, 0)
        ctx.set(op.single_output('Out'),
                pool.at[page, off].set(x.astype(pool.dtype)))
        return
    rows = jnp.arange(table.shape[0], dtype=jnp.int32)
    page = table[rows, jnp.clip(positions // pt, 0, P - 1)]
    ctx.set(op.single_output('Out'),
            pool.at[page, positions % pt].set(x[:, 0].astype(pool.dtype)))


def _gather_pages(pool, table):
    """pool[table] as each row's dense sequence: [B, P*pt, H, dk]."""
    B, P = table.shape
    return pool[table].reshape(B, P * pool.shape[1],
                               pool.shape[2], pool.shape[3])


def _mask_after(x, positions, window=0):
    """x [slots, H, 1, J] with the columns j > positions[s] set to
    -1e9 and, with a `window`, those at or before positions[s] - window
    too."""
    j = jnp.arange(x.shape[-1], dtype=jnp.int32)
    valid = j[None, :] <= positions[:, None]           # [slots, J]
    if window:
        valid &= j[None, :] > positions[:, None] - window
    valid = valid[:, None, None, :]                    # [slots, 1, 1, J]
    return jnp.where(valid, x, -1e9)


@op_emitter('kv_page_gather')
def _kv_page_gather_emit(ctx, op):
    """Assemble each row's logical K or V sequence from the pool:
    Pool [N, pt, H, dk], Table [B, P] int32 -> [B, P*pt, H, dk], the
    whole window of every row as a dense cache that matmul + mask +
    softmax + matmul contract over. The prefill and verify programs
    attend this way (a prefill chunk gathers one row); the decode
    program does not gather: its `paged_attention` reads the live pages
    in place. Unpopulated table entries gather the null page — garbage
    that the paged masks set to -1e9 before the softmax."""
    pool = ctx.get(op.single_input('Pool'))
    table = ctx.get(op.single_input('Table')).astype(jnp.int32)
    ctx.set(op.single_output('Out'), _gather_pages(pool, table))


@op_emitter('paged_decode_mask')
def _paged_decode_mask_emit(ctx, op):
    """Validity mask for paged decode scores: X [slots, H, 1, J]
    (J = P*pt gathered positions), Positions [slots]. The page table is
    an absolute address space — logical index j holds the token at
    position j, valid iff j <= positions[s] (the token being appended
    this step included). Same set-to--1e9 semantics as the
    causal_mask op, so masked lanes underflow to exactly 0.0
    after the softmax's exp — the bit-exactness contract. It is the
    mask inside paged_attention's reference lowering."""
    x = ctx.get(op.single_input('X'))
    positions = ctx.get(op.single_input('Positions')).astype(jnp.int32)
    ctx.set(op.single_output('Out'), _mask_after(x, positions))


def _paged_attention_reference(q, k_pool, v_pool, table, positions,
                               sm_scale, pin, window=0):
    """The composition the decode program held before this op, op for
    op (kv_page_gather, transpose, matmul with alpha, paged_decode_mask,
    softmax, matmul): the arithmetic every bit-exact serving contract
    was written against. `pin` places the heads axis on a mesh."""
    qt = pin(jnp.transpose(q, (0, 2, 1, 3)))                   # [S,H,1,dh]
    kt = pin(jnp.transpose(_gather_pages(k_pool, table), (0, 2, 1, 3)))
    vt = pin(jnp.transpose(_gather_pages(v_pool, table), (0, 2, 1, 3)))
    rep = qt.shape[1] // kt.shape[1]
    if rep > 1:                 # query head h reads K/V head h // rep
        kt, vt = jnp.repeat(kt, rep, axis=1), jnp.repeat(vt, rep, axis=1)
    scores = jnp.matmul(qt, jnp.swapaxes(kt, -1, -2)) * sm_scale
    scores = _mask_after(scores, positions, window)            # [S,H,1,J]
    probs = jax.nn.softmax(scores.astype(jnp.float32),
                           axis=-1).astype(scores.dtype)
    return jnp.transpose(jnp.matmul(probs, vt), (0, 2, 1, 3))


@op_emitter('paged_window_attention')
@op_emitter('paged_attention')
def _paged_attention_emit(ctx, op):
    """One decode step's attention through the page tables: Q
    [S, 1, H, dh], KPool / VPool [N, pt, KVH, dh] (KVH divides H: query
    head h reads K/V head h // (H / KVH)), Table [S, P] int32,
    Positions [S] int32, attrs sm_scale, head_axis and window -> Out
    [S, 1, H, dh]. Lane s attends to its logical positions
    0..Positions[s] (the row appended this step included), which the
    table maps to pool[Table[s, j // pt], j % pt]; with a `window` (0:
    none) to the last `window` of them, max(0, Positions[s] - window +
    1)..Positions[s], and the table's entries before the page that holds
    the first are never read (the table and the positions of a sliding
    layer count from the first page its stream still holds:
    serving/paging.py; such a layer's op goes by the type
    `paged_window_attention`, the same emitter under a name of its own,
    so that a device trace tells its calls from the full layers').
    Idle and mid-prefill
    lanes arrive with a zero table row and position 0: they read the
    null page's first row and their output is discarded downstream.

    Two lowerings of the one sum, chosen by what is there to see. On a
    TPU (or under FLAGS_pallas_interpret), for pages the kernel tiles
    (pallas/paged_attention.supported), the Pallas kernel reads each
    lane's live pages out of the pool and nothing else; on a mesh it
    runs per shard of the heads axis, as flash_attention does.
    Everywhere else the reference composition gathers the window.
    Pools whose rows are wider than Q's heads hold several K/V heads a
    row (two heads of 64: [N, pt, KVH / 2, 128]): the kernel reads them
    as pairs (pallas/paged_attention.paged_attention_d64), the
    reference as the [N, pt, KVH, dh] they are."""
    from ..pallas import paged_attention as _pa
    from ..flags import get_flag
    q = ctx.get(op.single_input('Q'))
    k_pool = ctx.get(op.single_input('KPool'))
    v_pool = ctx.get(op.single_input('VPool'))
    table = ctx.get(op.single_input('Table')).astype(jnp.int32)
    positions = ctx.get(op.single_input('Positions')).astype(jnp.int32)
    sm_scale = op.attr('sm_scale')
    window = int(op.attr('window', 0))
    mesh = getattr(ctx, 'mesh', None)
    axis = op.attr('head_axis', '')
    if mesh is None or axis not in mesh.axis_names \
            or q.shape[2] % mesh.shape[axis] \
            or k_pool.shape[2] % mesh.shape[axis]:
        axis = None
    on_tpu = jax.default_backend() == 'tpu'
    pack = k_pool.shape[3] // q.shape[3]
    if pack > 1:
        # heads narrower than a lane row lie `pack` to a row of the pool
        # (DecodeSpec.head_pack; Q keeps the model's heads)
        if window or (mesh is not None and mesh.size > 1):
            raise NotImplementedError(
                'paged attention over packed heads with a window or a mesh')
        if pack == 2 and _pa.supported(k_pool.shape[1], q.shape[3]) and (
                on_tpu or bool(get_flag('pallas_interpret'))):
            out = _pa.paged_attention_d64(
                q[:, 0], k_pool, v_pool,
                jnp.clip(table, 0, k_pool.shape[0] - 1), positions,
                sm_scale=sm_scale, interpret=not on_tpu)[:, None]
        else:
            heads = k_pool.shape[:2] + (k_pool.shape[2] * pack, q.shape[3])
            out = _paged_attention_reference(
                q, k_pool.reshape(heads), v_pool.reshape(heads), table,
                positions, sm_scale, lambda x: x)
    elif _pa.supported(k_pool.shape[1], k_pool.shape[3]) and (
            on_tpu or bool(get_flag('pallas_interpret'))):
        kernel = functools.partial(_pa.paged_attention, sm_scale=sm_scale,
                                   interpret=not on_tpu)
        if window:
            kernel = functools.partial(kernel, window=window)
        if mesh is not None and mesh.size > 1:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            lane, pool = P(None, axis, None), P(None, None, axis, None)
            kernel = shard_map(kernel, mesh=mesh,
                               in_specs=(lane, pool, pool, P(), P()),
                               out_specs=lane, check_vma=False)
        out = kernel(q[:, 0], k_pool, v_pool,
                     jnp.clip(table, 0, k_pool.shape[0] - 1),
                     positions)[:, None]
    elif mesh is None:
        out = _paged_attention_reference(q, k_pool, v_pool, table,
                                         positions, sm_scale, lambda x: x,
                                         window)
    else:
        from ..parallel.mesh import named_sharding
        pin = functools.partial(
            jax.lax.with_sharding_constraint,
            shardings=named_sharding(mesh, (None, axis, None, None)))
        out = _paged_attention_reference(q, k_pool, v_pool, table,
                                         positions, sm_scale, pin, window)
    ctx.set(op.single_output('Out'), out)


@op_emitter('paged_block_attention')
def _paged_block_attention_emit(ctx, op):
    """A block step's attention through the page tables: Q [S, R, H, dh]
    (R rows a lane: a block of a model that generates by diffusion over
    blocks), KPool / VPool [N, pt, KVH, dh], Table [S, P] int32,
    Positions [S] int32 (the position of each lane's LAST block row),
    attr sm_scale -> Out [S, R, H, dh]. Every row of lane s attends to
    the lane's logical positions 0..Positions[s]: the committed pages
    and all R rows of its own block, which the program wrote through
    the table just before. An op of its own beside paged_attention, not
    a `rows` attribute of it: every existing caller's op and kernel call
    stay letter for letter what they were, and a device trace tells a
    block step's attention from a decode step's by name.

    Because every row of a lane sees the same keys, the R rows are to
    the kernel what the H / KVH query heads of a K/V head already are:
    more rows of the one product against pages that are read once. On a
    TPU (or under FLAGS_pallas_interpret), for pages the kernel tiles,
    q is regrouped to [S, KVH * (H / KVH * R), dh] and goes through the
    kernel of pallas/paged_attention.py under the name
    `paged_block_attention`; everywhere else through the reference
    composition (gather, mask after Positions, softmax), which is the
    CPU's path and the tests' truth. R = 1 is paged_attention's sum."""
    from ..pallas import paged_attention as _pa
    from ..flags import get_flag
    q = ctx.get(op.single_input('Q'))
    k_pool = ctx.get(op.single_input('KPool'))
    v_pool = ctx.get(op.single_input('VPool'))
    table = ctx.get(op.single_input('Table')).astype(jnp.int32)
    positions = ctx.get(op.single_input('Positions')).astype(jnp.int32)
    sm_scale = op.attr('sm_scale')
    if getattr(ctx, 'mesh', None) is not None and ctx.mesh.size > 1:
        raise NotImplementedError('paged_block_attention on a mesh')
    on_tpu = jax.default_backend() == 'tpu'
    if _pa.supported(k_pool.shape[1], k_pool.shape[3]) and (
            on_tpu or bool(get_flag('pallas_interpret'))):
        S, R, H, dh = q.shape
        KVH = k_pool.shape[2]
        rep = H // KVH
        # query row r of head kv * rep + g -> row (g * R + r) of K/V
        # head kv's group
        grouped = jnp.transpose(q.reshape(S, R, KVH, rep, dh),
                                (0, 2, 3, 1, 4)).reshape(S, H * R, dh)
        out = _pa.paged_attention(
            grouped, k_pool, v_pool,
            jnp.clip(table, 0, k_pool.shape[0] - 1), positions,
            sm_scale=sm_scale, interpret=not on_tpu,
            name='paged_block_attention')
        out = jnp.transpose(out.reshape(S, KVH, rep, R, dh),
                            (0, 3, 1, 2, 4)).reshape(S, R, H, dh)
    else:
        out = _paged_attention_reference(q, k_pool, v_pool, table,
                                         positions, sm_scale, lambda x: x)
    ctx.set(op.single_output('Out'), out)


@op_emitter('spec_verify_mask')
def _spec_verify_mask_emit(ctx, op):
    """Causal validity mask for the speculative verify pass: X
    [slots, H, K1, J] scores (K1 = k proposals + the base token),
    Positions [slots, K1] (absolute position of each verify row).
    Row r of slot s may see logical index j iff j <= positions[s, r] —
    paged_decode_mask per row, paged_prefill_mask per slot. Same
    set-to--1e9 semantics so masked lanes underflow to exactly 0.0
    after the softmax's exp — the bit-exactness contract that makes
    verify-row logits identical to the plain decode step's at the same
    position over the same cache."""
    x = ctx.get(op.single_input('X'))
    positions = ctx.get(op.single_input('Positions')).astype(jnp.int32)
    j = jnp.arange(x.shape[-1], dtype=jnp.int32)
    valid = j[None, None, :] <= positions[:, :, None]  # [slots, K1, J]
    valid = valid[:, None, :, :]                       # [slots, 1, K1, J]
    ctx.set(op.single_output('Out'), jnp.where(valid, x, -1e9))


@op_emitter('paged_prefill_mask')
def _paged_prefill_mask_emit(ctx, op):
    """Causal mask for a prefill chunk against the gathered history:
    X [1, H, C, J] scores, Positions [C] (absolute position of each
    chunk row). Row i may see logical index j iff j <= positions[i] —
    plain causality expressed against the page-table address space, so
    a chunk attends to every previously written page plus its own
    already-written rows. With attr `window` (a sliding layer's; 0:
    none) a band: row i sees index j iff positions[i] - window < j <=
    positions[i]. With attr `block` (a model that generates by
    diffusion over blocks; 0: none, plain causality) row i sees index j
    iff j // block <= positions[i] // block: causal over blocks,
    bidirectional inside one. Padding rows carry garbage positions;
    their score rows are never gathered downstream. Without Positions
    the rows are a whole sequence's from its start (a saved model's
    form)."""
    x = ctx.get(op.single_input('X'))
    positions = ctx.get(op.single_input('Positions')).astype(jnp.int32) \
        if op.input('Positions') else jnp.arange(x.shape[-2], dtype=jnp.int32)
    j = jnp.arange(x.shape[-1], dtype=jnp.int32)
    block = int(op.attr('block', 0))
    if block:
        valid = j[None, :] // block <= positions[:, None] // block
    else:
        valid = j[None, :] <= positions[:, None]       # [C, J]
    window = int(op.attr('window', 0))
    if window:
        valid &= j[None, :] > positions[:, None] - window
    valid = valid[None, None, :, :]                    # [1, 1, C, J]
    ctx.set(op.single_output('Out'), jnp.where(valid, x, -1e9))


def _decode_mask_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = x.shape
    out.dtype = x.dtype


def _position_embedding_at_infer(op, block):
    pos = block.var_recursive(op.single_input('Pos'))
    idx = block.var_recursive(op.single_input('Index'))
    out = block.var_recursive(op.single_output('Out'))
    if len(idx.shape) == 2:
        out.shape = (idx.shape[0], idx.shape[1], pos.shape[-1])
    else:
        out.shape = (idx.shape[0], 1, pos.shape[-1])
    out.dtype = pos.dtype


def _gather_time_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = (x.shape[0],) + tuple(x.shape[2:])
    out.dtype = x.dtype


def _kv_pool_update_infer(op, block):
    pool = block.var_recursive(op.single_input('Pool'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = pool.shape
    out.dtype = pool.dtype


def _kv_page_gather_infer(op, block):
    pool = block.var_recursive(op.single_input('Pool'))
    table = block.var_recursive(op.single_input('Table'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = (table.shape[0], table.shape[1] * pool.shape[1],
                 pool.shape[2], pool.shape[3])
    out.dtype = pool.dtype


register_op('kv_page_cow', infer_shape=_kv_pool_update_infer,
            no_grad=True)
register_op('state_row_copy', infer_shape=_kv_pool_update_infer,
            no_grad=True)
register_op('kv_page_write', infer_shape=_kv_pool_update_infer,
            no_grad=True)
register_op('kv_page_append', infer_shape=_kv_pool_update_infer,
            no_grad=True)
register_op('kv_page_gather', infer_shape=_kv_page_gather_infer,
            no_grad=True)
register_op('paged_attention', infer_shape=_ring_infer,
            no_grad=True)
register_op('paged_window_attention', infer_shape=_ring_infer,
            no_grad=True)
register_op('paged_block_attention', infer_shape=_ring_infer,
            no_grad=True)
register_op('paged_decode_mask', infer_shape=_decode_mask_infer,
            no_grad=True)
register_op('paged_prefill_mask', infer_shape=_decode_mask_infer,
            no_grad=True)
register_op('spec_verify_mask', infer_shape=_decode_mask_infer,
            no_grad=True)
register_op('position_embedding_at', infer_shape=_position_embedding_at_infer,
            no_grad=True)
register_op('gather_time', infer_shape=_gather_time_infer, no_grad=True)
