"""The compiler options ParallelExecutor gives a TPU mesh program so its
gradient sums run asynchronously (parallel_executor._OVERLAP_OPTIONS):
who gets them (a mesh of more than one TPU device, observed from the
devices), who does not (virtual CPU devices, a mesh of one), that a dp
step is still the single-device step, and -- compiled here for a
described v5e:2x2, no chip -- that the options still do to the schedule
what they were added for."""
import os
import sys
import types

import numpy as np
import pytest

import jax
import paddle_tpu as fluid
from paddle_tpu import parallel_executor as pem
from paddle_tpu.executor import PreparedProgram, _DeviceSegment
from paddle_tpu.obs import telemetry

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'tools'))

from test_parallel_executor import _build  # noqa: E402


@pytest.fixture
def counting():
    was = telemetry.enabled()
    telemetry.enable()
    telemetry.reset()
    yield lambda: telemetry.snapshot()['counters']['parallel.overlap_compiles']
    if not was:
        telemetry.disable()


def _segment(prog, loss):
    prepared = PreparedProgram(prog, 0, ('x', 'y'), [loss.name])
    return next(s for s in prepared.steps if isinstance(s, _DeviceSegment))


def _fake(platform, n):
    return [types.SimpleNamespace(platform=platform) for _ in range(n)]


@pytest.mark.parametrize('n_devices', [2, 4, 8])
def test_virtual_cpu_mesh_takes_no_compiler_options(n_devices, counting):
    prog, startup, loss = _build()
    fluid.Executor(fluid.CPUPlace()).run(startup)
    pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                main_program=prog,
                                devices=jax.devices()[:n_devices])
    assert pe._overlap_options() is None
    options = pe._jit_options(_segment(prog, loss), ('x', 'y'))
    assert set(options) == {'in_shardings', 'out_shardings'}
    # and the step compiles and runs: the CPU compiler would refuse them
    out = pe.run(fetch_list=[loss.name],
                 feed={'x': np.ones((16, 8), 'float32'),
                       'y': np.ones((16, 1), 'float32')})
    assert np.isfinite(out[0]).all()
    assert counting() == 0


@pytest.mark.parametrize('platform,n,want', [
    ('tpu', 1, False),      # the one-chip cell: the parent's executable
    ('tpu', 2, True),
    ('tpu', 4, True),
    ('cpu', 4, False),
    ('gpu', 4, False),
])
def test_rule_reads_the_devices(platform, n, want, counting):
    """The condition is the devices' platform and number, nothing else:
    no flag, no environment variable, no BuildStrategy field."""
    prog, startup, loss = _build()
    pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                main_program=prog,
                                devices=jax.devices()[:1])
    plain = pe._jit_options(_segment(prog, loss), ('x', 'y'))
    assert set(plain) == {'in_shardings'} and counting() == 0
    pe._devices = _fake(platform, n)
    options = pe._jit_options(_segment(prog, loss), ('x', 'y'))
    if want:
        assert options['compiler_options'] == pem._OVERLAP_OPTIONS
        assert options['compiler_options'] is not pem._OVERLAP_OPTIONS
        assert counting() == 1
    else:
        assert set(options) == {'in_shardings'} and counting() == 0
    assert options['in_shardings'][2] == plain['in_shardings'][2]


def test_mixed_platform_mesh_takes_none():
    prog, startup, loss = _build()
    pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                main_program=prog,
                                devices=jax.devices()[:1])
    pe._devices = _fake('tpu', 2) + _fake('cpu', 2)
    assert pe._overlap_options() is None


def test_no_switch_exists():
    """Not configurable: nothing in the flag registry or the strategies
    names the rule."""
    from paddle_tpu import flags
    assert not [k for k in flags._FLAGS if 'overlap' in k.lower()
                or 'async' in k.lower() and 'reduce' in k.lower()]
    for obj in (fluid.BuildStrategy(), fluid.ExecutionStrategy()):
        assert not [k for k in vars(obj) if 'overlap' in k]


def test_dp4_gradients_match_single_device():
    """The existing parity (test_parallel_executor), one more case: the
    gradients themselves, over 4 devices."""
    rng = np.random.RandomState(7)
    xb = rng.randn(32, 8).astype('float32')
    yb = rng.randn(32, 1).astype('float32')

    def grads(run, prog, loss):
        names = [p.name + '@GRAD' for p in prog.global_block().vars.values()
                 if isinstance(p, fluid.framework.Parameter)]
        return names, run([loss.name] + names)

    prog, startup, loss = _build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        names, single = grads(
            lambda f: exe.run(prog, feed={'x': xb, 'y': yb}, fetch_list=f),
            prog, loss)
    prog2, startup2, loss2 = _build()
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(startup2)
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss2.name,
                                    main_program=prog2,
                                    devices=jax.devices()[:4])
        _, multi = grads(
            lambda f: pe.run(fetch_list=f, feed={'x': xb, 'y': yb}),
            prog2, loss2)
    assert len(names) == 4
    for name, a, b in zip(['loss'] + names, single, multi):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6, err_msg=name)


# -- compiled for a described v5e:2x2 (no chip) ------------------------------

# one block whose two feed-forward gradients are 32 MiB each in bf16 (the
# cell's [2048, 8192] are too): large enough to stand alone as sums
_WIDE = {'n_embd': 1024, 'n_head': 8, 'n_inner': 16384, 'n_positions': 128,
         'vocab_size': 512, 'n_layer': 1,
         'flags': {'FLAGS_amp_bf16_param_grads': True}}


@pytest.fixture(scope='module')
def v5e_2x2():
    import mesh_schedule
    try:
        devices = mesh_schedule.describe('v5e:2x2')
    except Exception as e:
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    from jax.experimental.compilation_cache import compilation_cache as cc
    from paddle_tpu import flags
    was = jax.config.jax_enable_compilation_cache
    flag_was = flags.get_flag('amp_bf16_param_grads')
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    yield devices
    flags.set_flags({'FLAGS_amp_bf16_param_grads': flag_was})
    jax.config.update('jax_enable_compilation_cache', was)
    cc.reset_cache()


def _rows(devices, per_step=8, options=None, overlap=True, replicated=False):
    import mesh_schedule
    from unittest import mock
    with fluid.unique_name.guard():
        program, loss = mesh_schedule.build_lm_step(_WIDE, per_step,
                                                    len(devices))
    with mock.patch.object(pem, '_OVERLAP_OPTIONS',
                           pem._OVERLAP_OPTIONS if options is None
                           else options):
        text = mesh_schedule.compile_step(
            program, [loss], devices, per_step, overlap=overlap,
            replicated=replicated).as_text()
    return mesh_schedule.list_collectives(text)


@pytest.fixture(scope='module')
def schedules(v5e_2x2):
    """{overlap: mesh_schedule.list_collectives' rows} of the dp step with
    every variable a replica (so that every collective is a gradient's
    sum), with the rule applied and with XLA's default schedule."""
    return {overlap: _rows(v5e_2x2, overlap=overlap, replicated=True)
            for overlap in (True, False)}


@pytest.fixture(scope='module')
def sharded_schedule(v5e_2x2):
    """The rows of the dp step as the executor places and compiles it:
    what an optimizer op updates a dp shard (state_sharding)."""
    return _rows(v5e_2x2)


def _big(rows):
    return [r for r in rows if r['bytes'] >= 32 << 20]


def test_tpu_mesh_counts_its_compiles(v5e_2x2, counting):
    _rows(v5e_2x2)
    assert counting() == 1
    _rows(v5e_2x2[:1], per_step=2)
    assert counting() == 1


def test_default_schedule_blocks_on_every_gradient_sum(schedules):
    rows = schedules[False]
    assert rows and all(r['kind'] == 'all-reduce' for r in rows)
    assert all(r['form'] == 'plain' for r in rows), rows


def test_rule_fuses_the_large_gradient_sums_into_compute(schedules):
    """The mark an upgrade of jax or libtpu must not silently lose: with
    the options a gradient of 32 MiB or more is summed by an async
    collective fusion that a compute fusion carries; by default none is."""
    big = _big(schedules[True])
    fused = [r for r in big if r['form'] == 'fused']
    # (the backward's last sum has no product left to ride: marked only)
    assert len(big) == 2 and fused, big
    assert all('mul_grad' in ' '.join(r['carriers']) for r in fused), fused
    assert not [r for r in schedules[True] if r['form'] == 'plain']
    assert not [r for r in schedules[False] if r['form'] == 'fused']


def test_rule_moves_no_bytes_and_no_dtype(schedules):
    """How the sums are scheduled, never what is summed: the same bytes in
    the same dtypes on both sides."""
    import mesh_schedule
    a = mesh_schedule.summarize(schedules[True])
    b = mesh_schedule.summarize(schedules[False])
    assert a['bytes_by_dtype'] == b['bytes_by_dtype']
    assert set(b['bytes_by_form']) == {'plain'}
    assert sum(a['bytes_by_form'].values()) == b['bytes_by_form']['plain']
    assert a['bytes_by_form']['fused'] >= 32 << 20


@pytest.mark.parametrize('option', sorted(pem._OVERLAP_OPTIONS))
def test_every_kept_option_is_needed(option, v5e_2x2):
    """An option that does nothing is not kept: without any one of them
    no sum of the compiled step rides a compute fusion."""
    rest = {k: v for k, v in pem._OVERLAP_OPTIONS.items() if k != option}
    if option == 'xla_tpu_enable_all_reduce_scatter_fusion':
        return      # its own case: test_sharded_update_keeps_the_gradient_sums
    rows = _rows(v5e_2x2, options=rest, replicated=True)
    assert rows and not [r for r in rows if r['form'] == 'fused'], \
        (option, rows)


def test_sharded_update_keeps_the_gradient_sums(v5e_2x2, schedules,
                                                sharded_schedule):
    """With its state held as dp shards the step sums its large
    gradients as the replicated step does (the same all-reduces in the
    same dtype, one riding a product), because the fourth option takes the
    compiler's own all-reduce-scatter fusion away: without the option a
    large gradient is summed in float32 by a custom fusion that blocks,
    which the tool lists as a plain reduce-scatter."""
    def big_sums(rows):
        return [(r['bytes'], tuple(r['dtypes']), r['form'])
                for r in _big(rows) if r['kind'] == 'all-reduce']
    assert not [r for r in sharded_schedule if r['kind'] == 'reduce-scatter']
    # (at this small batch GSPMD moves the activations of one product
    # and not its gradient: one sum fewer than the replicated step's)
    kept, was = big_sums(sharded_schedule), big_sums(schedules[True])
    assert kept and {k[:2] for k in kept} <= {w[:2] for w in was}, (kept, was)
    assert [k for k in kept if k[2] == 'fused'], kept
    rest = {k: v for k, v in pem._OVERLAP_OPTIONS.items()
            if k != 'xla_tpu_enable_all_reduce_scatter_fusion'}
    scattered = [r for r in _rows(v5e_2x2, options=rest)
                 if r['kind'] == 'reduce-scatter']
    assert _big(scattered), scattered
    assert all(r['form'] == 'plain' and set(r['dtypes']) == {'f32'}
               for r in _big(scattered)), scattered


def test_sharded_update_gathers_the_weights_after_the_cast(sharded_schedule):
    """A weight held as a float32 shard is gathered as bf16, after AMP's
    cast, and the large gathers ride a product (async collective
    fusions); nothing of float32 larger than a norm's gain or a bias is
    gathered."""
    gathers = [r for r in sharded_schedule if r['kind'] == 'all-gather']
    big = _big(gathers)
    assert big and all(set(r['dtypes']) == {'bf16'} for r in big), big
    fused = [r for r in big if r['form'] == 'fused']
    assert fused and all(
        'mul' in ' '.join(r['carriers']) for r in fused), big
    assert all(r['dtypes'].get('f32', 0) <= 1 << 20 for r in gathers)


def test_sharded_update_holds_a_quarter_of_the_state(v5e_2x2):
    """What a chip holds as arguments: the replicated step's masters and
    moments, a quarter of them under the rule."""
    import mesh_schedule
    sizes = {}
    for replicated in (True, False):
        with fluid.unique_name.guard():
            program, loss = mesh_schedule.build_lm_step(_WIDE, 8, 4)
        mem = mesh_schedule.compile_step(
            program, [loss], v5e_2x2, 8,
            replicated=replicated).memory_analysis()
        sizes[replicated] = mem.argument_size_in_bytes
    assert sizes[False] < 0.3 * sizes[True], sizes


def test_fresh_uncommitted_weights_and_gradient_fetches_compile(v5e_2x2):
    """What the benchmark's check runs after training: new weights put in
    the scope as plain (uncommitted) arrays, then the step with
    gradients fetched. State enters and leaves a dp mesh's step where
    state_sharding holds it; with only the outputs held there the TPU
    compiler refused the program ("Expected aliased input ... and
    output ... to have the same size": a donated weight it had laid out
    one way against an output held another)."""
    import mesh_schedule
    from paddle_tpu.framework import Parameter
    with fluid.unique_name.guard():
        program, loss = mesh_schedule.build_lm_step(_WIDE, 8, 4)
    params = [v.name for v in program.global_block().vars.values()
              if isinstance(v, Parameter)]
    fetch = [loss, params[2] + '@GRAD', params[-1] + '@GRAD']
    compiled = mesh_schedule.compile_step(program, fetch, v5e_2x2, 8,
                                          uncommitted=params)
    placed, _, _ = compiled.input_shardings[0]
    pe = fluid.ParallelExecutor(use_cuda=True, main_program=program,
                                devices=list(v5e_2x2))
    for name in params:
        assert placed[name].is_equivalent_to(
            pe.state_sharding(name),
            len(program.global_block().vars[name].shape)), name


# what the TPU compiler prints for a gradient summed into a shard by its
# own fusion (the dp step compiled without the fourth option), cut to
# the lines the tool reads
_SCATTER_HLO = """HloModule jit_seg_fn, is_scheduled=true

%add.1.clone (x: f32[], y: f32[]) -> f32[] {
  %x = f32[]{:T(128)} parameter(0)
  %y = f32[]{:T(128)} parameter(1)
  ROOT %add.9 = f32[]{:T(128)} add(%x, %y)
}

%all-reduce-scatter.7 (input.7: f32[2048,8192]) -> f32[512,8192] {
  %input.7 = f32[2048,8192]{1,0:T(8,128)S(1)} parameter(0)
  %all-reduce.36 = f32[2048,8192]{1,0:T(8,128)} all-reduce(%input.7), channel_id=85, replica_groups={{0,1,2,3}}, to_apply=%add.1.clone
  %partition-id.17 = u32[] partition-id()
  ROOT %dynamic-slice.80 = f32[512,8192]{1,0:T(8,128)S(1)} dynamic-slice(%all-reduce.36, %partition-id.17), dynamic_slice_sizes={512,8192}
}

ENTRY %main.1 (p0: bf16[8192,2048], p1: bf16[8192,8192]) -> f32[512,8192] {
  %p0 = bf16[8192,2048]{1,0} parameter(0)
  %p1 = bf16[8192,8192]{1,0} parameter(1)
  %convolution_bitcast_fusion = f32[2048,8192]{1,0:T(8,128)} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(seg_fn)/mul_grad.66/transpose(jvp())/dot_general"}
  %fusion.11 = f32[512,8192]{1,0:T(8,128)S(1)} fusion(%convolution_bitcast_fusion), kind=kCustom, calls=%all-reduce-scatter.7, metadata={op_name="jit(seg_fn)/mul_grad.66/transpose(jvp())/dot_general"}
  ROOT %divide_subtract_fusion.6 = f32[512,8192]{1,0} fusion(%fusion.11), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(seg_fn)/adam.133/sub"}
}
"""


def test_tool_lists_an_all_reduce_scatter_fusion():
    """A sum no instruction of the entry computation names: the tool
    lists it as a reduce-scatter that blocks, with the operand's bytes
    (what crosses the links) and dtype, the op that produced it and the
    op that waits for it."""
    import mesh_schedule
    row, = mesh_schedule.list_collectives(_SCATTER_HLO)
    assert (row['kind'], row['form'], row['name']) == \
        ('reduce-scatter', 'plain', 'fusion.11')
    assert row['bytes'] == 2048 * 8192 * 4 and row['dtypes'] == \
        {'f32': 2048 * 8192 * 4}
    assert row['scope'] == 'mul_grad.66'
    assert row['first_use']['scope'] == 'adam.133' and not row['between']
    summary = mesh_schedule.summarize([row])
    assert summary['bytes_by_kind_and_form'] == \
        {'reduce-scatter': {'plain': 2048 * 8192 * 4}}


def test_flash_forward_of_the_training_cells_compiles_for_v5e(v5e_2x2):
    """The forward both training cells run on each chip (4 sequences x 16
    heads, T=2048, d=128, bf16, causal), with the schedule and blocks the
    shape gets, lowered by Mosaic and compiled by the installed TPU
    compiler: a slice off the tiling, a layout it cannot change or too
    much VMEM is refused here, without a chip."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.pallas import flash_attention as fa
    x = jax.ShapeDtypeStruct((64, 2048, 128), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e_2x2[0]))
    fa._fwd.clear_cache()
    compiled = fa._fwd.lower(x, x, x, True, 128 ** -0.5, False).compile()
    assert fa._RESOLVED_FWD_ARM == 'online'
    assert fa._RESOLVED_FWD_BLOCKS == fa._BLOCK_TABLE_FWD[(2048, 128)]
    assert 'tpu_custom_call' in compiled.as_text()
    o, lse = compiled.out_info
    assert (o.shape, str(o.dtype)) == ((64, 2048, 128), 'bfloat16')
    assert (lse.shape, str(lse.dtype)) == ((64, 2048, 1), 'float32')


def test_flash_backward_of_the_training_cells_compiles_for_v5e(v5e_2x2):
    """... and the backward they run: the whole head one block of ten
    chunk pairs, lse and delta fetched as rows, inside the scoped VMEM
    the kv-major estimate asks for."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.pallas import flash_attention as fa
    one = SingleDeviceSharding(v5e_2x2[0])
    x = jax.ShapeDtypeStruct((64, 2048, 128), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((64, 1, 2048), jnp.float32, sharding=one)
    fa._bwd.clear_cache()
    compiled = fa._bwd.lower(x, x, x, x, lse, x, True, 128 ** -0.5,
                             False).compile()
    assert fa._RESOLVED_ARM == 'kvmajor'
    assert fa._RESOLVED_BWD_BLOCKS == fa._BLOCK_TABLE[(2048, 128)]
    assert 'tpu_custom_call' in compiled.as_text()
    assert [(g.shape, str(g.dtype)) for g in compiled.out_info] \
        == [((64, 2048, 128), 'bfloat16')] * 3


def test_kda_step_kernel_of_the_solar_cell_compiles_for_v5e(v5e_2x2):
    """The step kernel at the cell's shape (48 lanes, 64 heads of 128 x
    128, the decay a third column): lowered by Mosaic, compiled by the
    installed TPU compiler, the state aliased and not copied."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.pallas import gated_delta as gd
    one = SingleDeviceSharding(v5e_2x2[0])

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    state, col, row = arg(48, 64, 128, 128), arg(48, 64, 128), arg(48, 64)
    compiled = jax.jit(gd.kda_step, donate_argnums=0).lower(
        state, col, col, col, row, col, arg(48, dtype=jnp.bool_)).compile()
    assert 'tpu_custom_call' in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 48 * 64 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 64 << 20


def test_kda_chunk_of_the_solar_cell_compiles_for_v5e(v5e_2x2):
    """A prefill chunk of 256 tokens through the chunk form at the
    cell's widths: what it keeps beside its inputs stays far under a
    lane's state times the blocks (no [C, C, dk] tensor a block is
    materialised for all heads at once beyond a few hundred MB)."""
    import functools
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops import delta_rule_ops as dr
    one = SingleDeviceSharding(v5e_2x2[0])

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)

    compiled = jax.jit(functools.partial(dr.kda_chunk, block=64, sub=16)) \
        .lower(arg(64, 128, 128), arg(256, 64, 128), arg(256, 64, 128),
               arg(256, 64, 128), arg(256, 64), arg(256, 64, 128)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_block_attention_of_the_diffusion_cell_compiles_for_v5e(v5e_2x2):
    """The block step's attention at the cell's shape (32 lanes, 4 rows
    x 32 query heads = 128 rows a lane on 4 K/V heads of 128, a table of
    96 pages of 16 tokens over 3073): the rows folded into the head
    groups of pallas/paged_attention.py's kernel, lowered by Mosaic
    under its own name and compiled by the installed TPU compiler; it
    keeps nothing beside its arguments."""
    import functools
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.pallas import paged_attention as pa
    one = SingleDeviceSharding(v5e_2x2[0])

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = arg(3073, 16, 4, 128)
    compiled = jax.jit(functools.partial(
        pa.paged_attention, sm_scale=128 ** -0.5,
        name='paged_block_attention')).lower(
            arg(32, 128, 128), pool, pool, arg(32, 96, dtype=jnp.int32),
            arg(32, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert 'tpu_custom_call' in text and 'paged_block_attention' in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# the expert cells' step programs: tools/moe_experts_arms.CELLS (five decode
# steps of 32-64 rows, and sdar30b's block step of 32 slots x 4 rows)
_EXPERTS = ('axk1', 'granite4hs', 'nemo3s', 'sdar30b', 'solar2', 'sthink21b')


@pytest.mark.parametrize('cell', _EXPERTS)
def test_expert_kernel_of_a_decode_step_compiles_for_v5e(v5e_2x2, cell):
    """A step's rows through the held stack at each cell's widths
    (pallas/moe_experts.py): lowered by Mosaic and compiled by the
    installed TPU compiler with the tiles the widths give (the VMEM
    limit follows from them), the stack read where it lies: no
    temporary beside the kernel's own."""
    import functools
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    import moe_experts_arms
    from paddle_tpu.pallas import moe_experts as me
    assert sorted(moe_experts_arms.CELLS) == list(_EXPERTS)
    rows, L, F, held, matrices, act, _ = moe_experts_arms.CELLS[cell]
    assert me.step_supported(rows, L, F, held, matrices)
    one = SingleDeviceSharding(v5e_2x2[0])

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    up = arg(held, L, F)
    compiled = jax.jit(functools.partial(me.moe_experts, act=act)).lower(
        arg(rows, L), arg(rows, held), arg(held, dtype=jnp.int32),
        arg(1, dtype=jnp.int32), up, up if matrices == 3 else None,
        arg(held, F, L)).compile()
    assert compiled.as_text().count('tpu_custom_call') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize('cell', ['granite4hs', 'nemo3s'])
def test_ssd_step_kernel_of_a_decode_step_compiles_for_v5e(v5e_2x2, cell):
    """The Mamba-2 step at each cell's shape (pallas/ssd.py): a whole
    lane's 4 MiB a block, in and out and double-buffered, lowered by
    Mosaic and compiled by the installed TPU compiler under the VMEM
    limit the shapes give; the donated state is updated where it lies
    and what the caller lays out beside it is kilobytes a lane."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    import ssd_step_arms
    from paddle_tpu.pallas import ssd
    S, H, P, N, G, _ = ssd_step_arms.CELLS[cell]
    assert ssd.supported(H, P, G, N)
    one = SingleDeviceSharding(v5e_2x2[0])

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(ssd.ssd_step.__wrapped__, donate_argnums=(0,)).lower(
        arg(S, H, P, N), arg(S, H, P), arg(S, G, N), arg(S, G, N),
        arg(S, H), arg(S, H), arg(H), arg(S, dtype=jnp.bool_)).compile()
    assert compiled.as_text().count('tpu_custom_call') == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == S * H * P * N * 4
    assert mem.temp_size_in_bytes < 1 << 20


# -- the page copy program, compiled for one described chip ------------------
# (here because this file is the one that describes a TPU: see v5e_2x2)

# (pool shape, slots, pools) of the four serving cells' configurations
_POOLS = {'gpt1b3': ((1152, 16, 16, 128), 16, 48),
          'olmohyb': ((3584, 16, 32, 128), 32, 4),
          'nemo3s': ((8192, 16, 2, 128), 64, 2),
          'axk1': ((16384, 16, 640), 48, 5)}


@pytest.mark.parametrize('cell', sorted(_POOLS))
def test_page_copy_program_updates_the_donated_pools_in_place(v5e_2x2, cell):
    """What a forking decode step dispatches (serving/paged.py): at the
    cells' real pool shapes and counts the compiled module aliases
    every pool and holds no copy of one. Nemotron's [8192, 16, 2, 128]
    is the one the compiler relaid out and back (134 MB of temporaries a
    pool) when its pages moved as [pt, H, dk] and not as rows."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.models.transformer import build_page_copy_program
    shape, slots, n = _POOLS[cell]
    names = ['kv_pool.%d' % i for i in range(n)]
    spec = types.SimpleNamespace(
        kv_layers=range(n), pool_shape=lambda pages, pt: shape,
        pool_names=lambda layer=None: names if layer is None
        else (names[layer],))
    program, feeds = build_page_copy_program(spec, slots, shape[0], shape[1])
    prepared = PreparedProgram(program, 0, feeds, [])
    segment, = [s for s in prepared.steps if isinstance(s, _DeviceSegment)]
    assert [op.type for op in segment.ops] == ['kv_page_cow'] * n
    jitted = fluid.Executor(fluid.CPUPlace())._compile_segment(
        segment, prepared.block, program, feed_names=tuple(feeds))
    one = SingleDeviceSharding(v5e_2x2[0])
    pool = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
    pairs = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    compiled = jitted.lower(dict.fromkeys(names, pool),
                            dict.fromkeys(feeds, pairs), key).compile()
    mem = compiled.memory_analysis()
    nbytes = 4 * int(np.prod(shape))
    assert mem.alias_size_in_bytes == n * nbytes
    # a few pages of temporaries at most, never a pool
    assert mem.temp_size_in_bytes < nbytes // 64
    whole = 'f32[%s]' % ','.join(map(str, shape))
    assert not [line for line in compiled.as_text().splitlines()
                if ' copy(' in line and whole in line.split(' copy(')[0]]


def test_the_agent_loop_cell_s_new_pieces_compile_for_v5e(v5e_2x2):
    """lfm2_serve_agentloop's shapes (64 lanes, 32 query heads of 64 on 8
    K/V heads two a lane row, a table of 552 pages of 16 tokens over
    12288; six conv pools [12288, 2, 2048]): the kernel of
    pallas/paged_attention.py on pairs of heads, lowered by Mosaic under
    the name paged_attention_d64 with nothing kept beside its arguments
    but the widened query and its output; the paged short_conv step and
    chunk, plain gathers and scatters that update the donated pool where
    it lies (no copy of a pool, no padded layout)."""
    import functools
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops import delta_rule_ops as dr
    from paddle_tpu.pallas import paged_attention as pa
    one = SingleDeviceSharding(v5e_2x2[0])

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    kv = arg(12288, 16, 4, 128)
    compiled = jax.jit(functools.partial(
        pa.paged_attention_d64, sm_scale=64 ** -0.5)).lower(
            arg(64, 32, 64), kv, kv, arg(64, 552, dtype=jnp.int32),
            arg(64, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert 'tpu_custom_call' in text and 'paged_attention_d64' in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20

    pool, w = arg(12288, 2, 2048), arg(3, 2048)
    nbytes = 4 * 12288 * 2 * 2048
    step = jax.jit(
        lambda pool, x, w, table, pos, live: dr._paged_conv_step(
            pool, x, w, None, 'none', table, pos, live, 16),
        donate_argnums=0).lower(
            pool, arg(64, 1, 2048), w, arg(64, 552, dtype=jnp.int32),
            arg(64, dtype=jnp.int32), arg(64, dtype=jnp.bool_)).compile()
    chunk = jax.jit(
        lambda pool, x, w, table, pos, n: dr._paged_conv_chunk(
            pool, x, w, None, 'none', table, pos, n, 16),
        donate_argnums=0).lower(
            pool, arg(1, 256, 2048), w, arg(1, 552, dtype=jnp.int32),
            arg(256, dtype=jnp.int32), arg(dtype=jnp.int32)).compile()
    for compiled in (step, chunk):
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == nbytes
        assert mem.temp_size_in_bytes < nbytes // 16
