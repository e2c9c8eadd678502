"""Device-side performance observatory: compile/JIT telemetry, live
MFU, and HBM watermarks.

The cluster half of obs/ (PR 4) watches the wire; this module watches
the device. The Executor's JIT pipeline reports into it at two points:

- compile time (`record_compile` / `jit_cache_miss` / `jit_cache_hit`)
  — every lazily-compiled device segment stamps an
  `xla.compile_latency` observation and bumps the
  `xla.jit_cache.{hit,miss}` counters, and its analytical cost
  (FLOPs / bytes accessed, from jax's compiled cost analysis) is
  accumulated onto the owning PreparedProgram so step attribution
  below has a work model to divide by.
- step time (`step_begin` / `step_end`) — wall latency of each
  `Executor.run` call lands in the `perf.step_latency` histogram, and
  combined with the compile-time FLOP count yields
  `perf.achieved_tflops` and `perf.mfu` gauges. A `return_numpy=True`
  fetch has already synchronized through the host transfer; otherwise
  the fetched arrays are `block_until_ready`'d before the clock stops.

Every hook is a no-op while telemetry is disabled — same
one-global-bool fast path as the rest of the registry — so the
executor hot loop pays nothing by default. The one deliberate
exception: capturing a segment's cost analysis requires a second
lower+compile of the already-jitted function (an explicit
lower().compile() does not warm jax's call cache), which doubles a
once-per-program cost. That is why it is gated on telemetry being
enabled rather than free-running.

MFU needs a peak-FLOPs denominator: PEAK_BF16_FLOPS below, keyed by
the exact `device_kind` (the one table; bench.py and chip_smoke.py
import it). A kind that is not in it raises. Off-TPU there is no peak,
so `perf.mfu` is set only when `FLAGS_perf_peak_tflops` pins one (CPU
tests).

HBM gauges (`hbm.bytes_in_use`, `hbm.peak_bytes`, `hbm.bytes_limit`,
`hbm.scope_bytes`, `hbm.watermark_bytes`) are refreshed on every
step_end from memory.hbm_snapshot(); on backends without PJRT memory
stats (CPU) bytes_in_use falls back to the scope footprint so the
series stay live in tests. `hbm.watermark_bytes` is a process-local
high-water mark that survives allocator-level peak resets.
"""
from __future__ import annotations

import time

from . import telemetry, trace
from .. import flags

__all__ = ['enabled', 'step_begin', 'step_end', 'jit_cache_hit',
           'jit_cache_miss', 'record_compile', 'segment_cost',
           'PEAK_BF16_FLOPS', 'device_peak_flops', 'describe_device',
           'require_tpu', 'update_hbm', 'compile_span']

# --- instruments (registered at import; zero until enabled) ---------
_compile_latency = telemetry.histogram('xla.compile_latency')
_jit_hits = telemetry.counter('xla.jit_cache.hit')
_jit_misses = telemetry.counter('xla.jit_cache.miss')
_step_latency = telemetry.histogram('perf.step_latency')
_steps = telemetry.counter('perf.steps')
_mfu = telemetry.gauge('perf.mfu')
_achieved_tflops = telemetry.gauge('perf.achieved_tflops')
_hbm_in_use = telemetry.gauge('hbm.bytes_in_use')
_hbm_peak = telemetry.gauge('hbm.peak_bytes')
_hbm_limit = telemetry.gauge('hbm.bytes_limit')
_hbm_scope = telemetry.gauge('hbm.scope_bytes')
_hbm_watermark = telemetry.gauge('hbm.watermark_bytes')

_watermark = 0          # process-local high-water of bytes_in_use
_slo_started = False    # lazy FLAGS_slo_rules watchdog, armed once

# Peak dense bf16 FLOP/s of one chip, keyed by the exact
# jax.Device.device_kind. Source of every figure: Google Cloud TPU
# documentation, the system-architecture page of the generation named
# in the comment. Only 'TPU v5 lite' has been seen on hardware by this
# repository (chip_smoke.py).
PEAK_BF16_FLOPS = {
    'TPU v4': 275e12,        # "TPU v4"
    'TPU v5 lite': 197e12,   # "TPU v5e"
    'TPU v5': 459e12,        # "TPU v5p"
    'TPU v6 lite': 918e12,   # "TPU v6e" (Trillium)
}


def enabled():
    return telemetry._enabled


def _pinned_peak_flops():
    """FLAGS_perf_peak_tflops in FLOP/s, or 0.0 when it pins nothing."""
    return max(float(flags.get_flag('perf_peak_tflops', 0.0)), 0.0) * 1e12


def device_peak_flops(device):
    """Peak dense bf16 FLOP/s of `device` for MFU attribution: the
    FLAGS_perf_peak_tflops override if set (TFLOP/s; the only way to
    get an MFU off-TPU), else the PEAK_BF16_FLOPS entry of its exact
    device_kind. An unknown kind raises: a guessed peak makes every MFU
    computed from it wrong without saying so."""
    pinned = _pinned_peak_flops()
    if pinned:
        return pinned
    kind = device.device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise ValueError(
            'no peak bf16 FLOP/s known for device_kind %r (platform %r); '
            'known kinds: %s. Add it to obs/perf.py PEAK_BF16_FLOPS with '
            'its source, or pin FLAGS_perf_peak_tflops.'
            % (kind, device.platform, sorted(PEAK_BF16_FLOPS)))
    return PEAK_BF16_FLOPS[kind]


def describe_device():
    """{'platform', 'device_kind', 'n_devices'} as JAX reports the
    default backend — stamped on every row a measurement prints."""
    import jax
    devs = jax.devices()
    return {'platform': devs[0].platform,
            'device_kind': devs[0].device_kind,
            'n_devices': len(devs)}


def require_tpu(min_devices=1):
    """For entry points that measure the chip (chip_smoke.py, bench.py,
    the --full tools): raise unless JAX's default backend is a TPU
    whose device_kind is in PEAK_BF16_FLOPS, with at least min_devices
    visible. JAX falls back to the CPU without an error when it finds
    no accelerator; a measurement must not. Returns describe_device()."""
    dev = describe_device()
    if dev['platform'] != 'tpu':
        raise RuntimeError(
            'this entry point needs a TPU; JAX found platform=%r '
            'device_kind=%r (%d device(s))'
            % (dev['platform'], dev['device_kind'], dev['n_devices']))
    if dev['device_kind'] not in PEAK_BF16_FLOPS:
        raise RuntimeError(
            'TPU device_kind %r is not in obs/perf.py PEAK_BF16_FLOPS '
            '(known: %s)' % (dev['device_kind'], sorted(PEAK_BF16_FLOPS)))
    if dev['n_devices'] < min_devices:
        raise RuntimeError(
            'this run needs %d TPU devices; JAX sees %d'
            % (min_devices, dev['n_devices']))
    return dev


# --- compile-time hooks ---------------------------------------------

def jit_cache_hit():
    _jit_hits.inc()


def jit_cache_miss():
    _jit_misses.inc()


def compile_span(fingerprint, segment, n_ops):
    """Trace span wrapping a device segment's first (compiling) call.
    The program fingerprint tag lets a timeline reader join the span
    to the jit_cache series and to rerun-vs-rerun comparisons."""
    return trace.span('xla.compile', fingerprint=fingerprint,
                      segment=segment, n_ops=n_ops)


def record_compile(latency_s, flops=0.0, bytes_accessed=0.0):
    _compile_latency.observe(latency_s)


def segment_cost(jitted, arg_struct):
    """Analytical (flops, bytes_accessed) for a jitted segment via the
    XLA cost model. Requires a fresh lower+compile (jax's jit call
    cache is not warmed by an explicit .lower().compile(), so this is
    a duplicated compile — acceptable once per segment when telemetry
    is on). Returns (0.0, 0.0) on any backend that can't answer."""
    try:
        cost = jitted.lower(*arg_struct).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get('flops', 0.0) or 0.0)
        nbytes = float(cost.get('bytes accessed', 0.0) or 0.0)
        return (max(flops, 0.0), max(nbytes, 0.0))
    except Exception:
        return (0.0, 0.0)


def pallas_extra_flops():
    """Drain trace-time extra-work notes from the Pallas kernels.

    XLA's cost model cannot see inside a Pallas custom call, so the
    flash segment is priced by the analytical 2-matmul attention model.
    Arms that execute MORE than that model (the twopass forward's
    second QK sweep) note the surplus at trace time; the executor
    drains it here right after the compiling call and folds it into
    the segment's cost_flops so live MFU divides by work that actually
    ran. Granularity is once-per-trace: a second program hitting the
    same inner-jit cache contributes nothing new (and needs nothing
    new — cost_flops is per-prepared-program, priced at its own
    compile). Draining is destructive; callers that only want to
    discard stale notes call this and ignore the return."""
    try:
        from paddle_tpu.pallas import flash_attention as _fa
        return float(_fa.take_extra_flops())
    except Exception:
        return 0.0


# --- step-time hooks ------------------------------------------------

def step_begin():
    """Start-of-run timestamp, or None when telemetry is off (the
    executor passes the None straight back to step_end's guard)."""
    if not telemetry._enabled:
        return None
    return time.perf_counter()


def step_end(t0, prepared=None, device=None, scope=None, sync=None):
    """Close out one Executor.run: observe step latency, derive
    achieved TFLOP/s + MFU from the prepared program's compile-time
    cost, refresh the hbm.* gauges, and (once) arm the FLAGS_slo_rules
    watchdog.

    `sync` is the fetched result list when the caller did NOT request
    numpy (so the timer must block on device completion first); None
    means the host fetch already synchronized."""
    if t0 is None or not telemetry._enabled:
        return
    if sync is not None:
        import jax
        jax.block_until_ready(sync)
    dt = time.perf_counter() - t0
    _step_latency.observe(dt)
    _steps.inc()
    flops = float(getattr(prepared, 'cost_flops', 0.0) or 0.0)
    if dt > 0.0 and flops > 0.0:
        achieved = flops / dt
        _achieved_tflops.set(achieved / 1e12)
        # the peak table knows TPUs only: off-TPU the gauge is set only
        # against a pinned FLAGS_perf_peak_tflops
        if device.platform == 'tpu' or _pinned_peak_flops():
            _mfu.set(achieved / device_peak_flops(device))
    update_hbm(device=device, scope=scope)
    _maybe_start_slo()


def update_hbm(device=None, scope=None):
    """Export memory.hbm_snapshot() as gauges + the process-local
    watermark. Callable standalone (bench_suite stamps it between
    steps of hand-rolled loops)."""
    global _watermark
    if not telemetry._enabled:
        return
    from .. import memory
    try:
        snap = memory.hbm_snapshot(device=device, scope=scope)
    except Exception:
        return
    _hbm_in_use.set(snap['bytes_in_use'])
    _hbm_peak.set(snap['peak_bytes'])
    _hbm_limit.set(snap['bytes_limit'])
    _hbm_scope.set(snap['scope_bytes'])
    if snap['bytes_in_use'] > _watermark:
        _watermark = snap['bytes_in_use']
    if snap['peak_bytes'] > _watermark:
        _watermark = snap['peak_bytes']
    _hbm_watermark.set(_watermark)


def _maybe_start_slo():
    """First instrumented step arms the declarative SLO watchdog when
    FLAGS_slo_rules is set — training runs get breach events without
    touching the serving engine's explicit start()/stop() wiring."""
    global _slo_started
    if _slo_started:
        return
    _slo_started = True
    if not flags.get_flag('slo_rules', ''):
        return
    from . import slo
    slo.maybe_start_global()


def _reset_for_tests():
    """Zero the module-local state telemetry.reset() can't see."""
    global _watermark, _slo_started
    _watermark = 0
    _slo_started = False
