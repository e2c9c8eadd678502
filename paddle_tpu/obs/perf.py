"""Device-side performance observatory: compile/JIT telemetry, step
counts and HBM watermarks.

The cluster half of obs/ (PR 4) watches the wire; this module watches
the device. The Executor's JIT pipeline reports into it at two points:

- compile time (`record_compile` / `jit_cache_miss` / `jit_cache_hit`)
  — every lazily-compiled device segment stamps an
  `xla.compile_latency` observation and bumps the
  `xla.jit_cache.{hit,miss}` counters; its first (compiling) call runs
  inside an `xla.compile` span (`compile_span`).
- step time (`step_begin` / `step_end`) — every `Executor.run` bumps
  `perf.steps`. `perf.step_latency` is the wall time of a run **whose
  fetch came back to the host** (`return_numpy=True` with a non-empty
  fetch list): there the fetch has waited for the device, so the time
  is the step's. A run that hands back device arrays, or fetches
  nothing, is not observed: its wall time would be the enqueue.

No hook waits on the device, reads the allocator or walks a scope: an
observatory that synchronises changes the schedule it watches (a
prefill chunk that returns nothing can be queued in front of the next
decode step only if nobody waits for it). Every hook is a no-op while
telemetry is disabled — the same one-global-bool fast path as the rest
of the registry.

HBM gauges (`hbm.bytes_in_use`, `hbm.peak_bytes`, `hbm.bytes_limit`,
`hbm.scope_bytes`, `hbm.watermark_bytes`) are read ON DEMAND: whenever
`telemetry.snapshot()` is taken (by a caller, the exporter thread or
the SLO watchdog), `update_hbm` asks memory.hbm_snapshot() for the
device and the scope of the newest `Executor.run`. On backends without
PJRT memory stats (CPU) bytes_in_use falls back to the scope footprint
so the series stay live in tests. `hbm.watermark_bytes` is a
process-local high-water mark that survives allocator-level peak
resets.

PEAK_BF16_FLOPS below is the one table of chip peaks, keyed by the
exact `device_kind` (bench.py and chip_smoke.py divide by it). A kind
that is not in it raises. Model FLOPs utilisation is not a gauge here:
it needs the FLOPs the MODEL requires, from its shapes, and XLA's cost
analysis counts recomputation and elementwise work (PERF.md).
"""
from __future__ import annotations

import time
import weakref

from . import telemetry
from .. import flags

__all__ = ['enabled', 'step_begin', 'step_end', 'jit_cache_hit',
           'jit_cache_miss', 'record_compile',
           'PEAK_BF16_FLOPS', 'device_peak_flops', 'describe_device',
           'require_tpu', 'update_hbm', 'compile_span']

# --- instruments (registered at import; zero until enabled) ---------
_compile_latency = telemetry.histogram('xla.compile_latency')
_jit_hits = telemetry.counter('xla.jit_cache.hit')
_jit_misses = telemetry.counter('xla.jit_cache.miss')
_step_latency = telemetry.histogram('perf.step_latency')
_steps = telemetry.counter('perf.steps')
_hbm_in_use = telemetry.gauge('hbm.bytes_in_use')
_hbm_peak = telemetry.gauge('hbm.peak_bytes')
_hbm_limit = telemetry.gauge('hbm.bytes_limit')
_hbm_scope = telemetry.gauge('hbm.scope_bytes')
_hbm_watermark = telemetry.gauge('hbm.watermark_bytes')

_watermark = 0          # process-local high-water of bytes_in_use
_slo_started = False    # lazy FLAGS_slo_rules watchdog, armed once
_last_device = None     # of the newest Executor.run: what update_hbm
_last_scope = None      # reads when a snapshot asks (scope: a weakref)

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
    """Peak dense bf16 FLOP/s of `device`, the denominator of an MFU:
    the FLAGS_perf_peak_tflops override if set (TFLOP/s; the only way
    to get one off-TPU), else the PEAK_BF16_FLOPS entry of its exact
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
    """The span around a device segment's first (compiling) call: trace,
    lowering and the XLA compile (or the persistent cache's retrieval)
    all happen inside it. The program fingerprint tag lets a timeline
    reader join the span to the jit_cache series and to rerun-vs-rerun
    comparisons."""
    from ..profiler import RecordEvent
    return RecordEvent('xla.compile', fingerprint=fingerprint,
                       segment=segment, n_ops=n_ops)


def record_compile(latency_s):
    _compile_latency.observe(latency_s)


# --- step-time hooks ------------------------------------------------

def step_begin():
    """Start-of-run timestamp, or None when telemetry is off (the
    executor passes the None straight back to step_end's guard)."""
    if not telemetry._enabled:
        return None
    return time.perf_counter()


def step_end(t0, device=None, scope=None, fetched=False):
    """Close out one Executor.run: count it, observe its latency if
    the caller's fetch already waited for the device (`fetched`),
    remember whose memory a snapshot should read, and (once) arm the
    FLAGS_slo_rules watchdog. Waits for nothing and reads nothing."""
    global _last_device, _last_scope
    if t0 is None or not telemetry._enabled:
        return
    _steps.inc()
    if fetched:
        _step_latency.observe(time.perf_counter() - t0)
    _last_device = device
    if scope is not None and (_last_scope is None
                              or _last_scope() is not scope):
        _last_scope = weakref.ref(scope)
    _maybe_start_slo()


def update_hbm(device=None, scope=None):
    """Export memory.hbm_snapshot() as gauges + the process-local
    watermark. Registered with telemetry.on_read, so every snapshot()
    sees fresh numbers of the newest run's device and scope; callable
    standalone with others."""
    global _watermark
    if not telemetry._enabled:
        return
    from .. import memory
    if device is None:
        device = _last_device
    if scope is None and _last_scope is not None:
        scope = _last_scope()
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


telemetry.on_read(update_hbm)


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
    global _watermark, _slo_started, _last_device, _last_scope
    _watermark = 0
    _slo_started = False
    _last_device = _last_scope = None
