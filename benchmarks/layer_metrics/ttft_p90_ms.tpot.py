"""Time from when a request was DUE to its first token, 90th percentile of the
requests due inside the window (72 at 1.6 requests/s: seven beyond it). Not an
end-to-end metric: over six seeds it spread by 40 % of its median (PERF.md
section 6), four times what a bound may be. Informs, does not decide."""
LAYER = 'service, not judged'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'host_clock'


def read(run):
    return run['counters'].get('ttft_p90_ms')
