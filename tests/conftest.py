"""Test harness config: force an 8-device virtual CPU mesh so multi-chip
sharding paths are exercised without TPU hardware (the analog of the
reference's multi-GPU tests that require real GPUs -- SURVEY.md §4.5 notes
the reference has no fake backend; we do better).

The tests run on the CPU: the platform is forced here, whatever
JAX_PLATFORMS says, so a tier-1 run on a TPU host never takes the chip.
The chip is checked by `python chip_smoke.py` (see
.claude/skills/verify/SKILL.md).
"""
import os
import sys

os.environ.setdefault('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in os.environ['XLA_FLAGS']:
    os.environ['XLA_FLAGS'] = (
        os.environ['XLA_FLAGS'] + ' --xla_force_host_platform_device_count=8'
    ).strip()

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        'markers',
        'timeout(seconds): subprocess-test budget (enforced by '
        'communicate() timeouts; informational without pytest-timeout)')
    config.addinivalue_line(
        'markers',
        'slow: long-running tests excluded from the tier-1 run '
        "(-m 'not slow')")
    config.addinivalue_line(
        'markers',
        'chaos: deterministic fault-injection tests '
        '(distributed/resilience.py harness). Deliberately NOT slow: '
        'tier-1 must prove the stack survives faults')


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs + scope + name generator
    (the analog of the reference's prog_scope decorator)."""
    import paddle_tpu as fluid
    from paddle_tpu import framework, unique_name
    main, startup = framework.Program(), framework.Program()
    prev_main = framework.switch_main_program(main)
    prev_startup = framework.switch_startup_program(startup)
    old_gen = unique_name.switch()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        yield
    framework.switch_main_program(prev_main)
    framework.switch_startup_program(prev_startup)
    unique_name.switch(old_gen)
