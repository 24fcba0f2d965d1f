"""Test config: force an 8-device virtual CPU mesh so multi-chip sharding
paths are exercised without TPU hardware (the driver separately dry-runs
multichip via __graft_entry__.dryrun_multichip)."""
import os

os.environ['JAX_PLATFORMS'] = 'cpu'
flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        'markers',
        "slow: heavy measurement tests excluded from tier-1 "
        "(-m 'not slow'); the nightly/full run includes them")


def pytest_collection_modifyitems(config, items):
    # decode-engine marker split (ISSUE 6 CI satellite): whenever the
    # generate suite is collected AS A WHOLE, its heavy throughput
    # measurement must be @slow AND at least one fast smoke variant must
    # remain unmarked, so tier-1 keeps coverage without the
    # re-traced-baseline compiles. Node-id selection collects a subset
    # by design — the split is unobservable there, don't assert on it.
    if any('::' in a for a in config.args):
        return
    for fname in ('test_generate.py', 'test_paged_generate.py',
                  'test_speculative.py', 'test_goodput.py',
                  'test_ffn_tail.py', 'test_blackbox.py',
                  'test_obslint.py', 'test_ps.py', 'test_fleet.py',
                  'test_health.py'):
        gen = [it for it in items
               if os.path.basename(str(it.fspath)) == fname]
        if gen:
            slow = [it for it in gen if it.get_closest_marker('slow')]
            fast = [it for it in gen if not it.get_closest_marker('slow')]
            assert slow, ('%s lost its @slow-marked heavy '
                          'measurement test' % fname)
            assert fast, ('%s lost its fast tier-1 smoke '
                          'variants' % fname)


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs + scope + name generator,
    mirroring the reference OpTest scratch-scope discipline."""
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    main, startup = fluid.Program(), fluid.Program()
    prev_main = fluid.framework.switch_main_program(main)
    prev_start = fluid.framework.switch_startup_program(startup)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        with unique_name.guard():
            yield
    fluid.framework.switch_main_program(prev_main)
    fluid.framework.switch_startup_program(prev_start)
