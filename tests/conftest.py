"""Test config: run on a virtual 8-device CPU mesh.

Mirrors the reference test strategy (SURVEY.md §4): distributed
correctness is tested without real hardware — here via
xla_force_host_platform_device_count, replacing the reference's
multi-process-localhost NCCL harness.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # overwrite whatever the env presets
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs and a fresh scope."""
    import paddle_tpu
    from paddle_tpu.framework import core
    from paddle_tpu.framework import executor as ex
    main, startup = core.Program(), core.Program()
    startup._is_startup = True
    prev_m = core.switch_main_program(main)
    prev_s = core.switch_startup_program(startup)
    old_scope = ex._global_scope
    ex._global_scope = ex.Scope()
    ex._scope_stack[:] = [ex._global_scope]
    np.random.seed(0)
    from paddle_tpu.ops.registry import reset_op_seed
    reset_op_seed()
    yield
    core.switch_main_program(prev_m)
    core.switch_startup_program(prev_s)
    ex._global_scope = old_scope
    ex._scope_stack[:] = [old_scope]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'); "
        "subprocess-heavy or long-wall-clock tests")


def retry_flaky(retries: int = 1, delay_s: float = 2.0):
    """Bounded single-retry for tests DOCUMENTED as in-suite flakes on
    core-bound CI hosts (they pass reliably in isolation and on the
    pristine tree under load — see the PR 12/13 notes in CHANGES.md).
    This is NOT a general license to retry: apply only with an
    in-docstring justification, and keep ``retries`` at 1 so a real
    regression (which fails deterministically) still fails the suite
    while a scheduler hiccup gets exactly one more shot after the
    host load transient passes."""
    import functools
    import time as _time

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            for attempt in range(retries + 1):
                try:
                    return fn(*args, **kwargs)
                except AssertionError:
                    if attempt >= retries:
                        raise
                    _time.sleep(delay_s)
        return wrapper

    return deco
