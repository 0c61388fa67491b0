"""Test config: force an 8-device virtual CPU mesh.

Mirrors the reference's "multi-node without a real cluster" strategy
(adapters/repos/db/clusterintegrationtest/ spins 10 in-process nodes):
we spin 8 virtual XLA CPU devices so every sharding/collective path is
exercised without TPU hardware. Must run before jax is imported anywhere.
"""

import os

# Force, not setdefault: whatever platform the ambient environment names,
# tests need the 8-device virtual CPU platform.
os.environ["JAX_PLATFORMS"] = "cpu"
# Replace (not just append) any ambient device-count flag: a stray
# `--xla_force_host_platform_device_count=1` would silently degrade every
# sharding test to the single-device path.
flags = [
    f
    for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f
]
flags.append("--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = " ".join(flags)

import jax

# Pin the config as well as the env var: anything that configured jax before
# this file ran must not move the tests off the virtual CPU mesh.
jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _faultline_isolation():
    """Keep failure-policy state from leaking across tests: a schedule
    someone forgot to disarm, a component health flag, or — subtler —
    an OPEN circuit breaker keyed on an OS-assigned port that the next
    test's fresh in-process node happens to reuse."""
    yield
    from weaviate_tpu.cluster.transport import reset_breakers
    from weaviate_tpu.replication.hashbeater import replication_status
    from weaviate_tpu.runtime import (degrade, driftwatch, faultline,
                                      kernelscope, metrics, tailboard)
    from weaviate_tpu.storage import recovery

    faultline.disarm()
    faultline.heal()  # partition topology rules, like the disarm above
    degrade.reset()
    reset_breakers()
    recovery.reset()
    replication_status.reset()
    # tailboard/SLO/flight registries + the metric series-cap cache:
    # sliding-window SLO counts or a tail ring leaking across tests
    # would make incident assertions order-dependent
    tailboard.reset_for_tests()
    metrics.reset_series_cap_for_tests()
    # kernelscope: memcpy EWMAs, variant residency, tenant meters and
    # the capture dir all live at module level — a leaked explain sink
    # or meter total would corrupt the next test's attribution math
    kernelscope.reset_for_tests()
    # driftwatch: sealed canary references, open findings and the
    # self-sealed live baseline are module state — a finding leaking
    # across tests would poison the next test's health assertions
    driftwatch.reset_for_tests()
