"""Test harness config: force an 8-device virtual CPU mesh before JAX loads.

Mirrors the reference's "multi-node without a cluster" strategy (SURVEY §4.5:
N gb processes on loopback) — here N virtual JAX CPU devices so the sharded
query plane (shard_map over the mesh) is exercised without TPU hardware.
Must run before any ``import jax`` in the test session.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running multi-process integration test")
    # never resolve real DNS from tests: the sandbox's resolver path can
    # hang, and every distinct host would pay the lookup timeout. The
    # deterministic pseudo-IP keeps per-IP politeness/sharding semantics
    # exercised (same host → same IP) without the network.
    from open_source_search_engine_tpu.utils import ipresolve
    ipresolve.resolver_override = ipresolve._pseudo_ip
