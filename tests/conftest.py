"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding correctness is tested
on ``xla_force_host_platform_device_count=8`` CPU devices (the driver
separately dry-runs the multi-chip path via ``__graft_entry__.dryrun_multichip``).
Must run before the first ``import jax`` anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Nothing in this installation imports jax before this conftest does (there
# is no sitecustomize, and no pytest plugin pulls it in), so the variables
# above are what jax reads. The config update says the same thing to a jax
# that some future plugin may have imported first: backend init is lazy, so
# it holds as long as nothing has touched jax.devices() yet.
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (`-m 'not slow'`); "
        "full-fidelity end-to-end runs",
    )


@pytest.fixture
def graph():
    from hypergraphdb_tpu import HyperGraph

    g = HyperGraph()
    yield g
    g.close()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def make_random_hypergraph(g, n_nodes=200, n_links=400, max_arity=4, seed=0,
                           n_types=3):
    """Shared fixture-builder: random nodes + random typed links; returns
    (node_handles, link_handles)."""
    r = np.random.default_rng(seed)
    nodes = list(g.add_nodes_bulk([f"n{i}" for i in range(n_nodes)]))
    links = []
    for i in range(n_links):
        arity = int(r.integers(1, max_arity + 1))
        ts = r.choice(nodes, size=arity, replace=False)
        links.append(g.add_link([int(t) for t in ts], value=i))
    return nodes, links
