"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the real chip is
``chip_smoke.py``'s business).  JAX_PLATFORMS is read when jax is
imported and XLA_FLAGS when its backend starts, so setting both here,
before any test module imports jax, is enough.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Randomized-testing seed (the OpenSearchTestCase reproducible-seed
# technique, ref test/framework/.../OpenSearchTestCase.java): every run
# draws a fresh seed unless OSTPU_TEST_SEED pins it; failures print the
# seed so `OSTPU_TEST_SEED=<n> pytest ...` reproduces exactly.
TEST_SEED = int(os.environ.get("OSTPU_TEST_SEED",
                               np.random.SeedSequence().entropy % 2**31))


def pytest_report_header(config):
    return (f"opensearch_tpu randomized seed: {TEST_SEED} "
            f"(reproduce with OSTPU_TEST_SEED={TEST_SEED})")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def random_rng(request):
    """Per-test randomized generator: seeded from the session seed + the
    test name, so runs randomize while staying reproducible."""
    import zlib

    sub = zlib.crc32(request.node.nodeid.encode())
    seed = (TEST_SEED * 1_000_003 + sub) % 2**63
    print(f"[randomized] {request.node.nodeid} seed={TEST_SEED}")
    return np.random.default_rng(seed)


@pytest.fixture
def tmp_data_dir(tmp_path):
    d = tmp_path / "data"
    d.mkdir()
    return d


@pytest.fixture
def host_recovery():
    """The state in which the product itself answers every scored term
    bag from ``TermBagPlan.host_topk``: the ``dispatch`` and ``batch``
    breakers open, as sustained device errors leave them.  Tests take
    the host side of a byte-parity check through it, so it is for
    queries that are term bags (a plan without a host scorer degrades
    under an open breaker).  Yields the health service: ``reset()``
    closes the breakers again mid-test.  Health and ledger are reset
    after."""
    from opensearch_tpu.common.device_health import device_health
    from opensearch_tpu.common.device_ledger import device_ledger

    health = device_health()
    health.reset()
    health.set_failure_threshold(1)
    health.set_open_interval_s(3600.0)
    for kind in ("dispatch", "batch"):
        health.record_failure(kind)
    yield health
    health.reset()
    device_ledger().reset()
