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
