"""Fixtures of the benchmark's tests (tests/conftest.py holds the run to
the CPU)."""

import gc

import pytest


@pytest.fixture
def cpu_kernels(monkeypatch):
    """Run the XLA kernels on the CPU backend, and put back every
    process-wide thing a run changes: the dynamic settings it sends land
    on module globals, and set-up freezes the collector's generations."""
    from opensearch_tpu.common.device_health import device_health
    from opensearch_tpu.common.device_ledger import device_ledger
    from opensearch_tpu.index import codec
    from opensearch_tpu.ops import bm25 as bm25_ops
    from opensearch_tpu.search import engine

    monkeypatch.setattr(bm25_ops, "HOST_SCORING", False)
    monkeypatch.setattr(codec, "QUANTIZED_MODE", codec.QUANTIZED_MODE)
    monkeypatch.setattr(engine, "BATCHER_ENABLED", engine.BATCHER_ENABLED)
    device_health().reset()
    device_ledger().reset()
    yield
    gc.unfreeze()
    device_health().reset()
    device_ledger().reset()
