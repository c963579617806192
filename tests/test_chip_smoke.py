"""chip_smoke.py's body at a tiny size on the CPU backend, so the command
is debugged here and chip time is not spent on typos — plus the two ways
the smoke must FAIL: without a TPU, and when a device fault was answered
from the host."""

import subprocess
import sys

import pytest

import chip_smoke
from opensearch_tpu.common.device_health import device_health
from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.index import codec
from opensearch_tpu.testing.fault_injection import DeviceFaultInjector

TINY = chip_smoke.Sizes(
    f32_docs=2048, f32_refresh_every=512, quant_docs=2048, vectors=2048,
    dim=16, vocab=8192, seq_queries=8, msearch_queries=16, knn_queries=4,
    pallas_rows=512, bulk_chunk=512)


@pytest.fixture
def device_kernels(monkeypatch):
    """Let a 2k-doc segment quantize, and start from clean
    process-global device books."""
    monkeypatch.setattr(codec, "QUANTIZED_MIN_DOCS", 1024)
    device_health().reset()
    device_ledger().reset()
    yield
    device_health().reset()
    device_ledger().reset()


def test_smoke_body_runs_every_step_on_cpu_kernels(device_kernels):
    out = chip_smoke.run_smoke(TINY, seed=7, platform="cpu",
                               device_count=1)
    for step in ("smoke_f32", "smoke_quant", "aggs", "knn", "half_budget",
                 "pallas"):
        assert out[step]["correct"] is True, step
    assert out["mesh"] == "not_run_1_device"
    assert out["loaded"]["smoke_f32"]["docs"] == 2048
    assert out["loaded"]["smoke_f32"]["segments"] == 4
    assert out["loaded"]["smoke_quant"]["segments"] == 1
    assert out["device"]["host_fallbacks"] == 0
    # the device-versus-host parity asked the degradation route for its
    # side: one recovered segment a query
    assert out["device"]["asked_fallbacks"] == TINY.seq_queries
    assert out["device"]["programs"]["plan.run_topk"] >= 1
    assert out["knn"]["recall_at_10"] == 1.0


def test_smoke_body_runs_the_mesh_section_on_virtual_devices(device_kernels):
    out = chip_smoke.run_smoke(TINY, seed=7, platform="cpu",
                               device_count=8)
    assert out["mesh"]["correct"] is True and out["mesh"]["devices"] == 4


def test_smoke_body_fails_when_a_device_fault_was_answered_from_the_host(
        device_kernels):
    """PR 15 answers a failing kernel from the byte-identical host path
    with a 200: correct answers, no device work.  The smoke must not
    pass on those."""
    inj = DeviceFaultInjector(seed=3)
    inj.dispatch_error("run_topk", times=1)
    with inj, pytest.raises(chip_smoke.SmokeFailure,
                            match="the device did not do the work"):
        chip_smoke.run_smoke(TINY, seed=7, platform="cpu", device_count=1)


def test_script_refuses_to_run_without_a_tpu():
    """Under JAX_PLATFORMS=cpu (what conftest sets) the script exits
    non-zero before loading anything and prints no result line."""
    r = subprocess.run([sys.executable, chip_smoke.__file__],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform: cpu" in r.stdout
    assert '"ok"' not in r.stdout and "loaded" not in r.stdout
