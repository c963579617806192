"""Bootstrap checks (ref bootstrap/BootstrapChecks.java:70): warn in dev
mode, abort with ALL failures listed in production mode."""

import pytest

from opensearch_tpu.bootstrap import (BootstrapCheck, BootstrapCheckError,
                                      default_checks,
                                      run_bootstrap_checks)
from opensearch_tpu.node import Node


def test_default_checks_run_and_report_cleanly(tmp_path):
    """Host limits differ per machine (this container ships a low
    vm.max_map_count, for instance) — assert the probes run and any
    failure is a well-formed actionable message, not that this
    particular host is production-ready."""
    fails = run_bootstrap_checks(default_checks(str(tmp_path)),
                                 enforce=False)
    for f in fails:
        assert f.startswith("[") and (
            "too low" in f or "unavailable" in f or "not writable" in f
            or "could not run" in f)
    names = {c.name for c in default_checks(str(tmp_path))}
    assert names == {"file descriptors", "vm.max_map_count",
                     "max threads", "data path writable",
                     "accelerator runtime"}


def test_accelerator_check_initializes_the_backend(monkeypatch):
    """The check brings the backend up at boot (a failure there must not
    wait for the first search) and reports a backend that cannot."""
    from opensearch_tpu import bootstrap
    from opensearch_tpu.common import device_ledger

    assert bootstrap._accelerator_check() is None
    assert device_ledger.backend_info()["platform"] == "cpu"

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(device_ledger, "backend_info", broken)
    msg = bootstrap._accelerator_check()
    assert msg.startswith("jax runtime unavailable") and "tpu" in msg


def test_enforce_reports_all_failures():
    checks = [BootstrapCheck("ok", lambda: None),
              BootstrapCheck("a", lambda: "first problem"),
              BootstrapCheck("b", lambda: "second problem")]
    with pytest.raises(BootstrapCheckError) as e:
        run_bootstrap_checks(checks, enforce=True)
    msg = str(e.value)
    assert "[a] first problem" in msg and "[b] second problem" in msg


def test_dev_mode_warns_instead_of_raising(caplog):
    import logging

    checks = [BootstrapCheck("a", lambda: "problem")]
    with caplog.at_level(logging.WARNING,
                         logger="opensearch_tpu.bootstrap"):
        fails = run_bootstrap_checks(checks, enforce=False)
    assert fails == ["[a] problem"]
    assert any("dev mode" in r.message for r in caplog.records)


def test_broken_probe_is_a_failure():
    def boom():
        raise OSError("probe exploded")

    fails = run_bootstrap_checks([BootstrapCheck("x", boom)],
                                 enforce=False)
    assert fails and "could not run" in fails[0]


def test_node_start_enforces_checks(tmp_path, monkeypatch):
    """Node.start wiring: enforce mode aborts boot on a failing check,
    dev (loopback) mode starts anyway.  The failing check is injected —
    real host limits vary by machine (and root bypasses permission-bit
    probes)."""
    import opensearch_tpu.bootstrap as bootstrap

    monkeypatch.setattr(
        bootstrap, "default_checks",
        lambda path: [BootstrapCheck("injected", lambda: "bad host")])
    monkeypatch.setenv("OSTPU_ENFORCE_BOOTSTRAP", "1")
    with pytest.raises(BootstrapCheckError) as e:
        Node(str(tmp_path / "n1"), port=0).start()
    assert "[injected] bad host" in str(e.value)
    # loopback dev mode: same failing check only warns
    monkeypatch.delenv("OSTPU_ENFORCE_BOOTSTRAP")
    n = Node(str(tmp_path / "n2"), port=0).start()
    try:
        assert n.port > 0
    finally:
        n.stop()
