"""Bootstrap checks: refuse to start a production node on a broken host.

Analog of ``bootstrap/BootstrapChecks.java`` (ref server/src/main/java/
org/opensearch/bootstrap/BootstrapChecks.java:70): each check inspects
one host limit; in development mode failures are logged as warnings, in
production mode (the reference: publishing to a non-loopback address;
here: ``bootstrap.checks=true`` or binding a non-loopback host) any
failure aborts startup.  JVM-specific checks (heap size, G1GC, client
JVM) have no analog here; the accelerator-runtime check fills that slot.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

from opensearch_tpu.common.errors import OpenSearchTpuError


class BootstrapCheckError(OpenSearchTpuError):
    status = 500


class BootstrapCheck:
    """One named predicate; returns an error message or None."""

    def __init__(self, name: str, fn: Callable[[], Optional[str]]):
        self.name = name
        self.fn = fn

    def run(self) -> Optional[str]:
        try:
            return self.fn()
        except Exception as e:  # noqa: BLE001 — a broken probe is a finding
            return f"check could not run: {e!r}"


def _file_descriptor_check(minimum: int = 4096) -> Optional[str]:
    """ref bootstrap/BootstrapChecks.java FileDescriptorCheck (65535 on
    Linux servers; relaxed here since shard files are columnar, not
    per-field)."""
    import resource

    soft, _hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft != resource.RLIM_INFINITY and soft < minimum:
        return (f"max file descriptors [{soft}] is too low, increase to "
                f"at least [{minimum}]")
    return None


def _max_map_count_check(minimum: int = 262144) -> Optional[str]:
    """ref MaxMapCountCheck — XLA/HBM staging mmaps many regions too."""
    path = "/proc/sys/vm/max_map_count"
    if not os.path.exists(path):        # non-Linux: not applicable
        return None
    with open(path) as f:
        count = int(f.read().strip())
    if count < minimum:
        return (f"max virtual memory areas vm.max_map_count [{count}] is "
                f"too low, increase to at least [{minimum}]")
    return None


def _max_threads_check(minimum: int = 1024) -> Optional[str]:
    """ref MaxNumberOfThreadsCheck (thread pools + per-search dispatch)."""
    import resource

    soft, _hard = resource.getrlimit(resource.RLIMIT_NPROC)
    if soft != resource.RLIM_INFINITY and soft < minimum:
        return (f"max number of threads [{soft}] is too low, increase "
                f"to at least [{minimum}]")
    return None


def _data_path_writable_check(data_path: str) -> Optional[str]:
    if not os.access(data_path, os.W_OK):
        return f"data path [{data_path}] is not writable"
    return None


def _accelerator_check() -> Optional[str]:
    """The heap/JVM slot: the compute backend must initialize — here,
    not on the first search, so a node whose accelerator failed to come
    up says so at boot instead of quietly scoring on the host."""
    try:
        from opensearch_tpu.common.device_ledger import backend_info
        backend_info()
    except Exception as e:  # noqa: BLE001
        return f"jax runtime unavailable: {e!r}"
    return None


def default_checks(data_path: str) -> list[BootstrapCheck]:
    return [
        BootstrapCheck("file descriptors", _file_descriptor_check),
        BootstrapCheck("vm.max_map_count", _max_map_count_check),
        BootstrapCheck("max threads", _max_threads_check),
        BootstrapCheck("data path writable",
                       lambda: _data_path_writable_check(data_path)),
        BootstrapCheck("accelerator runtime", _accelerator_check),
    ]


def run_bootstrap_checks(checks: list[BootstrapCheck], *,
                         enforce: bool) -> list[str]:
    """Run all checks; returns failure messages.  ``enforce`` (production
    mode) raises BootstrapCheckError listing EVERY failure (the reference
    reports all failures at once, not just the first)."""
    import logging

    failures = []
    for c in checks:
        msg = c.run()
        if msg is not None:
            failures.append(f"[{c.name}] {msg}")
    if failures:
        if enforce:
            raise BootstrapCheckError(
                "node validation exception\nbootstrap checks failed\n"
                + "\n".join(failures))
        log = logging.getLogger("opensearch_tpu.bootstrap")
        for f in failures:
            log.warning("bootstrap check failure (dev mode): %s", f)
    return failures
