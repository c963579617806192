"""Deterministic fault injection over the LocalTransport hub.

Analog of the test framework's ``MockTransportService`` +
``NetworkDisruption`` (test/framework .../test/transport/
MockTransportService.java, .../test/disruption/NetworkDisruption.java):
first-class drop / delay / duplicate / disconnect rules that match on
the transport ACTION NAME (glob patterns), scoped one-shot or sticky,
with every probabilistic choice drawn from a seeded RNG — the same seed
replays the same fault schedule, so every fault-tolerance test in this
repo is reproducible bit-for-bit.

Usage::

    hub = LocalTransport.Hub()
    faults = FaultInjector(hub, seed=42)
    faults.drop("indices:data/read/search*", target="n2", times=1)
    faults.delay(0.2, action="internal:coordination/*")
    faults.disconnect("n2")          # full partition
    faults.heal("n2")                # lift it
    faults.partition({"n0"}, {"n1", "n2"})   # symmetric two-sided split
    faults.heal_partition()          # reconnect the halves
    faults.clear()                   # lift everything
"""

from __future__ import annotations

import fnmatch
import os
import random
import threading
import time
from typing import Optional

from opensearch_tpu.common.errors import NodeDisconnectedError
from opensearch_tpu.transport.service import Directive, peek_action


class _Rule:
    """One installed fault: match → act, ``times``-bounded or sticky."""

    def __init__(self, injector: "FaultInjector", action: str,
                 source: Optional[str], target: Optional[str],
                 probability: float, times: Optional[int]):
        self.injector = injector
        self.action = action
        self.source = source
        self.target = target
        self.probability = float(probability)
        self.remaining = times           # None = sticky
        self._lock = threading.Lock()

    def matches(self, src: str, dst: str, frame: bytes) -> bool:
        if self.source is not None and src != self.source:
            return False
        if self.target is not None and dst != self.target:
            return False
        if self.action not in ("*", None):
            act = peek_action(frame)
            # exact match first: real action names contain glob
            # metacharacters ("...shard[r]"), which fnmatch would
            # otherwise read as a character class
            if act != self.action \
                    and not fnmatch.fnmatch(act, self.action):
                return False
        with self._lock:
            if self.remaining is not None and self.remaining <= 0:
                return False
            if self.probability < 1.0 \
                    and self.injector._random() >= self.probability:
                return False
            if self.remaining is not None:
                self.remaining -= 1
        return True

    def __call__(self, src: str, dst: str, frame: bytes):
        if self.matches(src, dst, frame):
            return self.act(src, dst)
        return None

    def act(self, src: str, dst: str):   # pragma: no cover - overridden
        return None


class _Drop(_Rule):
    def __init__(self, *a, silent: bool = False):
        super().__init__(*a)
        self.silent = silent

    def act(self, src, dst):
        if self.silent:
            # swallow: the sender's future just never resolves (times
            # out) — the lost-frame failure mode, vs. the fast-failing
            # connection-refused one below
            return Directive(copies=0)
        raise NodeDisconnectedError(
            f"[fault_injection] dropped frame {src}->{dst}")


class _Delay(_Rule):
    def __init__(self, *a, seconds: float):
        super().__init__(*a)
        self.seconds = float(seconds)

    def act(self, src, dst):
        return self.seconds


class _Duplicate(_Rule):
    def __init__(self, *a, copies: int = 2):
        super().__init__(*a)
        self.copies = int(copies)

    def act(self, src, dst):
        return Directive(copies=self.copies)


class _Stall(_Rule):
    """Hold matching frames on an Event instead of a wall-clock delay:
    the frame is delivered the instant ``release()`` fires — the
    deterministic slow-node primitive (no sleeps, no timing slop)."""

    def __init__(self, *a):
        super().__init__(*a)
        self.gate = threading.Event()

    def act(self, src, dst):
        return Directive(gate=self.gate)

    def release(self):
        self.gate.set()


class _Partition:
    """Symmetric network split: frames CROSSING the cut (either
    direction) fail fast; traffic within each side flows normally — the
    ``NetworkDisruption.TwoPartitions`` analog (a ``disconnect`` is the
    degenerate one-node-vs-everyone case)."""

    def __init__(self, side_a, side_b):
        self.side_a = frozenset(side_a)
        self.side_b = frozenset(side_b)

    def __call__(self, src: str, dst: str, frame: bytes):
        if (src in self.side_a and dst in self.side_b) \
                or (src in self.side_b and dst in self.side_a):
            raise NodeDisconnectedError(
                f"[fault_injection] partition cut {src}->{dst}")
        return None


class FaultInjector:
    """Installs/uninstalls rules on a ``LocalTransport.Hub``; every
    random draw comes from one seeded stream guarded by a lock, so a
    fixed seed gives a fixed schedule regardless of which fault fires
    first."""

    def __init__(self, hub, seed: int = 0):
        self.hub = hub
        self.seed = int(seed)
        self._rng = random.Random(seed)
        self._rng_lock = threading.Lock()
        self._installed: list = []
        self._partitions: dict[str, object] = {}
        self._group_partitions: list[_Partition] = []

    def _random(self) -> float:
        with self._rng_lock:
            return self._rng.random()

    def _install(self, rule):
        self.hub.add_rule(rule)
        self._installed.append(rule)
        return rule

    # -- faults ------------------------------------------------------------

    def drop(self, action: str = "*", source: Optional[str] = None,
             target: Optional[str] = None, probability: float = 1.0,
             times: Optional[int] = None, silent: bool = False):
        """Drop matching frames.  ``silent=True`` swallows them (the
        sender times out); default raises at send time (the sender sees
        a NodeDisconnectedError immediately)."""
        return self._install(_Drop(self, action, source, target,
                                   probability, times, silent=silent))

    def delay(self, seconds: float, action: str = "*",
              source: Optional[str] = None, target: Optional[str] = None,
              probability: float = 1.0, times: Optional[int] = None):
        return self._install(_Delay(self, action, source, target,
                                    probability, times, seconds=seconds))

    def duplicate(self, action: str = "*", source: Optional[str] = None,
                  target: Optional[str] = None, probability: float = 1.0,
                  times: Optional[int] = None, copies: int = 2):
        """Deliver matching frames ``copies`` times — the at-least-once
        hazard handlers must tolerate (idempotency probes)."""
        return self._install(_Duplicate(self, action, source, target,
                                        probability, times, copies=copies))

    def stall(self, action: str = "*", source: Optional[str] = None,
              target: Optional[str] = None, probability: float = 1.0,
              times: Optional[int] = None) -> _Stall:
        """Hold matching frames until the returned rule's ``release()``
        is called (delivery is event-driven, not timed)."""
        return self._install(_Stall(self, action, source, target,
                                    probability, times))

    def slow_search_node(self, node_id: str, seconds: float,
                         times: Optional[int] = None):
        """Degrade one data node's shard query phase: every
        ``indices:data/read/search[shards]`` frame TO ``node_id`` is
        delayed — the canonical adaptive-replica-selection scenario (the
        coordinator should derank the node and reroute to healthy
        copies)."""
        from opensearch_tpu.cluster.node import A_SEARCH_SHARDS
        return self.delay(seconds, action=A_SEARCH_SHARDS,
                          target=node_id, times=times)

    def induce_search_duress(self, service, ticks: int = 1) -> None:
        """Deterministic duress simulation: force the given
        SearchBackpressureService's next ``ticks`` evaluations to read
        as node-in-duress, bypassing the real probes — the fault
        harness's answer to 'make this node overloaded NOW' without
        burning real CPU or heap."""
        service.force_duress(ticks)

    def disconnect(self, node_id: str):
        """Full partition: everything to/from ``node_id`` fails fast."""
        if node_id in self._partitions:
            return self._partitions[node_id]
        rule = self.hub.disconnect(node_id)
        self._installed.append(rule)
        self._partitions[node_id] = rule
        return rule

    def partition(self, side_a, side_b) -> _Partition:
        """Symmetric split between two node groups: every frame crossing
        the cut fails fast in BOTH directions, while each side keeps
        talking internally (so a minority side can still try — and fail —
        to reach quorum).  Returns the rule; ``heal_partition()`` lifts
        it (or all of them)."""
        rule = _Partition(side_a, side_b)
        self._install(rule)
        self._group_partitions.append(rule)
        return rule

    def heal_partition(self, rule: Optional[_Partition] = None) -> bool:
        """Lift one ``partition()`` (or every installed one)."""
        victims = ([rule] if rule is not None
                   else list(self._group_partitions))
        healed = False
        for r in victims:
            if r in self._group_partitions:
                self._group_partitions.remove(r)
                self._installed.remove(r)
                healed = self.hub.remove_rule(r) or healed
        return healed

    def heal(self, node_id: str) -> bool:
        """Lift a ``disconnect`` partition."""
        rule = self._partitions.pop(node_id, None)
        if rule is None:
            return False
        self._installed.remove(rule)
        return self.hub.remove_rule(rule)

    def remove(self, rule) -> bool:
        if rule in self._installed:
            self._installed.remove(rule)
        for nid, r in list(self._partitions.items()):
            if r is rule:
                del self._partitions[nid]
        if rule in self._group_partitions:
            self._group_partitions.remove(rule)
        return self.hub.remove_rule(rule)

    def clear(self):
        """Uninstall every rule THIS injector added (other hub rules are
        left alone, unlike ``hub.clear_rules``)."""
        for rule in self._installed:
            self.hub.remove_rule(rule)
        self._installed.clear()
        self._partitions.clear()
        self._group_partitions.clear()


# ---------------------------------------------------------------------------
# Disk fault injection (the MockFileSystem / disruptive-FS analog)
# ---------------------------------------------------------------------------


class _DiskRule:
    """One installed disk fault: matches (op, absolute path) by fnmatch
    pattern, ``times``-bounded or sticky, probability drawn from the
    injector's seeded stream — the same Directive idioms as the
    transport rules above."""

    def __init__(self, injector: "DiskFaultInjector", op: str,
                 pattern: str, probability: float, times: Optional[int],
                 **params):
        self.injector = injector
        self.op = op                     # read | write | fsync
        self.pattern = pattern
        self.probability = float(probability)
        self.remaining = times           # None = sticky
        self.params = params
        self._lock = threading.Lock()

    def matches(self, op: str, path: str) -> bool:
        if op != self.op:
            return False
        if path != self.pattern and not fnmatch.fnmatch(path, self.pattern):
            return False
        with self._lock:
            if self.remaining is not None and self.remaining <= 0:
                return False
            if self.probability < 1.0 \
                    and self.injector._random() >= self.probability:
                return False
            if self.remaining is not None:
                self.remaining -= 1
        return True


class _CorruptedReader:
    """File-object proxy serving pre-corrupted bytes; supports the
    read/iterate/context-manager surface the store and json/numpy
    loaders use."""

    def __init__(self, path: str, data: bytes, text: bool):
        import io
        self.name = path
        self._buf = (io.StringIO(data.decode("utf-8", "replace"))
                     if text else io.BytesIO(data))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __iter__(self):
        return iter(self._buf)

    def __getattr__(self, name):
        return getattr(self._buf, name)


class DiskFaultInjector:
    """Deterministic disk-level fault injection: while active, patches
    ``builtins.open`` and ``os.fsync`` so files whose ABSOLUTE PATH
    matches an installed rule misbehave — bit-flips and truncation on
    read, EIO/ENOSPC on write or fsync, slow fsync — everything else
    passes through untouched.  Every probabilistic choice and corruption
    offset comes from one seeded stream, so a fixed seed replays the
    same damage.

    Usage::

        disk = DiskFaultInjector(seed=7)
        disk.corrupt_read(f"{data}/segments/*.npz", times=1)
        disk.fail_fsync(f"{data}/*", err=errno.EIO)
        with disk:                       # activate() / deactivate()
            ...
    """

    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        self._rng = random.Random(seed)
        self._rng_lock = threading.Lock()
        self._rules: list[_DiskRule] = []
        self._rules_lock = threading.Lock()
        self._active = False
        self._real_open = None
        self._real_fsync = None
        self._fd_paths: dict[int, str] = {}

    def _random(self) -> float:
        with self._rng_lock:
            return self._rng.random()

    def _randrange(self, n: int) -> int:
        with self._rng_lock:
            return self._rng.randrange(n)

    # -- lifecycle ---------------------------------------------------------

    def activate(self) -> "DiskFaultInjector":
        import builtins
        if self._active:
            return self
        self._active = True
        self._real_open = builtins.open
        self._real_fsync = os.fsync
        builtins.open = self._open
        os.fsync = self._fsync
        return self

    def deactivate(self):
        import builtins
        if not self._active:
            return
        builtins.open = self._real_open
        os.fsync = self._real_fsync
        self._active = False
        self._fd_paths.clear()

    __enter__ = activate

    def __exit__(self, *exc):
        self.deactivate()
        return False

    # -- rules -------------------------------------------------------------

    def _install(self, rule: _DiskRule) -> _DiskRule:
        with self._rules_lock:
            self._rules.append(rule)
        return rule

    def corrupt_read(self, pattern: str, times: Optional[int] = None,
                     probability: float = 1.0,
                     mode: str = "bitflip") -> _DiskRule:
        """Serve damaged bytes when a matching file is opened for
        reading: ``bitflip`` XORs one seeded byte, ``truncate`` cuts the
        tail at a seeded offset — the two bit-rot shapes checksum
        verification must catch."""
        if mode not in ("bitflip", "truncate"):
            raise ValueError(f"unknown corruption mode [{mode}]")
        return self._install(_DiskRule(self, "read", pattern, probability,
                                       times, mode=mode))

    def fail_read(self, pattern: str, err: Optional[int] = None,
                  times: Optional[int] = None,
                  probability: float = 1.0) -> _DiskRule:
        """EIO (or ``err``) when a matching file is opened for reading."""
        import errno
        return self._install(_DiskRule(self, "read", pattern, probability,
                                       times, err=err or errno.EIO))

    def fail_write(self, pattern: str, err: Optional[int] = None,
                   times: Optional[int] = None,
                   probability: float = 1.0) -> _DiskRule:
        """EIO (or ``err``) when a matching file is opened for writing."""
        import errno
        return self._install(_DiskRule(self, "write", pattern, probability,
                                       times, err=err or errno.EIO))

    def enospc(self, pattern: str, times: Optional[int] = None,
               probability: float = 1.0) -> _DiskRule:
        """Disk-full on write — the classic slow-death failure mode."""
        import errno
        return self.fail_write(pattern, err=errno.ENOSPC, times=times,
                               probability=probability)

    def fail_fsync(self, pattern: str, err: Optional[int] = None,
                   times: Optional[int] = None,
                   probability: float = 1.0) -> _DiskRule:
        """EIO (or ``err``) from ``os.fsync`` on a matching file — the
        fault FsHealthService's probe exists to notice."""
        import errno
        return self._install(_DiskRule(self, "fsync", pattern, probability,
                                       times, err=err or errno.EIO))

    def slow_fsync(self, pattern: str, seconds: float,
                   times: Optional[int] = None,
                   probability: float = 1.0) -> _DiskRule:
        """Delay ``os.fsync`` on a matching file (degrading-disk shape:
        the write path stalls before it fails)."""
        return self._install(_DiskRule(self, "fsync", pattern, probability,
                                       times, seconds=float(seconds)))

    def remove(self, rule: _DiskRule) -> bool:
        with self._rules_lock:
            if rule in self._rules:
                self._rules.remove(rule)
                return True
        return False

    def clear(self):
        with self._rules_lock:
            self._rules.clear()

    # -- patched entry points ----------------------------------------------

    def _match(self, op: str, path: str) -> Optional[_DiskRule]:
        with self._rules_lock:
            rules = list(self._rules)
        for rule in rules:
            if rule.matches(op, path):
                return rule
        return None

    def _corrupt(self, data: bytes, mode: str) -> bytes:
        if not data:
            return data
        if mode == "truncate":
            return data[: self._randrange(len(data))]
        i = self._randrange(len(data))
        return data[:i] + bytes([data[i] ^ 0xFF]) + data[i + 1:]

    def _open(self, file, mode="r", *args, **kwargs):
        real = self._real_open
        if not isinstance(file, (str, bytes, os.PathLike)):
            return real(file, mode, *args, **kwargs)
        path = os.path.abspath(os.fsdecode(file))
        writing = any(c in mode for c in "wax+")
        rule = self._match("write" if writing else "read", path)
        if rule is not None and "err" in rule.params:
            raise OSError(rule.params["err"],
                          "[fault_injection] injected disk error", path)
        if rule is not None and not writing and "mode" in rule.params:
            with real(path, "rb") as f:
                data = f.read()
            return _CorruptedReader(path, self._corrupt(
                data, rule.params["mode"]), text="b" not in mode)
        f = real(file, mode, *args, **kwargs)
        try:
            self._fd_paths[f.fileno()] = path
        except (OSError, ValueError, AttributeError):
            pass
        return f

    def _fsync(self, fd):
        path = self._fd_paths.get(fd)
        if path is not None:
            rule = self._match("fsync", path)
            if rule is not None:
                if "seconds" in rule.params:
                    time.sleep(rule.params["seconds"])
                else:
                    raise OSError(rule.params["err"],
                                  "[fault_injection] injected fsync error",
                                  path)
        return self._real_fsync(fd)


# ---------------------------------------------------------------------------
# Device fault injection (the accelerator's failure modes)
# ---------------------------------------------------------------------------


class InjectedDeviceError(RuntimeError):
    """Base for injected accelerator faults; ``__device_fault__`` is
    what ``common/device_health.py::is_device_error`` classifies on, so
    the degradation paths treat these exactly like real jax/XLA
    runtime errors."""

    __device_fault__ = True


class InjectedOOMError(InjectedDeviceError):
    """Staging RESOURCE_EXHAUSTED (the device allocator's OOM shape)."""


class InjectedCompileError(InjectedDeviceError):
    """XLA compilation failure at dispatch time."""


class InjectedDispatchError(InjectedDeviceError):
    """A launched device program failing mid-execution."""


class InjectedMeshLossError(InjectedDeviceError):
    """A mesh member dropping out of the device collective."""


class _DeviceRule:
    """One installed device fault: matches (op, names...) by fnmatch
    pattern against any of the site's name candidates (kernel name,
    segment id, staging kind), ``times``-bounded or sticky, probability
    drawn from the injector's seeded stream — the same Directive idioms
    as the transport and disk rules above."""

    def __init__(self, injector: "DeviceFaultInjector", op: str,
                 pattern: str, probability: float, times: Optional[int],
                 **params):
        self.injector = injector
        self.op = op               # stage | dispatch | mesh
        self.pattern = pattern
        self.probability = float(probability)
        self.remaining = times     # None = sticky
        self.params = params
        self.fired = 0
        self._lock = threading.Lock()

    def matches(self, op: str, names: tuple) -> bool:
        if op != self.op:
            return False
        if self.pattern not in ("*", None):
            for name in names:
                # exact first (fnmatch metachars can appear in segment
                # ids), then glob
                if name == self.pattern \
                        or fnmatch.fnmatch(str(name), self.pattern):
                    break
            else:
                return False
        with self._lock:
            if self.remaining is not None and self.remaining <= 0:
                return False
            if self.probability < 1.0 \
                    and self.injector._random() >= self.probability:
                return False
            if self.remaining is not None:
                self.remaining -= 1
            self.fired += 1
        return True


class DeviceFaultInjector:
    """Deterministic accelerator fault injection: while active, wraps
    the sanctioned device entry points — the residency ledger's
    ``stage``/``device_put`` (every H2D transfer flows through them,
    enforced by tools/check_device_staging.py), the query-path kernels
    ``plan.run_topk``/``plan.run_full`` (and the mesh's
    ``run_topk_mesh``), the batched kernel
    ``batch.batch_impact_union_topk``, and the mesh collective
    ``MeshSearcher.search``/``mesh_metric_aggs`` — so matching calls
    misbehave: staging RESOURCE_EXHAUSTED, XLA compile failure,
    dispatch exceptions, slow-device latency, NaN-poisoned top-k
    scores, mesh-member loss.  One-shot or sticky, matched by kernel /
    segment / staging-kind pattern; every probabilistic choice comes
    from one seeded stream, so a fixed seed replays the same faults.

    Usage::

        dev = DeviceFaultInjector(seed=7)
        dev.oom("seg_*")                 # sticky staging OOM
        dev.poison_topk(times=3)         # 3 NaN-poisoned results
        dev.slow_device(0.05, times=2)
        dev.lose_mesh_member()
        with dev:                        # activate() / deactivate()
            ...
    """

    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        self._rng = random.Random(seed)
        self._rng_lock = threading.Lock()
        self._rules: list[_DeviceRule] = []
        self._rules_lock = threading.Lock()
        self._active = False
        self._saved: list[tuple] = []

    def _random(self) -> float:
        with self._rng_lock:
            return self._rng.random()

    # -- lifecycle ---------------------------------------------------------

    def activate(self) -> "DeviceFaultInjector":
        if self._active:
            return self
        self._active = True
        from opensearch_tpu.common.device_ledger import device_ledger
        from opensearch_tpu.parallel import dist_search
        from opensearch_tpu.search import batch as batch_mod
        from opensearch_tpu.search import plan as plan_mod

        led = device_ledger()
        inj = self

        real_stage = led.stage

        def stage(group, host_array, *, kind: str, field: str = "",
                  name: str = ""):
            seg = getattr(group, "segment", "-") if group is not None \
                else "-"
            inj._check("stage", (seg, kind, field))
            return real_stage(group, host_array, kind=kind, field=field,
                              name=name)

        real_put = led.device_put

        def device_put(group, value, sharding=None, *, kind: str = "mesh",
                       field: str = "", name: str = ""):
            seg = getattr(group, "segment", "-") if group is not None \
                else "-"
            inj._check("stage", (seg, kind, name))
            return real_put(group, value, sharding, kind=kind,
                            field=field, name=name)

        self._saved.append((led, "stage", led.__dict__.get("stage")))
        self._saved.append((led, "device_put",
                            led.__dict__.get("device_put")))
        led.stage = stage
        led.device_put = device_put

        def wrap_kernel(mod, attr, name=None):
            real = getattr(mod, attr)
            name = name or attr

            def kernel(*args, **kwargs):
                inj._check("dispatch", (name,))
                out = real(*args, **kwargs)
                return inj._maybe_poison(name, out)
            self._saved.append((mod, attr, real))
            setattr(mod, attr, kernel)

        wrap_kernel(plan_mod, "run_topk")
        # the mesh's one program a segment index over the chips: the
        # same kernel, the same rules
        wrap_kernel(dist_search, "run_topk_mesh", name="run_topk")
        wrap_kernel(plan_mod, "run_full")
        wrap_kernel(batch_mod, "batch_impact_union_topk")

        def wrap_mesh(attr):
            real = getattr(dist_search.MeshSearcher, attr)

            def mesh_entry(ms_self, *args, **kwargs):
                inj._check("mesh", (attr,))
                return real(ms_self, *args, **kwargs)
            self._saved.append((dist_search.MeshSearcher, attr, real))
            setattr(dist_search.MeshSearcher, attr, mesh_entry)

        wrap_mesh("search")
        wrap_mesh("mesh_metric_aggs")
        return self

    def deactivate(self):
        if not self._active:
            return
        for owner, attr, prev in reversed(self._saved):
            if isinstance(owner, type) or hasattr(owner, "__name__"):
                setattr(owner, attr, prev)
            elif prev is None:
                owner.__dict__.pop(attr, None)   # restore the bound method
            else:
                setattr(owner, attr, prev)
        self._saved.clear()
        self._active = False

    __enter__ = activate

    def __exit__(self, *exc):
        self.deactivate()
        return False

    # -- the interception core ---------------------------------------------

    def _match(self, op: str, names: tuple) -> Optional[_DeviceRule]:
        with self._rules_lock:
            rules = list(self._rules)
        for rule in rules:
            if rule.matches(op, names):
                return rule
        return None

    def _check(self, op: str, names: tuple) -> None:
        rule = self._match(op, names)
        if rule is None:
            return
        if "seconds" in rule.params:
            time.sleep(rule.params["seconds"])
            return
        err = rule.params.get("err")
        if err is not None:
            raise err(rule.params["message"].format(names=names))

    def _maybe_poison(self, kernel: str, out):
        """NaN-poison the score component of a top-k kernel result (the
        first array of the tuple; the first ``k`` lanes of ``run_topk``'s
        packed ``int32[2k + 2]``, as bits) — the silent-corruption
        failure shape the result-sanity guard exists to catch."""
        rule = self._match("poison", (kernel,))
        if rule is None:
            return out
        import jax.numpy as jnp
        if not isinstance(out, tuple):
            k = (out.shape[0] - 2) // 2
            return out.at[:k].set(0x7FC00000)      # float32 NaN's bits
        vals = out[0]
        return (jnp.full_like(vals, jnp.nan), *out[1:])

    # -- rules -------------------------------------------------------------

    def _install(self, rule: _DeviceRule) -> _DeviceRule:
        with self._rules_lock:
            self._rules.append(rule)
        return rule

    def oom(self, pattern: str = "*", times: Optional[int] = None,
            probability: float = 1.0) -> _DeviceRule:
        """RESOURCE_EXHAUSTED on matching H2D stagings (pattern matches
        segment id, staging kind, or field)."""
        return self._install(_DeviceRule(
            self, "stage", pattern, probability, times,
            err=InjectedOOMError,
            message="RESOURCE_EXHAUSTED: out of memory while staging "
                    "{names} (injected)"))

    def compile_failure(self, pattern: str = "*",
                        times: Optional[int] = None,
                        probability: float = 1.0) -> _DeviceRule:
        """XLA compile failure on matching kernel dispatches."""
        return self._install(_DeviceRule(
            self, "dispatch", pattern, probability, times,
            err=InjectedCompileError,
            message="INTERNAL: XLA compilation of {names} failed "
                    "(injected)"))

    def dispatch_error(self, pattern: str = "*",
                       times: Optional[int] = None,
                       probability: float = 1.0) -> _DeviceRule:
        """A matching device program fails at launch."""
        return self._install(_DeviceRule(
            self, "dispatch", pattern, probability, times,
            err=InjectedDispatchError,
            message="INTERNAL: device program {names} failed "
                    "(injected)"))

    def slow_device(self, seconds: float, pattern: str = "*",
                    times: Optional[int] = None,
                    probability: float = 1.0) -> _DeviceRule:
        """Matching dispatches stall ``seconds`` before launching (the
        degrading-accelerator latency shape)."""
        return self._install(_DeviceRule(
            self, "dispatch", pattern, probability, times,
            seconds=float(seconds)))

    def poison_topk(self, pattern: str = "*",
                    times: Optional[int] = None,
                    probability: float = 1.0) -> _DeviceRule:
        """Matching top-k kernels return NaN scores instead of real
        ones — caught by the result-sanity guard at the D2H sync, which
        discards and recomputes on the host."""
        return self._install(_DeviceRule(
            self, "poison", pattern, probability, times))

    def lose_mesh_member(self, times: Optional[int] = None,
                         probability: float = 1.0) -> _DeviceRule:
        """The mesh collective loses a member mid-dispatch; the engine
        must demote to the counted host scatter fallback."""
        return self._install(_DeviceRule(
            self, "mesh", "*", probability, times,
            err=InjectedMeshLossError,
            message="UNAVAILABLE: mesh member lost during {names} "
                    "(injected)"))

    def remove(self, rule: _DeviceRule) -> bool:
        with self._rules_lock:
            if rule in self._rules:
                self._rules.remove(rule)
                return True
        return False

    def clear(self):
        with self._rules_lock:
            self._rules.clear()

    def stats(self) -> dict:
        with self._rules_lock:
            return {"rules": len(self._rules),
                    "fired": sum(r.fired for r in self._rules)}


# ---------------------------------------------------------------------------
# Remote blob-store fault injection (the search tier's "S3 is down")
# ---------------------------------------------------------------------------


class RemoteStoreFaultInjector:
    """Deterministic remote-store outage: while active, the given
    repositories' blob reads (searcher pulls) and/or writes (primary
    uploads) raise ``RemoteStoreError`` — the blob-service-outage class
    of fault the transport/disk injectors cannot reach, because the
    store is accessed as a library, not over the cluster transport.

    Each cluster node holds its OWN ``Repository`` object over the
    shared location (every reference node names the same bucket), so
    the injector patches the bound ``read_blob``/``write_blob`` of
    every repo it is given.  Soak's ``stall_remote_store`` directive
    stalls reads fleet-wide; ``release_remote_store`` restores."""

    def __init__(self, repos):
        self._repos = list(repos)
        self._saved: list[tuple] = []
        self.failed_reads = 0
        self.failed_writes = 0
        self._lock = threading.Lock()

    def stall(self, reads: bool = True, writes: bool = False) -> None:
        from opensearch_tpu.index.remote_store import RemoteStoreError
        if self._saved:
            return                       # already active
        for repo in self._repos:
            blobs = repo.blobs
            self._saved.append(
                (blobs, blobs.read_blob, blobs.write_blob))
            if reads:
                def failing_read(name, _inj=self, _repo=repo):
                    with _inj._lock:
                        _inj.failed_reads += 1
                    raise RemoteStoreError(
                        "[fault_injection] remote store stalled "
                        f"(read of [{name}])")
                blobs.read_blob = failing_read
            if writes:
                def failing_write(name, data, fail_if_exists=False,
                                  _inj=self):
                    with _inj._lock:
                        _inj.failed_writes += 1
                    raise RemoteStoreError(
                        "[fault_injection] remote store stalled "
                        f"(write of [{name}])")
                blobs.write_blob = failing_write

    def release(self) -> None:
        for blobs, read, write in self._saved:
            blobs.read_blob = read
            blobs.write_blob = write
        self._saved.clear()

    def __enter__(self):
        self.stall()
        return self

    def __exit__(self, *exc):
        self.release()
