"""Heartbeat health: child-side writer, launcher-side staleness check.

The launcher's fail-whole monitor (launch.py) only sees *exits* — a child
that hangs (deadlocked collective, wedged data pipeline, a device that
stopped answering) keeps the whole job alive forever. Heartbeats close that
gap: every training process touches a per-rank file on its log cadence, and
the launcher treats a heartbeat that stops aging as a hung child, kills it,
and lets the existing attribution + restart machinery (PR 3) take over.

Pure stdlib on both sides — the launcher must never import jax.

Wiring: the launcher exports ``DDL_HEARTBEAT_DIR`` to its children (plus
the pre-existing ``DDL_PROCESS_ID``); the train loop calls
:meth:`HeartbeatWriter.from_env` and beats on log cadence. A child that
never writes (old binary, crashed in startup) is never judged by the
watchdog — staleness only applies after the first beat, so startup/compile
time needs no special-cased grace period.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

ENV_HEARTBEAT_DIR = "DDL_HEARTBEAT_DIR"
_ENV_PROCESS_ID = "DDL_PROCESS_ID"  # set by launch.ProcessSpec.env()

# Elastic membership (launch.py --elastic). The launcher exports one JSON
# env var to the children of a re-formed attempt — {"trigger": "host_lost" |
# "hung" | "host_rejoin" | "host_join" | "host_drain", "degree_before": D0,
# "degree_after": D1, "epoch": E, "detect_t": monotonic-seconds-at-
# detection, "drain_done_t": monotonic-seconds-when-the-last-member-exited}
# — so the training loop can close the reconfiguration_time_s span
# (detection -> first post-resume step) AND split it into phases, all on
# the SAME CLOCK_MONOTONIC clock the launcher read. The rejoin/join marker
# file is how an arriving host announces itself to the membership
# controller: its launcher (or the host_join / host_rejoin fault, in
# simulation) touches it in the shared rendezvous (heartbeat) directory;
# drain markers announce a planned leave the same way.
ENV_ELASTIC_EVENT = "DDL_ELASTIC_EVENT"
# The membership epoch this child was formed under (namespaces its
# heartbeat file and lets it ignore the reform barrier of its OWN epoch).
ENV_ELASTIC_EPOCH = "DDL_ELASTIC_EPOCH"
# The child's ORIGINAL host identity (stable across re-formations, unlike
# DDL_PROCESS_ID which is the slot of the current attempt).
ENV_ELASTIC_HOST = "DDL_ELASTIC_HOST"
# Exit code of a child that drained voluntarily at a step boundary after
# seeing a reform barrier: "try again with the new membership", which is
# exactly os.EX_TEMPFAIL's meaning. The launcher's monitor treats it as a
# planned exit, never a failure.
EXIT_DRAIN = 75
_REJOIN_MARKER = "rejoin"
_DRAIN_PREFIX = "drain."
_REFORM_FILE = "reform.json"


def heartbeat_path(directory: str, process_id: int,
                   epoch: Optional[int] = None) -> str:
    """Per-epoch heartbeat namespace: epoch 0 (or None — every non-elastic
    caller) keeps the legacy ``heartbeat.N`` name; a re-formed membership
    epoch E > 0 beats into ``heartbeat.eE.N``, so a stale file from a
    previous epoch can never feed the new epoch's staleness clock or its
    host-loss attribution."""
    if epoch:
        return os.path.join(directory, f"heartbeat.e{int(epoch)}.{process_id}")
    return os.path.join(directory, f"heartbeat.{process_id}")


def rejoin_path(directory: str) -> str:
    return os.path.join(directory, _REJOIN_MARKER)


def _write_marker(directory: str, name: str, payload: dict) -> None:
    tmp = os.path.join(directory, f".{name}.tmp.{os.getpid()}")
    try:
        with open(tmp, "w") as fh:
            json.dump(payload, fh)
        os.replace(tmp, os.path.join(directory, name))
    except OSError:
        pass


def announce_rejoin(directory: str) -> None:
    """Touch the rejoin marker — a returned host asking the elastic
    controller to grow the job back. Atomic (tmp + replace), best-effort."""
    _write_marker(directory, _REJOIN_MARKER,
                  {"time": time.time(), "pid": os.getpid(),
                   "kind": "host_rejoin"})


def announce_join(directory: str) -> None:
    """The rendezvous-scoped spelling of the same announcement: a NEW (or
    returned) host asking to be folded in at the next step boundary. Shares
    the rejoin marker file — one grow path — but stamps its kind so the
    controller reports the trigger it actually saw."""
    _write_marker(directory, _REJOIN_MARKER,
                  {"time": time.time(), "pid": os.getpid(),
                   "kind": "host_join"})


def consume_rejoin(directory: str) -> bool:
    """True iff a rejoin/join marker existed; the marker is removed
    (consumed) so one announcement triggers exactly one re-formation."""
    try:
        os.remove(rejoin_path(directory))
        return True
    except OSError:
        return False


def consume_join(directory: str) -> Optional[str]:
    """Like :func:`consume_rejoin` but returns the announcement's kind
    (``host_join`` / ``host_rejoin``), or None when no marker existed."""
    path = rejoin_path(directory)
    kind = "host_rejoin"
    try:
        with open(path) as fh:
            payload = json.load(fh)
        if isinstance(payload, dict) and payload.get("kind"):
            kind = str(payload["kind"])
    except (OSError, ValueError):
        pass
    try:
        os.remove(path)
        return kind
    except OSError:
        return None


def announce_drain(directory: str, host: Optional[int] = None) -> None:
    """A planned leave: this host asks to be drained out of the membership
    at the next step boundary (maintenance, rebalancing — the opposite of a
    host_lost, which is involuntary and saves nothing). ``host`` is the
    ORIGINAL host identity; defaults to ``DDL_ELASTIC_HOST`` and then
    ``DDL_PROCESS_ID``."""
    if host is None:
        raw = (os.environ.get(ENV_ELASTIC_HOST)
               or os.environ.get(_ENV_PROCESS_ID, "0"))
        try:
            host = int(raw)
        except ValueError:
            host = 0
    _write_marker(directory, f"{_DRAIN_PREFIX}{int(host)}",
                  {"time": time.time(), "pid": os.getpid()})


def consume_drains(directory: str) -> list[int]:
    """Original host ids with a pending drain announcement; the markers are
    removed (consumed) so each announcement triggers one re-formation."""
    hosts = []
    try:
        names = os.listdir(directory)
    except OSError:
        return hosts
    for name in names:
        if not name.startswith(_DRAIN_PREFIX):
            continue
        suffix = name[len(_DRAIN_PREFIX):]
        if not suffix.isdigit():
            continue
        try:
            os.remove(os.path.join(directory, name))
            hosts.append(int(suffix))
        except OSError:
            pass
    return sorted(hosts)


def reform_path(directory: str) -> str:
    return os.path.join(directory, _REFORM_FILE)


def request_reform(directory: str, *, epoch: int, trigger: str,
                   save: bool = True) -> None:
    """Launcher-side: raise the join/leave barrier. Children poll this file
    at their step boundaries; one whose epoch is older than the barrier's
    saves (when ``save`` — every member is alive, so the collective save
    works) and exits :data:`EXIT_DRAIN` voluntarily. ``save=False`` marks a
    barrier raised because a member is already DEAD (host_lost/hung): a
    collective save would wedge on the missing rank, so survivors exit
    immediately and the re-formed attempt resumes from the last committed
    checkpoint."""
    _write_marker(directory, _REFORM_FILE,
                  {"epoch": int(epoch), "trigger": str(trigger),
                   "save": bool(save), "time": time.time()})


def read_reform(directory: str, *,
                newer_than_epoch: Optional[int] = None) -> Optional[dict]:
    """The pending reform barrier, or None. With ``newer_than_epoch``, a
    barrier at or below that epoch is ignored — a re-formed child must not
    re-drain on the barrier that formed it."""
    try:
        with open(reform_path(directory)) as fh:
            barrier = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(barrier, dict):
        return None
    if newer_than_epoch is not None:
        try:
            if int(barrier.get("epoch", 0)) <= int(newer_than_epoch):
                return None
        except (TypeError, ValueError):
            return None
    return barrier


def clear_reform(directory: str) -> None:
    try:
        os.remove(reform_path(directory))
    except OSError:
        pass


def current_epoch() -> int:
    """The membership epoch this process was formed under (0 outside an
    elastic launcher)."""
    try:
        return int(os.environ.get(ENV_ELASTIC_EPOCH, "0") or 0)
    except ValueError:
        return 0


def poll_drain() -> Optional[dict]:
    """Child-side step-boundary check: the reform barrier demanding THIS
    process drain, or None. One os.stat-grade read per call — cheap enough
    for every step boundary — and only armed under a heartbeat-exporting
    launcher."""
    directory = os.environ.get(ENV_HEARTBEAT_DIR)
    if not directory:
        return None
    return read_reform(directory, newer_than_epoch=current_epoch())


def read_elastic_event() -> Optional[dict]:
    """The launcher-exported membership event this process was re-formed
    under, or None on a normal (non-reconfigured) attempt."""
    raw = os.environ.get(ENV_ELASTIC_EVENT)
    if not raw:
        return None
    try:
        event = json.loads(raw)
    except ValueError:
        return None
    return event if isinstance(event, dict) else None


class HeartbeatWriter:
    """Touches this process's heartbeat file; the file's mtime IS the
    signal (content is a small JSON breadcrumb for humans)."""

    def __init__(self, directory: str, process_id: int = 0,
                 epoch: int = 0):
        self.directory = directory
        self.process_id = int(process_id)
        self.epoch = int(epoch)
        self.path = heartbeat_path(directory, self.process_id, self.epoch)
        os.makedirs(directory, exist_ok=True)

    @classmethod
    def from_env(cls) -> Optional["HeartbeatWriter"]:
        directory = os.environ.get(ENV_HEARTBEAT_DIR)
        if not directory:
            return None
        return cls(directory, int(os.environ.get(_ENV_PROCESS_ID, "0") or 0),
                   epoch=current_epoch())

    def beat(self, step: int) -> None:
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as fh:
                json.dump({"step": int(step), "time": time.time(),
                           "pid": os.getpid()}, fh)
            os.replace(tmp, self.path)
        except OSError:
            pass  # a full/broken disk must never kill the training step


def check_stale(directory: str, num_processes: int, timeout_s: float,
                now: Optional[float] = None,
                epoch: Optional[int] = None) -> list[tuple[int, float]]:
    """(process_id, age_s) for every child whose heartbeat file exists and
    is older than ``timeout_s``. ``now`` is injectable (fake clock in
    tests); it is compared against file mtimes, so tests steer it with
    ``os.utime``. Children that never beat are not reported — the watchdog
    arms per child on its first beat. ``epoch`` selects the membership
    epoch's heartbeat namespace (None/0 = the legacy files)."""
    if now is None:
        now = time.time()
    stale = []
    for pid in range(num_processes):
        try:
            mtime = os.stat(heartbeat_path(directory, pid, epoch)).st_mtime
        except OSError:
            continue
        age = now - mtime
        if age > timeout_s:
            stale.append((pid, age))
    return stale
