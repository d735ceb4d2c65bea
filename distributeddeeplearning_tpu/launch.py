"""Pod-slice launcher — the TPU-native replacement for mpirun / Batch-AI.

The reference launched N ranks with ``mpirun`` under a Batch-AI job and let
MPI handle rendezvous (SURVEY.md §2 #9-#10, §3.1). On TPU the moral
equivalents are:

- **rendezvous**: ``jax.distributed.initialize(coordinator, num_processes,
  process_id)`` — replaces ``MPI_Init``; XLA then sees the global device set.
- **process placement**: one Python process per TPU host. On Cloud TPU pod
  slices the TPU runtime supplies topology env vars and
  ``jax.distributed.initialize()`` needs no arguments; everywhere else (and
  for local multi-process development on CPU) this module wires the
  coordinator explicitly through ``DDL_*`` env vars.
- **failure detection** (SURVEY.md §5.3): the reference's mpirun died whole
  when any rank died. ``monitor`` reproduces that for the processes this
  launcher owns: first local child to exit nonzero triggers terminate-all
  and a nonzero launcher exit, so a wrapper can restart the job from the
  last checkpoint (fail-whole + checkpoint-resume semantics). Across hosts
  (``--hostfile``), each host's launcher only sees its own child; a *remote*
  rank's death reaches the survivors through jax.distributed's coordinator
  heartbeat, which tears down their processes — the local launcher then
  reports that nonzero exit. Cross-host detection latency is therefore the
  heartbeat timeout, not this monitor's poll interval.

Usage (local dev, 2 simulated hosts on CPU):
    python launch.py --num-processes 2 -- python train.py --backend cpu ...

Usage (TPU pod slice, run on every host, e.g. via gcloud ssh --worker=all):
    python launch.py -- python train.py --backend tpu ...
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import time
from typing import Callable, Optional, Sequence

from distributeddeeplearning_tpu import hostmesh
from distributeddeeplearning_tpu.observability import flight as flightlib
from distributeddeeplearning_tpu.observability import health, telemetry
from distributeddeeplearning_tpu.observability import metrics as metricslib
from distributeddeeplearning_tpu.robustness import faults

ENV_COORDINATOR = "DDL_COORDINATOR"
ENV_NUM_PROCESSES = "DDL_NUM_PROCESSES"
ENV_PROCESS_ID = "DDL_PROCESS_ID"

# Exit codes that mean "the operator stopped the job", never "retry":
# 130 = SIGINT via shell, 143 = SIGTERM via shell (128+15), -15 = SIGTERM
# as reported by subprocess.Popen for a signal-killed child.
_OPERATOR_STOP_RCS = (130, 143, -15)


@dataclasses.dataclass(frozen=True)
class ProcessSpec:
    """One training process in the job (≈ one MPI rank, one TPU host)."""

    process_id: int
    num_processes: int
    coordinator: str  # "host:port"

    def env(self) -> dict[str, str]:
        return {
            ENV_COORDINATOR: self.coordinator,
            ENV_NUM_PROCESSES: str(self.num_processes),
            ENV_PROCESS_ID: str(self.process_id),
        }


def plan_local(num_processes: int, *, port: int = 9531,
               coordinator_host: str = "127.0.0.1") -> list[ProcessSpec]:
    """Specs for N processes on this machine (multi-host simulation)."""
    coord = f"{coordinator_host}:{port}"
    return [ProcessSpec(i, num_processes, coord) for i in range(num_processes)]


def plan_from_hostfile(path: str, *, port: int = 9531) -> list[ProcessSpec]:
    """Specs from a one-host-per-line file (first host is coordinator) —
    the launcher-side analogue of an MPI hostfile. Each host runs the
    launcher with ``--process-id`` matching its line number."""
    with open(path) as f:
        hosts = [ln.strip() for ln in f if ln.strip()
                 and not ln.lstrip().startswith("#")]
    if not hosts:
        raise ValueError(f"hostfile {path!r} lists no hosts")
    coord = f"{hosts[0]}:{port}"
    return [ProcessSpec(i, len(hosts), coord) for i in range(len(hosts))]


def maybe_initialize_distributed() -> Optional[int]:
    """Called by train.py at startup. Joins the job if one is configured.

    Returns the process id when distributed was initialized, else None.
    Resolution order:
    1. ``DDL_*`` env vars (set by this launcher) → explicit initialize;
    2. Cloud TPU pod-slice env (multi-host libtpu topology) → argless
       initialize, deferring to the TPU runtime's own metadata;
    3. otherwise single-process: do nothing.
    """
    import jax

    if os.environ.get(ENV_COORDINATOR):
        spec = ProcessSpec(
            process_id=int(os.environ[ENV_PROCESS_ID]),
            num_processes=int(os.environ[ENV_NUM_PROCESSES]),
            coordinator=os.environ[ENV_COORDINATOR])
        # (Multi-process runs on the CPU backend — virtual hosts in tests,
        # the elastic soak — ride jaxlib's gloo collectives, its default.)
        jax.distributed.initialize(
            coordinator_address=spec.coordinator,
            num_processes=spec.num_processes,
            process_id=spec.process_id)
        return spec.process_id
    # Cloud TPU pod slice: the runtime's own topology env lists >1 worker
    # host; defer entirely to it. (A 1-host listing — also what this dev
    # image sets — is single-process and needs no rendezvous.)
    workers = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len(workers.split(",")) > 1:
        jax.distributed.initialize()
        return jax.process_index()
    return None


# ---------------------------------------------------------------------------
# Child spawn + monitoring (fail-whole semantics)
# ---------------------------------------------------------------------------

def spawn(spec: ProcessSpec, command: Sequence[str], *,
          extra_env: Optional[dict[str, str]] = None) -> subprocess.Popen:
    env = dict(os.environ)
    env.update(spec.env())
    env.update(extra_env or {})
    return subprocess.Popen(list(command), env=env)


def attribute_failure(heartbeat_dir: Optional[str], slot: int, *,
                      hung: bool = False, ever_beat: bool = False,
                      epoch: Optional[int] = None) -> str:
    """Classify one failed child from the heartbeat evidence.

    The hang watchdog and the elastic controller share ONE staleness clock
    (``--heartbeat-timeout`` over the same files), so the three verdicts
    partition cleanly:

    - ``hung``       — the watchdog killed it for heartbeat staleness while
      the process lived; the host is unusable either way, so elastic mode
      treats it as host loss.
    - ``host_lost``  — the child HAD a heartbeat and the file vanished with
      the process: a dead host takes its filesystem presence with it (the
      ``host_lost`` fault models exactly this). A transient crash leaves
      its last heartbeat behind.
    - ``crash``      — heartbeat intact (or never armed): the host is fine,
      the process died; the generic restart path applies.
    """
    if hung:
        return "hung"
    if (heartbeat_dir is not None and ever_beat and not os.path.exists(
            health.heartbeat_path(heartbeat_dir, slot, epoch))):
        return "host_lost"
    return "crash"


class ElasticController:
    """Membership controller for ``--elastic``: automatic re-formation at a
    new data-parallel degree on host loss or gain.

    The controller owns the live host set of a local simulated pod. When
    the monitor attributes a failure as host loss (or hang — same staleness
    clock), the lost host leaves the set and the next attempt re-plans at
    the surviving degree: fewer processes, the training command's ``--dp``
    rewritten to ``devices_per_host x live_hosts``, coordinator env
    re-exported by ``plan_local`` as usual. The global batch is left
    untouched, so a transformer trajectory continues bitwise through the
    re-formation (tests/test_elastic_resume.py). A returning host announces
    itself through the rejoin marker (observability/health.py); the monitor
    then stops the job gracefully (children save at the next step boundary
    via the loop's preemption handler) and the same machinery grows the
    plan back.

    Re-formations are PLANNED reconfigurations: ``run_with_restarts``
    relaunches without exponential backoff (the delay exists to
    de-synchronise shared-cause crash storms) and without burning the
    restart budget (which guards against crash loops — a re-formation IS
    the recovery). Pure stdlib, like the rest of the launcher.

    **Rendezvous membership** (this PR): the controller holds a membership
    ``epoch``, bumped per committed re-formation. A membership change
    (join/rejoin/drain marker, or a host-loss attribution) raises the
    reform barrier (``health.request_reform``) instead of tearing surviving
    children down: each child polls the barrier at its step boundary, saves
    collectively when every member is alive (``save=True``), and exits
    ``health.EXIT_DRAIN`` voluntarily. Heartbeats are namespaced per epoch
    so a previous epoch's frozen files never feed the new epoch's staleness
    clock. An optional **geometry table** (``--elastic-geometry``) maps
    live-host counts to full mesh shapes (dp/pp/optimizer-sharding),
    letting re-formation cross the ZeRO-stage and pipeline axes — the
    canonical checkpoint layout makes any pair restorable. When the table
    forces a smaller host count than survived, **topology-aware survivor
    selection** (hostmesh.select_survivors) keeps the ICI ring contiguous,
    logging chosen + rejected candidates to flight.
    """

    def __init__(self, num_hosts: int, heartbeat_dir: str, *, base_dp: int,
                 min_hosts: int = 1,
                 tele: Optional[telemetry.Telemetry] = None,
                 geometry: Optional[dict[int, dict]] = None):
        if num_hosts < 1:
            raise ValueError(f"num_hosts must be >= 1, got {num_hosts}")
        if base_dp % num_hosts:
            raise ValueError(
                f"--dp {base_dp} does not divide evenly over {num_hosts} "
                f"host(s); elastic re-formation needs a whole number of "
                f"data shards per host")
        self.max_hosts = num_hosts
        self.devices_per_host = base_dp // num_hosts
        self.heartbeat_dir = heartbeat_dir
        self.min_hosts = max(int(min_hosts), 1)
        self.tele = tele
        self.geometry = dict(geometry or {})  # live hosts -> mesh shape
        self.epoch = 0                        # membership epoch (0 = first)
        self.live = list(range(num_hosts))   # original host ids, sorted
        self.events: list[dict] = []         # committed re-formations
        self._slots = list(self.live)        # slot -> host id, per attempt
        self._pending: Optional[dict] = None
        self._export: Optional[dict] = None

    @property
    def num_processes(self) -> int:
        return len(self.live)

    @property
    def degree(self) -> int:
        geo = self.geometry.get(len(self.live))
        if geo is not None:
            return int(geo["dp"])
        return self.devices_per_host * len(self.live)

    @property
    def has_pending(self) -> bool:
        """A membership change is planned but not yet committed — the
        monitor uses this to pick the drain barrier over fail-whole."""
        return self._pending is not None

    @property
    def pending_trigger(self) -> Optional[str]:
        return self._pending["trigger"] if self._pending else None

    def command(self, command: Sequence[str]) -> list[str]:
        """The training command at the current membership. Default: ``--dp``
        rewritten to ``devices_per_host x live`` (global batch untouched —
        trajectories stay bitwise). With a geometry entry for the live host
        count, the full mesh shape is rewritten: ``--dp``, ``--pp``, and
        ``--optimizer-sharding`` — cross-axis re-formation routed through
        the canonical checkpoint layout."""
        out = _with_flag_value(command, "--dp", str(self.degree))
        geo = self.geometry.get(len(self.live))
        if geo is not None:
            if "pp" in geo:
                out = _with_flag_value(out, "--pp", str(geo["pp"]))
            if "sharding" in geo:
                out = _with_flag_value(out, "--optimizer-sharding",
                                       str(geo["sharding"]))
        return out

    def child_env(self, base: dict[int, dict[str, str]]) -> dict:
        """Per-slot extra env for the next attempt. Fault plans follow the
        ORIGINAL host identity across re-formations (a plan injected into
        host 2 stays with host 2 whatever slot it lands on); every child
        learns its membership epoch (``DDL_ELASTIC_EPOCH`` — heartbeat
        namespace + barrier filter) and original host id
        (``DDL_ELASTIC_HOST`` — drain announcements); and every child of a
        re-formed attempt receives the membership event
        (``DDL_ELASTIC_EVENT``) so the loop can close the
        reconfiguration_time_s span on the shared monotonic clock."""
        self._slots = list(self.live)
        out: dict[int, dict[str, str]] = {}
        for slot, host in enumerate(self._slots):
            env = dict(base.get(host) or {})
            env[health.ENV_ELASTIC_EPOCH] = str(self.epoch)
            env[health.ENV_ELASTIC_HOST] = str(host)
            if self._export is not None:
                env[health.ENV_ELASTIC_EVENT] = json.dumps(self._export)
            out[slot] = env
        self._export = None  # the event tags exactly one attempt
        return out

    def note_failure(self, slot: int, rc: int, *, hung: bool = False,
                     ever_beat: bool = False) -> str:
        """Attribute one failed child; on host loss, shrink the membership
        and plan a re-formation. Returns the attribution string."""
        label = attribute_failure(self.heartbeat_dir, slot, hung=hung,
                                  ever_beat=ever_beat, epoch=self.epoch)
        if label in ("hung", "host_lost"):
            host = (self._slots[slot] if slot < len(self._slots) else None)
            if host is not None and host in self.live:
                before = self.degree
                self.live.remove(host)
                self._shrink_to_feasible()
                self._plan(label, before)
        return label

    def poll_rejoin(self) -> bool:
        """Consume a rejoin/join announcement. True when lost hosts
        returned and a grow re-formation is now planned — the monitor
        should then drain the job at the barrier. A marker with no one
        missing is consumed and ignored (the cluster is already whole)."""
        kind = health.consume_join(self.heartbeat_dir)
        if kind is None:
            return False
        if len(self.live) >= self.max_hosts:
            return False
        before = self.degree
        self.live = list(range(self.max_hosts))
        self._plan(kind, before)
        return True

    def poll_membership(self) -> Optional[str]:
        """Consume every pending membership announcement — join/rejoin
        markers (grow) and drain markers (planned leave) — and return the
        trigger of the newly planned re-formation, or None. The monitor
        calls this each poll; a returned trigger means it should raise the
        reform barrier."""
        trigger: Optional[str] = None
        if self.poll_rejoin():
            trigger = self._pending["trigger"]
        for host in health.consume_drains(self.heartbeat_dir):
            if host not in self.live:
                continue
            if len(self.live) <= max(self.min_hosts, 1):
                print(f"# launcher: drain of host {host} ignored — only "
                      f"{len(self.live)} host(s) live (min "
                      f"{self.min_hosts})", file=sys.stderr, flush=True)
                continue
            before = self.degree
            self.live.remove(host)
            self._shrink_to_feasible()
            self._plan("host_drain", before)
            trigger = "host_drain"
        return trigger

    def _shrink_to_feasible(self) -> None:
        """With a geometry table, only listed host counts (plus the full
        pod) have a mesh shape; after a shrink, land on the largest
        feasible count <= survivors using topology-aware survivor
        selection (ICI ring contiguity). Without a table every count is
        feasible (dp-only scaling) and this is a no-op."""
        if not self.geometry:
            return
        feasible = sorted(set(self.geometry) | {self.max_hosts})
        target = max((f for f in feasible if f <= len(self.live)),
                     default=None)
        if target is None or target >= len(self.live):
            return
        survivors, rejected = hostmesh.select_survivors(
            self.live, target, self.max_hosts)
        contiguous = hostmesh.is_contiguous_arc(survivors, self.max_hosts)
        flightlib.get().record(
            "survivor_selection", candidates=list(self.live),
            chosen=survivors, rejected=rejected,
            ring_size=self.max_hosts, contiguous=contiguous)
        print(f"# launcher: topology-aware shrink: hosts {self.live} -> "
              f"{survivors} (rejected {rejected}; ring "
              f"{'contiguous' if contiguous else 'BISECTED'})",
              file=sys.stderr, flush=True)
        self.live = survivors

    def note_drain_complete(self) -> None:
        """Stamp the moment the last member exited into the pending event —
        the detect->drain phase boundary of the reconfiguration breakdown."""
        if self._pending is not None:
            self._pending["drain_done_t"] = telemetry.now_s()

    def _plan(self, trigger: str, degree_before: int) -> None:
        now = telemetry.now_s()
        flightlib.get().record("membership", trigger=trigger,
                               degree_before=degree_before,
                               degree_after=self.degree,
                               live_hosts=list(self.live))
        if self._pending is None:
            self._pending = {"trigger": trigger,
                             "degree_before": degree_before,
                             "degree_after": self.degree,
                             # save-capable iff no member is dead: a
                             # collective save would wedge on a lost rank.
                             "save": trigger not in ("host_lost", "hung"),
                             "detect_t": now}
        else:
            # Several hosts lost in one poll: one re-formation, spanning
            # from the pre-batch degree to the final survivors.
            self._pending["degree_after"] = self.degree
            if trigger in ("host_lost", "hung"):
                self._pending["save"] = False

    def take_reconfiguration(self) -> Optional[dict]:
        """The planned membership change for the next attempt, or None.
        Consumes the plan, bumps the membership epoch, and arms the event
        export for the re-formed children. Returns None (give up -> generic
        failure path) when the surviving set is below ``min_hosts``."""
        event, self._pending = self._pending, None
        if event is None:
            return None
        if len(self.live) < self.min_hosts or not self.live:
            print(f"# launcher: elastic: only {len(self.live)} host(s) "
                  f"survive (min {self.min_hosts}) — cannot re-form, "
                  f"giving up", file=sys.stderr, flush=True)
            return None
        event["degree_after"] = self.degree
        self.epoch += 1
        event["epoch"] = self.epoch
        self.events.append(dict(event))
        self._export = dict(event)
        return event


def _await_drain(procs: Sequence[subprocess.Popen], heartbeat_dir: str,
                 elastic: "ElasticController", trigger: str, *, save: bool,
                 deadline_s: float, poll_interval_s: float = 0.2,
                 grace_s: float = 10.0) -> None:
    """Raise the reform barrier and wait for every child to exit on its
    own — the no-teardown half of rendezvous membership. Children poll the
    barrier at their step boundaries, save collectively when ``save`` (all
    members alive), and exit ``health.EXIT_DRAIN``. A child wedged past the
    deadline (e.g. a survivor stuck in a collective with a dead peer that
    gloo never errors out of) is escalated to the old terminate path."""
    health.request_reform(heartbeat_dir, epoch=elastic.epoch + 1,
                          trigger=trigger, save=save)
    flightlib.get().record("reform_barrier", trigger=trigger,
                           epoch=elastic.epoch + 1, save=save)
    deadline = time.monotonic() + deadline_s
    escalated = False
    while any(p.poll() is None for p in procs):
        if time.monotonic() > deadline:
            late = sum(1 for p in procs if p.poll() is None)
            print(f"# launcher: drain barrier deadline ({deadline_s:.0f}s) "
                  f"passed with {late} child(ren) still running — "
                  f"escalating to terminate", file=sys.stderr, flush=True)
            flightlib.get().record("drain_escalated", children=late,
                                   trigger=trigger)
            _terminate_all(procs, grace_s)
            escalated = True
            break
        time.sleep(poll_interval_s)
    elastic.note_drain_complete()
    health.clear_reform(heartbeat_dir)
    rcs = [p.poll() for p in procs]
    drained = sum(1 for rc in rcs if rc == health.EXIT_DRAIN)
    flightlib.get().record("drain_complete", trigger=trigger,
                           drained=drained, rcs=[int(rc) if rc is not None
                                                 else None for rc in rcs],
                           escalated=escalated)
    print(f"# launcher: drain complete — {drained}/{len(rcs)} child(ren) "
          f"exited at the barrier (rc={health.EXIT_DRAIN})",
          file=sys.stderr, flush=True)


def monitor(children: Sequence[subprocess.Popen], *,
            poll_interval_s: float = 0.2,
            grace_s: float = 10.0,
            heartbeat_dir: Optional[str] = None,
            heartbeat_timeout_s: float = 0.0,
            heartbeat_epoch: int = 0,
            tele: Optional[telemetry.Telemetry] = None,
            elastic: Optional["ElasticController"] = None) -> int:
    """Wait for all children; kill the survivors as soon as one fails.

    Returns 0 iff every child exited 0 — the contract a restart wrapper
    checks before deciding to relaunch from the last checkpoint.

    ``heartbeat_dir`` + ``heartbeat_timeout_s > 0`` arm the hang watchdog
    (observability/health.py): a child whose heartbeat file stops aging for
    longer than the timeout is presumed hung (deadlocked collective, wedged
    loader) and SIGKILLed — the next poll then attributes it and tears the
    job down fail-whole, exactly like a crash. A child that never beat is
    never judged, so startup/compile time needs no grace tuning.

    With an ``elastic`` controller, failures are attributed from the
    heartbeat evidence (crash vs host_lost vs hung) and host losses shrink
    the controller's membership for the next attempt; a join/rejoin/drain
    marker in the heartbeat dir raises the reform barrier — children save
    at their next step boundary and exit voluntarily (rendezvous
    membership: surviving children are never torn down for a planned
    change). ``heartbeat_epoch`` selects the heartbeat namespace this
    attempt's children beat into.
    """
    procs = list(children)
    hb_armed = heartbeat_dir is not None and heartbeat_timeout_s > 0
    track_beats = heartbeat_dir is not None and (hb_armed or
                                                 elastic is not None)
    ever_beat: set[int] = set()   # slots whose heartbeat file ever appeared
    hung: set[int] = set()        # slots the watchdog killed for staleness
    try:
        while True:
            if track_beats:
                for idx in range(len(procs)):
                    if idx not in ever_beat and os.path.exists(
                            health.heartbeat_path(heartbeat_dir, idx,
                                                  heartbeat_epoch)):
                        ever_beat.add(idx)
            if hb_armed:
                for idx, age in health.check_stale(
                        heartbeat_dir, len(procs), heartbeat_timeout_s,
                        epoch=heartbeat_epoch):
                    if idx < len(procs) and procs[idx].poll() is None:
                        print(f"# launcher: child {idx} heartbeat stale "
                              f"({age:.1f}s > {heartbeat_timeout_s:.1f}s) — "
                              f"presumed hung, killing (fail-whole)",
                              file=sys.stderr, flush=True)
                        if tele is not None:
                            tele.instant("launcher:heartbeat_stale",
                                         child=idx, age_s=round(age, 1))
                        flightlib.get().record("heartbeat_stale", child=idx,
                                               age_s=round(age, 1))
                        hung.add(idx)
                        procs[idx].kill()
            if elastic is not None:
                trigger = elastic.poll_membership()
                if trigger is not None:
                    # A membership change was announced while every member
                    # is alive: raise the reform barrier instead of tearing
                    # the job down. Children save collectively at their
                    # next step boundary and exit EXIT_DRAIN voluntarily —
                    # run_with_restarts then relaunches at the new
                    # membership without burning the budget.
                    if trigger in ("host_rejoin", "host_join"):
                        print(f"# launcher: host rejoin announced "
                              f"({trigger}) — draining at the reform "
                              f"barrier to re-form at the grown degree",
                              file=sys.stderr, flush=True)
                    else:
                        print(f"# launcher: host drain announced — "
                              f"draining at the reform barrier to re-form "
                              f"at the shrunk degree",
                              file=sys.stderr, flush=True)
                    if tele is not None:
                        tele.instant("launcher:membership_change",
                                     trigger=trigger)
                    if trigger in ("host_rejoin", "host_join"):
                        flightlib.get().record("host_rejoin",
                                               trigger=trigger)
                    else:
                        flightlib.get().record("host_drain", trigger=trigger)
                    _await_drain(procs, heartbeat_dir, elastic, trigger,
                                 save=True, deadline_s=max(grace_s, 30.0),
                                 poll_interval_s=poll_interval_s,
                                 grace_s=grace_s)
                    return 1
            codes = [p.poll() for p in procs]
            failed = [(i, c) for i, c in enumerate(codes)
                      if c not in (None, 0)]
            if failed:
                # Failure attribution BEFORE tearing the job down: once the
                # survivors are terminated every child is "dead", and the
                # operator can no longer tell the culprit from the victims.
                for idx, c in failed:
                    why = f" (killed by signal {-c})" if c < 0 else ""
                    attributed = ""
                    label = None
                    if heartbeat_dir is not None:
                        if elastic is not None:
                            label = elastic.note_failure(
                                idx, int(c), hung=idx in hung,
                                ever_beat=idx in ever_beat)
                        else:
                            label = attribute_failure(
                                heartbeat_dir, idx, hung=idx in hung,
                                ever_beat=idx in ever_beat)
                        attributed = f" [attributed: {label}]"
                        if tele is not None:
                            tele.instant("launcher:failure_attributed",
                                         child=idx, attribution=label)
                    flightlib.get().record("child_exit", child=idx,
                                           rc=int(c), attribution=label)
                    print(f"# launcher: child {idx} exited rc={c}{why}"
                          f"{attributed}", file=sys.stderr, flush=True)
                survivors = sum(1 for c in codes if c is None)
                if (elastic is not None and elastic.has_pending
                        and survivors):
                    # Host loss with a re-formation planned: survivors
                    # drain at the reform barrier instead of being torn
                    # down. save=False — the dead peer makes a collective
                    # save impossible (a gloo save would wedge on the
                    # missing rank); survivors exit at their next step
                    # boundary and the re-formed attempt resumes from the
                    # last committed checkpoint. A survivor that crashes
                    # first on its own collective error counts as exited.
                    print(f"# launcher: membership loss — draining "
                          f"{survivors} surviving child(ren) at the reform "
                          f"barrier (no teardown)",
                          file=sys.stderr, flush=True)
                    _await_drain(procs, heartbeat_dir, elastic,
                                 elastic.pending_trigger or "host_lost",
                                 save=False,
                                 deadline_s=max(grace_s, 10.0),
                                 poll_interval_s=poll_interval_s,
                                 grace_s=grace_s)
                    return int(failed[0][1]) or 1
                if survivors:
                    print(f"# launcher: terminating {survivors} surviving "
                          "child(ren) (fail-whole)",
                          file=sys.stderr, flush=True)
                _terminate_all(procs, grace_s)
                return int(failed[0][1]) or 1
            if all(c == 0 for c in codes):
                return 0
            time.sleep(poll_interval_s)
    except KeyboardInterrupt:
        _terminate_all(procs, grace_s)
        return 130


def _terminate_all(procs: Sequence[subprocess.Popen], grace_s: float) -> None:
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    deadline = time.monotonic() + grace_s
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                p.kill()


def run_local(num_processes: int, command: Sequence[str], *,
              port: int = 9531,
              child_env: Optional[dict[int, dict[str, str]]] = None,
              heartbeat_dir: Optional[str] = None,
              heartbeat_timeout_s: float = 0.0,
              heartbeat_epoch: int = 0,
              tele: Optional[telemetry.Telemetry] = None,
              elastic: Optional["ElasticController"] = None) -> int:
    """Spawn + monitor N local processes (the `mpirun -np N` replacement).

    ``child_env`` maps process_id → extra env vars for that child only —
    how ``--child-fault-plan`` targets one rank of a simulated pod.
    With a ``heartbeat_dir``, children are told to beat there
    (``DDL_HEARTBEAT_DIR``; the train loop beats on log cadence) and the
    monitor watches for staleness. ``heartbeat_epoch`` names the membership
    epoch this attempt beats under (elastic rendezvous; 0 = the legacy
    un-namespaced files).
    """
    specs = plan_local(num_processes, port=port)
    if heartbeat_dir is not None:
        # A restarted attempt must not be judged by the previous attempt's
        # (now frozen) heartbeats: each attempt re-arms from nothing.
        for s in specs:
            try:
                os.remove(health.heartbeat_path(heartbeat_dir, s.process_id,
                                                heartbeat_epoch))
            except OSError:
                pass
    children = []
    for s in specs:
        extra = dict((child_env or {}).get(s.process_id) or {})
        if heartbeat_dir is not None:
            extra[health.ENV_HEARTBEAT_DIR] = heartbeat_dir
            extra.setdefault(health.ENV_ELASTIC_EPOCH, str(heartbeat_epoch))
        children.append(spawn(s, command, extra_env=extra))
    return monitor(children, heartbeat_dir=heartbeat_dir,
                   heartbeat_timeout_s=heartbeat_timeout_s,
                   heartbeat_epoch=heartbeat_epoch, tele=tele,
                   elastic=elastic)


def _backoff_delay(attempt: int, base_s: float, cap_s: float) -> float:
    """Exponential backoff with deterministic jitter.

    Jitter de-synchronises many launchers restarting after a shared-cause
    failure (coordinator blip) without randomness — a Knuth-hash fraction of
    the attempt number, so reruns of the same job back off identically.
    """
    delay = base_s * 2.0 ** max(attempt - 1, 0)
    frac = ((attempt * 2654435761) & 0xFFFFFFFF) / 2.0 ** 32
    return min(delay * (1.0 + 0.25 * frac), cap_s)


def _latest_ckpt_step(directory: str) -> Optional[int]:
    """Largest numeric subdirectory of an orbax root, stdlib-only (the
    launcher must not import jax/orbax — children own the accelerator)."""
    try:
        steps = [int(n) for n in os.listdir(directory) if n.isdigit()]
    except OSError:
        return None
    return max(steps, default=None)


def run_with_restarts(run_once, max_restarts: int, *,
                      backoff_s: float = 3.0,
                      backoff_cap_s: float = 60.0,
                      progress_fn: Optional[Callable[[], object]] = None,
                      sleep=None,
                      tele: Optional[telemetry.Telemetry] = None,
                      elastic: Optional["ElasticController"] = None) -> int:
    """Fail-whole + auto-relaunch: the in-launcher restart wrapper.

    The reference's failure story was "mpirun dies whole, Batch AI resubmits
    the job" (SURVEY.md §5.3); ``run_once`` is one whole-job attempt, and a
    nonzero exit relaunches it with exponential backoff (``backoff_s``
    doubling per consecutive failure, capped at ``backoff_cap_s``, with
    deterministic jitter). Paired with checkpoint-resume
    (train/checkpoint.py restores latest and the data stream repositions),
    each relaunch continues from the last saved step.

    ``max_restarts`` is a *restart budget between progress*, not a lifetime
    total: when ``progress_fn`` (e.g. latest checkpoint step) returns a new
    value after an attempt, the budget refills — a job that keeps advancing
    survives any number of transient faults, while a crash-loop that never
    reaches the next checkpoint exhausts the budget and stops. Without a
    ``progress_fn`` the budget is a plain lifetime cap (old behaviour).

    Operator stops (rc 130 = SIGINT, 143/-15 = SIGTERM) are never retried —
    a preempted child that saved and exited via its SIGTERM handler, or an
    operator ^C, must not resurrect the job.

    Each attempt exports its index via ``DDL_FAULT_PLAN``'s companion env
    (``DDL_RESTART_ATTEMPT``) so attempt-scoped fault injection
    (robustness/faults.py) fires only on the intended attempt.

    With an ``elastic`` controller, an attempt that ended in a PLANNED
    membership change (host lost -> shrink; host rejoined -> grow)
    relaunches immediately: no exponential backoff (the delay exists to
    de-synchronise shared-cause crash storms, not planned
    reconfigurations) and no restart-budget charge (the budget guards
    against crash loops; a re-formation IS the recovery). ^C (rc 130)
    still stops unconditionally.

    ``sleep`` is injectable for tests (defaults to ``time.sleep``).
    """
    do_sleep = sleep if sleep is not None else time.sleep
    total = 0          # attempts so far (exported to children)
    window_used = 0    # restarts consumed since the last observed progress
    last_progress = progress_fn() if progress_fn is not None else None
    prev_attempt = os.environ.get(faults.ENV_ATTEMPT)
    storm_detector = None  # lazy: only elastic jobs pay for it
    try:
        while True:
            os.environ[faults.ENV_ATTEMPT] = str(total)
            rc = run_once()
            total += 1
            if rc == 0:
                return rc
            if tele is not None:
                tele.instant("launcher:attempt_failed", rc=rc,
                             attempt=total - 1)
            flightlib.get().record("attempt_failed", rc=rc,
                                   attempt=total - 1)
            if rc == 130:
                # ^C is ALWAYS an operator stop, even mid-reconfiguration.
                print(f"# launcher: operator stop (rc={rc}); not retrying",
                      file=sys.stderr, flush=True)
                return rc
            if elastic is not None:
                event = elastic.take_reconfiguration()
                if event is not None:
                    print(f"# launcher: elastic re-formation "
                          f"({event['trigger']}): degree "
                          f"{event['degree_before']} -> "
                          f"{event['degree_after']} — relaunching "
                          f"immediately (planned reconfiguration: no "
                          f"backoff, budget untouched)",
                          file=sys.stderr, flush=True)
                    if tele is not None:
                        tele.instant("launcher:elastic_reconfigure",
                                     trigger=event["trigger"],
                                     degree_before=event["degree_before"],
                                     degree_after=event["degree_after"])
                    # The loop records "reconfiguration" when the re-formed
                    # attempt lands its first step; this is the plan side.
                    flightlib.get().record(
                        "reconfiguration_planned",
                        trigger=event["trigger"],
                        degree_before=event["degree_before"],
                        degree_after=event["degree_after"],
                        epoch=event.get("epoch"))
                    # Re-formation storm watch: a handful of planned
                    # re-formations is the feature working; a storm means
                    # membership is flapping faster than training can
                    # amortize (observability/anomaly.py discipline).
                    if storm_detector is None:
                        from distributeddeeplearning_tpu.observability \
                            import anomaly as anomalylib
                        storm_detector = anomalylib.AnomalyDetector()
                    flagged = storm_detector.update_elastic(
                        telemetry.now_s(), epoch=event.get("epoch"))
                    if flagged:
                        from distributeddeeplearning_tpu.observability \
                            import anomaly as anomalylib
                        anomalylib.report(flagged,
                                          flight_rec=flightlib.get(),
                                          tele=tele)
                    if progress_fn is not None:
                        # A re-formed attempt starts a fresh progress
                        # window — don't let the pre-shrink baseline
                        # double-count as progress later.
                        last_progress = progress_fn()
                    continue
            if rc in _OPERATOR_STOP_RCS:
                print(f"# launcher: operator stop (rc={rc}); not retrying",
                      file=sys.stderr, flush=True)
                return rc
            if progress_fn is not None:
                progress = progress_fn()
                if progress != last_progress and window_used:
                    print(f"# launcher: progress observed "
                          f"({last_progress!r} -> {progress!r}); restart "
                          "budget refilled",
                          file=sys.stderr, flush=True)
                    window_used = 0
                last_progress = progress
            if window_used >= max_restarts:
                if progress_fn is not None and max_restarts > 0:
                    print(f"# launcher: no progress across {window_used} "
                          f"consecutive restarts (budget={max_restarts}) — "
                          "crash loop, giving up",
                          file=sys.stderr, flush=True)
                flightlib.get().record("giving_up", rc=rc,
                                       restarts=window_used)
                return rc
            window_used += 1
            delay = _backoff_delay(window_used, backoff_s, backoff_cap_s)
            if tele is not None:
                tele.instant("launcher:restart", attempt=total,
                             restart=window_used, backoff_s=round(delay, 2))
            flightlib.get().record("restart", attempt=total,
                                   restart=window_used,
                                   backoff_s=round(delay, 2))
            print(f"# launcher: job failed (rc={rc}); restart "
                  f"{window_used}/{max_restarts} in {delay:.1f}s "
                  f"(resumes from the latest checkpoint)",
                  file=sys.stderr, flush=True)
            do_sleep(delay)
    finally:
        if prev_attempt is None:
            os.environ.pop(faults.ENV_ATTEMPT, None)
        else:
            os.environ[faults.ENV_ATTEMPT] = prev_attempt


def run_from_hostfile(path: str, process_id: int, command: Sequence[str], *,
                      port: int = 9531) -> int:
    """Run this host's single process of a hostfile-defined job."""
    specs = plan_from_hostfile(path, port=port)
    if not 0 <= process_id < len(specs):
        raise ValueError(
            f"process_id {process_id} out of range for {len(specs)} hosts")
    child = spawn(specs[process_id], command)
    return monitor([child])


# ---------------------------------------------------------------------------
# Serve mode: replica supervision with token-identical re-dispatch
# ---------------------------------------------------------------------------

def _spawn_replica(replica: int, num_replicas: int, workdir: str, *,
                   attempt: int, heartbeat_dir: Optional[str],
                   fault_plan: Optional[str],
                   trace_dir: Optional[str] = None) -> subprocess.Popen:
    """One serve replica process. Heartbeat/flight identity reuse the
    training child conventions (``DDL_PROCESS_ID`` names both files); no
    coordinator is exported — replicas are independent model copies, not
    ranks of one mesh. ``trace_dir`` arms per-request tracing in the
    child (``DDL_TRACE_DIR``) — set per spawn, never on the supervisor's
    own environ, so a traced serve run cannot leak tracing into later
    untraced children.

    One process per chip: replica ``i`` is given chip ``i`` of the host and
    no other, through libtpu's own visibility settings, before the child
    imports jax — without them every replica would open every chip and all
    but the first would fail. The supervisor itself never touches jax. On a
    CPU run (tests) the settings are inert."""
    env = dict(os.environ)
    env[ENV_PROCESS_ID] = str(replica)
    env[ENV_NUM_PROCESSES] = str(num_replicas)
    env.pop(ENV_COORDINATOR, None)
    env.update(replica_chip_env(replica))
    if trace_dir is not None:
        env[telemetry.ENV_TRACE_DIR] = trace_dir
    else:
        env.pop(telemetry.ENV_TRACE_DIR, None)
    # Serve replicas are outside the training membership: a stale elastic
    # epoch/identity inherited from a training launcher would namespace
    # their heartbeats away from the supervisor's staleness check.
    env.pop(health.ENV_ELASTIC_EPOCH, None)
    env.pop(health.ENV_ELASTIC_HOST, None)
    env.pop(health.ENV_ELASTIC_EVENT, None)
    env[faults.ENV_ATTEMPT] = str(attempt)
    if fault_plan:
        env[faults.ENV_PLAN] = fault_plan
    else:
        env.pop(faults.ENV_PLAN, None)
    if heartbeat_dir is not None:
        env[health.ENV_HEARTBEAT_DIR] = heartbeat_dir
        # A restarted replica must not inherit its predecessor's last
        # heartbeat: stale mtimes would mask a hang.
        try:
            os.remove(health.heartbeat_path(heartbeat_dir, replica))
        except OSError:
            pass
    command = [sys.executable, "-m",
               "distributeddeeplearning_tpu.serve.replica",
               "--workdir", workdir, "--replica", str(replica)]
    return subprocess.Popen(command, env=env)


def replica_chip_env(replica: int) -> dict[str, str]:
    """libtpu settings that show a process exactly one chip of its host:
    the chip by index, and a 1x1x1 process topology so the runtime does not
    wait for the host's other chips (nor take the whole-host lock)."""
    return {"TPU_VISIBLE_CHIPS": str(replica),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def _dispatch_request(workdir: str, replica: int, attempt: int,
                      payload: dict) -> None:
    """Atomically drop one request file into a replica's inbox. The inbox
    is per (replica, attempt): a warm-restarted replica must not replay
    its predecessor's inbox — those victims were re-dispatched already."""
    inbox = os.path.join(workdir, "inbox", f"r{replica}.a{attempt}")
    os.makedirs(inbox, exist_ok=True)
    name = f"req-{payload['uid']:06d}-{payload.get('dispatch', 0)}.json"
    tmp = os.path.join(inbox, name + ".tmp")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f)
    os.replace(tmp, os.path.join(inbox, name))


class AutoscalePolicy:
    """Deterministic hysteresis over the supervisor's queue-depth gauge.

    The elastic controller's substrate applied to serving (ROADMAP 1d):
    instead of mesh re-formation, membership change means spawning or
    draining independent replicas. The policy is pure — ``decide`` sees
    only the gauge values the supervisor just observed into
    ``observability/metrics.py`` and its own streak counters — so unit
    tests can drive it with synthetic traffic and pin every transition.

    Scale-up: the backlog has exceeded ``up_backlog_per_replica`` open
    requests per live replica for ``up_sustain_polls`` consecutive polls
    (a burst shorter than the sustain window is absorbed, not scaled
    for). Scale-down: the queue has been empty for ``down_idle_polls``
    consecutive polls. Both directions respect [min_replicas,
    max_replicas]; a decision resets both streaks so scale events are
    spaced by at least one full sustain window.
    """

    def __init__(self, min_replicas: int, max_replicas: int, *,
                 up_backlog_per_replica: float = 2.0,
                 up_sustain_polls: int = 3,
                 down_idle_polls: int = 40):
        if min_replicas < 1:
            raise ValueError(f"min_replicas={min_replicas}: need >= 1")
        if max_replicas < min_replicas:
            raise ValueError(f"max_replicas={max_replicas} < "
                             f"min_replicas={min_replicas}")
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.up_backlog_per_replica = float(up_backlog_per_replica)
        self.up_sustain_polls = int(up_sustain_polls)
        self.down_idle_polls = int(down_idle_polls)
        self._up_streak = 0
        self._idle_streak = 0

    def decide(self, *, queue_depth: int, live_replicas: int) -> int:
        """+1 (scale up), -1 (scale down), or 0 — given the current open
        (dispatched or due, unclosed) request count and live replicas."""
        if queue_depth > self.up_backlog_per_replica * max(1, live_replicas):
            self._up_streak += 1
        else:
            self._up_streak = 0
        if queue_depth == 0:
            self._idle_streak += 1
        else:
            self._idle_streak = 0
        if (self._up_streak >= self.up_sustain_polls
                and live_replicas < self.max_replicas):
            self._up_streak = self._idle_streak = 0
            return 1
        if (self._idle_streak >= self.down_idle_polls
                and live_replicas > self.min_replicas):
            self._up_streak = self._idle_streak = 0
            return -1
        return 0


def run_serve(num_replicas: int, requests: Sequence[dict],
              serve_config: dict, *, workdir: str,
              heartbeat_dir: Optional[str] = None,
              heartbeat_timeout_s: float = 0.0,
              max_restarts: int = 1, max_request_retries: int = 3,
              child_fault_plans: Optional[dict] = None,
              flight_dir: Optional[str] = None,
              poll_interval_s: float = 0.05,
              timeout_s: float = 600.0,
              autoscale: Optional[AutoscalePolicy] = None,
              trace_dir: Optional[str] = None,
              clock: Callable[[], float] = time.monotonic) -> dict:
    """Supervise N serve-engine replicas over one request trace.

    The serving analogue of ``run_local`` + ``run_with_restarts``, with one
    structural difference: a training job fails whole (every rank computes
    the same update), but replicas are independent — one dying must NOT
    tear the others down. Instead its in-flight requests are re-dispatched
    to survivors with the token prefix the supervisor already received
    folded into the prompt, so the completed stream is token-identical to
    an uninterrupted run (greedy prefix-folding, the same path preemption
    resume uses). The dead replica is restarted warm (shared AOT
    executable cache via ``config.json``) under a per-replica restart
    budget, with ``DDL_RESTART_ATTEMPT`` bumped so attempt-scoped faults
    do not re-fire.

    ``requests``: dicts with ``prompt``/``max_new_tokens`` (+ optional
    ``tenant``/``arrival_s`` relative to the run start). Returns per-uid
    results plus the incident/restart accounting; the flight record gets
    the full chain (``serve_replica_lost`` -> ``serve_redispatch`` ->
    ``serve_replayed``) for ``tools/postmortem.py``.

    With ``autoscale`` (an :class:`AutoscalePolicy`), the supervisor
    observes its open-request backlog and shed count into
    ``observability/metrics.py`` gauges every poll and lets the policy
    drive the replica count: scale-up spawns a fresh replica that warms
    from the SHARED serve AOT executable cache (every replica reads the
    same ``config.json``, so the fingerprint matches and the new replica
    skips compilation); scale-down routes through the stop-sentinel drain
    gate, so a scaled-down replica still runs the shutdown leak check.

    With ``trace_dir``, every replica records per-request span trees
    (``serve/tracing.py``) into ``trace.p<rid>.json`` there, the
    supervisor records its dispatch/redispatch/replica-lost instants into
    its own per-process file, and after the drain everything is merged
    into ``trace_dir/trace.merged.json`` (``out["merged_trace"]``) — one
    Chrome trace where a re-dispatched request's spans are flow-linked
    across the replica processes it lived on.
    """
    if num_replicas < 1:
        raise ValueError(f"num_replicas={num_replicas}: need >= 1")
    if autoscale is not None:
        # Start inside the policy's band: the floor is the availability
        # promise, the ceiling the cost cap.
        num_replicas = min(max(num_replicas, autoscale.min_replicas),
                           autoscale.max_replicas)
    os.makedirs(workdir, exist_ok=True)
    if heartbeat_dir is not None:
        os.makedirs(heartbeat_dir, exist_ok=True)
    with open(os.path.join(workdir, "config.json"), "w",
              encoding="utf-8") as f:
        json.dump(dict(serve_config), f, indent=2, sort_keys=True)

    if flight_dir is not None:
        os.environ[flightlib.ENV_FLIGHT_DIR] = flight_dir
        os.environ.setdefault(flightlib.ENV_RUN_ID, flightlib.mint_run_id())
        flightlib.configure(flight_dir,
                            run_id=os.environ[flightlib.ENV_RUN_ID],
                            host="launcher")
    flight = flightlib.get()
    flight.record("serve_launch", num_replicas=num_replicas,
                  requests=len(requests), max_restarts=max_restarts)

    # Supervisor-side tracing: its OWN registry (never the module
    # singleton — a bench tracing an in-process engine in this same
    # process must not be clobbered), on a pid far above any replica id.
    sup_tele = None
    if trace_dir is not None:
        os.makedirs(trace_dir, exist_ok=True)
        sup_tele = telemetry.Telemetry(
            enabled=True, trace_dir=trace_dir, process_index=10_000,
            process_name="serve-supervisor")

    plans = dict(child_fault_plans or {})
    for plan in plans.values():
        faults.parse_plan(plan)  # fail fast on grammar errors

    reqs: dict[int, dict] = {}
    for i, d in enumerate(requests):
        uid = int(d.get("uid", i))
        reqs[uid] = {
            "tenant": d.get("tenant", "default"),
            "prompt": [int(t) for t in d["prompt"]],
            "max_new": int(d["max_new_tokens"]),
            "arrival_s": float(d.get("arrival_s", 0.0)),
            "tokens": [], "replica": None, "dispatched": False,
            "finished": False, "failed": None, "retries": 0,
            "dispatches": 0, "first_token_t": None,
        }

    reps: list[dict] = []
    for i in range(num_replicas):
        proc = _spawn_replica(i, num_replicas, workdir, attempt=0,
                              heartbeat_dir=heartbeat_dir,
                              fault_plan=plans.get(i),
                              trace_dir=trace_dir)
        reps.append({"proc": proc, "alive": True, "attempt": 0,
                     "restarts": 0, "ever_beat": False, "hung": False,
                     "last_step": 0, "offset": 0, "rc": None,
                     "drained": False, "draining": False,
                     "boots": []})
        flight.record("spawn", child=i, pid=proc.pid, scope="serve")

    redispatched = 0
    total_restarts = 0
    scale_ups = 0
    scale_downs = 0
    gauges = metricslib.MetricsRegistry(
        run_id=os.environ.get(flightlib.ENV_RUN_ID, "")) \
        if autoscale is not None else None
    poll_n = 0
    stopping = False
    t0 = clock()

    def closed(st: dict) -> bool:
        return st["finished"] or st["failed"] is not None

    def drain_events(rid: int) -> None:
        rep = reps[rid]
        path = os.path.join(workdir, "events", f"r{rid}.jsonl")
        try:
            with open(path, "rb") as f:
                f.seek(rep["offset"])
                blob = f.read()
        except OSError:
            return
        cut = blob.rfind(b"\n")
        if cut < 0:
            return
        rep["offset"] += cut + 1
        for line in blob[:cut + 1].splitlines():
            try:
                e = json.loads(line)
            except ValueError:
                continue
            kind = e.get("ev")
            if "step" in e:
                rep["last_step"] = max(rep["last_step"], int(e["step"]))
            if kind == "token":
                st = reqs.get(int(e["uid"]))
                if st is not None and st["replica"] == rid \
                        and not closed(st):
                    if st["first_token_t"] is None:
                        st["first_token_t"] = clock()
                    st["tokens"].extend(int(t) for t in e["tokens"])
            elif kind == "finished":
                st = reqs.get(int(e["uid"]))
                if st is not None and st["replica"] == rid:
                    st["finished"] = True
                    if st["retries"]:
                        flight.record("serve_replayed",
                                      request=int(e["uid"]), replica=rid,
                                      tokens=len(st["tokens"]),
                                      retries=st["retries"],
                                      token_identical=True)
            elif kind == "failed":
                st = reqs.get(int(e["uid"]))
                if st is not None and st["replica"] == rid:
                    st["failed"] = e.get("reason", "unknown")
            elif kind == "drained":
                rep["drained"] = True
            elif kind == "ready":
                # One per boot: which device the replica holds and whether
                # it warm-booted from the AOT cache (restarts append).
                rep["boots"].append({k: e.get(k) for k in
                                     ("attempt", "device", "aot")})

    def on_replica_death(rid: int, rc: int) -> None:
        nonlocal redispatched, total_restarts
        rep = reps[rid]
        rep["alive"], rep["rc"] = False, rc
        drain_events(rid)  # salvage everything the OS buffered
        if rc == 0 and rep["drained"]:
            return  # clean drain after the stop sentinel
        label = attribute_failure(heartbeat_dir, rid, hung=rep["hung"],
                                  ever_beat=rep["ever_beat"])
        victims = [uid for uid, st in reqs.items()
                   if st["replica"] == rid and st["dispatched"]
                   and not closed(st)]
        flight.record("child_exit", child=rid, rc=rc, attribution=label,
                      scope="serve")
        flight.record("serve_replica_lost", replica=rid, rc=rc,
                      step=rep["last_step"], attribution=label,
                      inflight=len(victims))
        if sup_tele is not None:
            sup_tele.instant("serve:replica_lost", replica=rid, rc=rc,
                             step=rep["last_step"], attribution=label,
                             inflight=len(victims))
        print(f"# launcher: serve replica {rid} lost at engine step "
              f"{rep['last_step']} (rc={rc}, {label}); "
              f"{len(victims)} in-flight request(s) to re-dispatch",
              file=sys.stderr, flush=True)
        for uid in victims:
            st = reqs[uid]
            st["replica"], st["dispatched"] = None, False
            if len(st["tokens"]) >= st["max_new"]:
                # Fully streamed; only the 'finished' line was lost.
                st["finished"] = True
                continue
            st["retries"] += 1
            if st["retries"] > max_request_retries:
                st["failed"] = "retries_exhausted"
                flight.record("serve_shed", request=uid,
                              reason="retries_exhausted", scope="serve")
            else:
                redispatched += 1
        if rep["restarts"] < max_restarts and not stopping:
            rep["restarts"] += 1
            rep["attempt"] += 1
            total_restarts += 1
            flight.record("restart", child=rid, attempt=rep["attempt"],
                          scope="serve")
            rep["proc"] = _spawn_replica(
                rid, num_replicas, workdir, attempt=rep["attempt"],
                heartbeat_dir=heartbeat_dir, fault_plan=plans.get(rid),
                trace_dir=trace_dir)
            rep["alive"], rep["hung"], rep["rc"] = True, False, None

    try:
        while True:
            now = clock()
            alive = [i for i, r in enumerate(reps)
                     if r["alive"] and not r["draining"]]
            # Dispatch due requests round-robin over live replicas; a
            # re-dispatched victim carries its received prefix.
            if alive:
                for uid in sorted(reqs):
                    st = reqs[uid]
                    if (st["dispatched"] or closed(st)
                            or now - t0 < st["arrival_s"]):
                        continue
                    rid = alive[st["dispatches"] % len(alive)]
                    rep = reps[rid]
                    payload = {"uid": uid, "tenant": st["tenant"],
                               "prompt": st["prompt"],
                               "max_new_tokens": st["max_new"],
                               "prefix": list(st["tokens"]),
                               "dispatch": st["dispatches"],
                               # Trace/flow id: the supervisor's GLOBAL
                               # uid, stable across re-dispatches, so
                               # every replica's spans for this request
                               # share one flow.
                               "trace": uid,
                               "redispatch": bool(st["retries"])}
                    _dispatch_request(workdir, rid, rep["attempt"], payload)
                    st["replica"], st["dispatched"] = rid, True
                    st["dispatches"] += 1
                    if st["retries"]:
                        flight.record("serve_redispatch", request=uid,
                                      to=rid, resumed_from=len(st["tokens"]),
                                      retries=st["retries"])
                        if sup_tele is not None:
                            sup_tele.instant("serve:redispatch",
                                             request=uid, to=rid,
                                             trace=uid,
                                             resumed_from=len(st["tokens"]),
                                             retries=st["retries"])
                    elif sup_tele is not None:
                        sup_tele.instant("serve:dispatch", request=uid,
                                         to=rid, trace=uid,
                                         dispatch=st["dispatches"] - 1)
            # Autoscaling: observe the gauges, then let the policy move
            # the replica count (elastic membership for independent
            # replicas — ROADMAP 1d).
            if autoscale is not None and not stopping:
                poll_n += 1
                backlog = sum(1 for st in reqs.values()
                              if not closed(st)
                              and now - t0 >= st["arrival_s"])
                shed = sum(1 for st in reqs.values()
                           if st["failed"] == "retries_exhausted")
                gauges.observe("serve_queue_depth", backlog, step=poll_n)
                gauges.observe("serve_shed_total", shed, step=poll_n)
                gauges.observe("serve_live_replicas", len(alive),
                               step=poll_n)
                move = autoscale.decide(queue_depth=backlog,
                                        live_replicas=len(alive)) \
                    if alive else 0
                if move > 0:
                    rid = len(reps)
                    proc = _spawn_replica(
                        rid, rid + 1, workdir, attempt=0,
                        heartbeat_dir=heartbeat_dir,
                        fault_plan=plans.get(rid),
                        trace_dir=trace_dir)
                    reps.append({"proc": proc, "alive": True,
                                 "attempt": 0, "restarts": 0,
                                 "ever_beat": False, "hung": False,
                                 "last_step": 0, "offset": 0, "rc": None,
                                 "drained": False, "draining": False,
                     "boots": []})
                    scale_ups += 1
                    flight.record("spawn", child=rid, pid=proc.pid,
                                  scope="serve")
                    flight.record("serve_scale_up", replica=rid,
                                  queue_depth=backlog,
                                  live=len(alive) + 1, warm=True)
                    print(f"# launcher: serve autoscale up — replica "
                          f"{rid} spawned warm (queue depth {backlog} "
                          f"over {len(alive)} live)",
                          file=sys.stderr, flush=True)
                elif move < 0:
                    # Drain the newest idle replica (no open requests
                    # assigned) through the stop-sentinel gate.
                    idle = [i for i in reversed(alive)
                            if not any(st["replica"] == i
                                       and st["dispatched"]
                                       and not closed(st)
                                       for st in reqs.values())]
                    if idle:
                        rid = idle[0]
                        reps[rid]["draining"] = True
                        with open(os.path.join(workdir, f"stop.r{rid}"),
                                  "w", encoding="utf-8") as f:
                            f.write("drain\n")
                        scale_downs += 1
                        flight.record("serve_scale_down", replica=rid,
                                      live=len(alive) - 1)
                        print(f"# launcher: serve autoscale down — "
                              f"replica {rid} draining (idle "
                              f"{autoscale.down_idle_polls} polls)",
                              file=sys.stderr, flush=True)
            for rid in range(len(reps)):
                if reps[rid]["alive"]:
                    drain_events(rid)
            if heartbeat_dir is not None:
                for rid in range(len(reps)):
                    rep = reps[rid]
                    if rep["alive"] and not rep["ever_beat"]:
                        rep["ever_beat"] = os.path.exists(
                            health.heartbeat_path(heartbeat_dir, rid))
                if heartbeat_timeout_s > 0:
                    beat_set = {i for i, r in enumerate(reps)
                                if r["alive"] and r["ever_beat"]}
                    for pid, age in health.check_stale(
                            heartbeat_dir, len(reps),
                            heartbeat_timeout_s):
                        if pid in beat_set and not reps[pid]["hung"]:
                            reps[pid]["hung"] = True
                            flight.record("heartbeat_stale", child=pid,
                                          age_s=round(age, 3), scope="serve")
                            reps[pid]["proc"].kill()
            for rid in range(len(reps)):
                rep = reps[rid]
                if rep["alive"]:
                    rc = rep["proc"].poll()
                    if rc is not None:
                        on_replica_death(rid, rc)
            if all(closed(st) for st in reqs.values()):
                if not stopping:
                    stopping = True
                    for rid in range(len(reps)):
                        with open(os.path.join(workdir, f"stop.r{rid}"),
                                  "w", encoding="utf-8") as f:
                            f.write("drain\n")
                if not any(r["alive"] for r in reps):
                    break
            elif not any(r["alive"] for r in reps):
                # Every replica is gone and its restart budget with it:
                # nothing can serve the open requests, so fail now instead
                # of polling an empty fleet until the timeout.
                raise RuntimeError(
                    f"serve supervision: no replica left alive (exit codes "
                    f"{[r['rc'] for r in reps]}, restart budget "
                    f"{max_restarts} spent) with "
                    f"{sum(1 for s in reqs.values() if not closed(s))} "
                    f"request(s) open")
            if now - t0 > timeout_s:
                raise RuntimeError(
                    f"serve supervision timed out after {timeout_s:.0f}s: "
                    f"{sum(1 for s in reqs.values() if not closed(s))} "
                    f"request(s) open, replicas alive="
                    f"{[i for i, r in enumerate(reps) if r['alive']]}")
            time.sleep(poll_interval_s)
    finally:
        for rep in reps:
            if rep["alive"]:
                rep["proc"].kill()
                rep["proc"].wait()

    # The drain gate: a replica that reaches its stop sentinel runs the
    # engine's shutdown leak check and exits 0 only if page accounting
    # balanced — so "every replica drained AND exited 0" IS the leak
    # check. A replica that died in shutdown (leak found) has rc != 0 and
    # no drained event; both must fail this.
    leak_check_ok = bool(reps) and all(
        r["rc"] == 0 and r["drained"] for r in reps)
    window_s = clock() - t0
    flight.record("serve_drained", window_s=round(window_s, 3),
                  redispatched=redispatched, restarts=total_restarts,
                  leak_check_ok=leak_check_ok, scale_ups=scale_ups,
                  scale_downs=scale_downs)
    results = {}
    for uid, st in reqs.items():
        ttft = None
        if st["first_token_t"] is not None:
            ttft = max(0.0, st["first_token_t"] - (t0 + st["arrival_s"]))
        results[uid] = {"tokens": list(st["tokens"]),
                        "finished": st["finished"],
                        "failed": st["failed"],
                        "retries": st["retries"], "ttft_s": ttft}
    out = {"results": results, "redispatched": redispatched,
           "restarts": total_restarts, "window_s": window_s,
           "leak_check_ok": leak_check_ok,
           "replica_rcs": {i: r["rc"] for i, r in enumerate(reps)},
           "replica_boots": {i: r["boots"] for i, r in enumerate(reps)}}
    if trace_dir is not None:
        if sup_tele is not None:
            sup_tele.export()
        merged, merge_errors = telemetry.merge_trace_dir(trace_dir)
        out["trace_dir"] = trace_dir
        out["merged_trace"] = merged
        if merge_errors:
            # Typically the SIGKILL'd replica's last file — report what
            # was salvaged rather than pretending the merge was whole.
            out["trace_merge_errors"] = merge_errors
    if autoscale is not None:
        out["autoscale"] = {"scale_ups": scale_ups,
                            "scale_downs": scale_downs,
                            "peak_replicas": len(reps),
                            "min_replicas": autoscale.min_replicas,
                            "max_replicas": autoscale.max_replicas,
                            "gauges": gauges.aggregate()["metrics"]}
    return out


def _main_serve(args, p) -> int:
    """CLI shim for serve mode: files in, run_serve, summary out."""
    import tempfile

    with open(args.serve, encoding="utf-8") as f:
        requests = json.load(f)
    if not isinstance(requests, list) or not requests:
        p.error(f"--serve {args.serve}: expected a non-empty JSON list")
    with open(args.serve_config, encoding="utf-8") as f:
        serve_config = json.load(f)

    plans: dict[int, str] = {}
    for item in args.child_fault_plan:
        idx_s, sep, plan = item.partition(":")
        if not sep or not idx_s.isdigit():
            p.error(f"--child-fault-plan expects IDX:PLAN, got {item!r}")
        plans[int(idx_s)] = plan

    workdir = args.serve_dir or tempfile.mkdtemp(prefix="ddl-serve-")
    # Heartbeats are always on in serve mode: attribution (hung vs crash
    # vs host_lost) needs ever_beat even when the staleness watchdog is
    # disabled.
    heartbeat_dir = args.heartbeat_dir or tempfile.mkdtemp(
        prefix="ddl-serve-hb-")

    autoscale = None
    if args.serve_autoscale:
        lo_s, sep, hi_s = args.serve_autoscale.partition(":")
        if not sep or not lo_s.isdigit() or not hi_s.isdigit():
            p.error(f"--serve-autoscale expects MIN:MAX, got "
                    f"{args.serve_autoscale!r}")
        try:
            autoscale = AutoscalePolicy(int(lo_s), int(hi_s))
        except ValueError as e:
            p.error(f"--serve-autoscale: {e}")

    out = run_serve(args.num_processes or 1, requests, serve_config,
                    workdir=workdir, heartbeat_dir=heartbeat_dir,
                    heartbeat_timeout_s=args.heartbeat_timeout,
                    max_restarts=args.max_restarts,
                    child_fault_plans=plans, flight_dir=args.flight_dir,
                    autoscale=autoscale,
                    trace_dir=args.serve_trace_dir)
    if args.serve_out:
        with open(args.serve_out, "w", encoding="utf-8") as f:
            json.dump(out, f, indent=2, sort_keys=True, default=str)
    done = sum(1 for r in out["results"].values() if r["finished"])
    print(f"# launcher: serve drained — {done}/{len(out['results'])} "
          f"finished, {out['redispatched']} re-dispatched, "
          f"{out['restarts']} restart(s), leak check "
          f"{'ok' if out['leak_check_ok'] else 'FAILED'} "
          f"({out['window_s']:.1f}s)", flush=True)
    ok = out["leak_check_ok"] and all(
        r["finished"] for r in out["results"].values())
    return 0 if ok else 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--num-processes", type=int, default=None,
                   help="spawn N local processes (multi-host simulation / "
                        "single-host multi-process)")
    p.add_argument("--hostfile", default=None,
                   help="one host per line; first is coordinator")
    p.add_argument("--process-id", type=int, default=None,
                   help="this host's line number in --hostfile")
    p.add_argument("--port", type=int, default=9531,
                   help="coordinator port")
    p.add_argument("--max-restarts", type=int, default=0,
                   help="relaunch the whole job up to N times after a "
                        "failure (resumes from the latest checkpoint); when "
                        "the command names a --checkpoint-dir, N is a budget "
                        "*between checkpoints* — progress refills it, a "
                        "crash loop exhausts it")
    p.add_argument("--backoff", type=float, default=3.0,
                   help="base restart delay in seconds (doubles per "
                        "consecutive failure, deterministic jitter)")
    p.add_argument("--backoff-cap", type=float, default=60.0,
                   help="maximum restart delay in seconds")
    p.add_argument("--child-fault-plan", action="append", default=[],
                   metavar="IDX:PLAN",
                   help="inject a fault plan (robustness/faults.py grammar) "
                        "into one local child, e.g. 0:sigkill@20 "
                        "(repeatable; local --num-processes jobs only)")
    p.add_argument("--heartbeat-timeout", type=float, default=0.0,
                   help="kill a child whose heartbeat file "
                        "(observability/health.py; children beat on their "
                        "log cadence) goes stale for this many seconds — a "
                        "hung child then feeds the normal fail-whole + "
                        "restart machinery. 0 disables. Size it well above "
                        "the training log interval")
    p.add_argument("--heartbeat-dir", default=None,
                   help="heartbeat file directory (default: a fresh temp "
                        "dir; local --num-processes jobs only)")
    p.add_argument("--elastic", action="store_true",
                   help="automatic mesh re-formation on host loss/gain: a "
                        "child attributed as a lost host (its heartbeat "
                        "vanished with it, or the hang watchdog killed it) "
                        "shrinks the plan and the job relaunches at the "
                        "surviving --dp degree from the latest checkpoint, "
                        "without sleeping the backoff or burning the "
                        "restart budget; a rejoin marker in the heartbeat "
                        "dir grows it back. Requires a local "
                        "--num-processes job whose command names --dp and "
                        "--checkpoint-dir; the global batch is unchanged, "
                        "so trajectories stay bitwise "
                        "(docs/fault_tolerance.md)")
    p.add_argument("--min-hosts", type=int, default=1,
                   help="with --elastic, give up (generic failure path) "
                        "instead of re-forming below this many hosts")
    p.add_argument("--elastic-geometry", action="append", default=[],
                   metavar="HOSTS:dp=D[,pp=P][,sharding=S]",
                   help="with --elastic, the full mesh shape to re-form at "
                        "when HOSTS hosts are live (repeatable), e.g. "
                        "1:dp=1,pp=4,sharding=none — re-formation then "
                        "crosses the pipeline-degree and ZeRO-stage axes "
                        "through the canonical checkpoint layout. dp*pp "
                        "must equal HOSTS x devices-per-host. Host counts "
                        "not listed (other than the full pod) shrink to "
                        "the largest listed count via topology-aware "
                        "survivor selection (docs/fault_tolerance.md)")
    p.add_argument("--serve-autoscale", default=None, metavar="MIN:MAX",
                   help="with --serve, autoscale the replica count between "
                        "MIN and MAX from the supervisor's queue-depth "
                        "gauge: sustained backlog per live replica scales "
                        "up (warm via the shared serve AOT fingerprint), "
                        "sustained idleness scales down (docs/serving.md)")
    p.add_argument("--flight-dir", default=None,
                   help="flight recorder directory (observability/"
                        "flight.py): the launcher mints one run id for the "
                        "whole job, exports it to every child of every "
                        "restart attempt, and appends its own spawn/"
                        "attribution/restart events — the crash-surviving "
                        "record tools/postmortem.py reads. Default: the "
                        "training command's own --flight-dir, else off")
    p.add_argument("--serve", default=None, metavar="REQUESTS.json",
                   help="serve mode: supervise --num-processes engine "
                        "replicas over this request trace (list of "
                        "{prompt, max_new_tokens[, tenant, arrival_s]}) "
                        "instead of launching a training command. Replicas "
                        "lost mid-decode have their in-flight requests "
                        "re-dispatched to survivors token-identically; "
                        "--max-restarts / --heartbeat-timeout / "
                        "--child-fault-plan / --flight-dir apply per "
                        "replica (docs/serving.md)")
    p.add_argument("--serve-config", default=None, metavar="CONFIG.json",
                   help="ServeConfig fields for serve mode (required with "
                        "--serve)")
    p.add_argument("--serve-dir", default=None,
                   help="serve-mode work directory for the inbox/event "
                        "files (default: a fresh temp dir)")
    p.add_argument("--serve-out", default=None,
                   help="write the serve-mode result summary (per-request "
                        "tokens, re-dispatch/restart accounting, leak "
                        "check) to this JSON file")
    p.add_argument("--serve-trace-dir", default=None,
                   help="with --serve, record per-request span trees in "
                        "every replica (serve/tracing.py) and merge the "
                        "per-replica files into "
                        "TRACE_DIR/trace.merged.json after the drain — "
                        "one Chrome trace, flow-linked across replicas")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="training command, after `--`")
    args = p.parse_args(argv)

    command = list(args.command)
    if command and command[0] == "--":
        command = command[1:]
    if args.serve is not None:
        if command:
            p.error("--serve replaces the training command; drop the "
                    "trailing command")
        if args.hostfile or args.elastic:
            p.error("--serve only supports local (--num-processes) jobs")
        if args.serve_config is None:
            p.error("--serve requires --serve-config")
        return _main_serve(args, p)
    if args.serve_autoscale:
        p.error("--serve-autoscale requires --serve")
    if args.serve_trace_dir:
        p.error("--serve-trace-dir requires --serve")
    if not command:
        p.error("no training command given (pass it after `--`)")

    # One compile cache for the whole job needs no plumbing here: every
    # child of every restart attempt inherits $JAX_COMPILATION_CACHE_DIR or
    # computes the same repo default (perf/compile_cache.py), so a restarted
    # attempt loads the previous attempt's executables.

    if args.hostfile:
        if args.process_id is None:
            p.error("--hostfile requires --process-id")
        if args.child_fault_plan:
            p.error("--child-fault-plan only supports local "
                    "(--num-processes) jobs")
        if args.heartbeat_timeout:
            # The watchdog kills by local child index; a hostfile job's one
            # local child maps to a remote rank set this launcher cannot
            # attribute — keep the semantics local-only, like restarts.
            p.error("--heartbeat-timeout only supports local "
                    "(--num-processes) jobs")
        if args.max_restarts:
            # A per-host restart decision is wrong for a whole-job semantic:
            # hosts whose rank exited 0 would never relaunch, leaving the
            # restarted ranks hung in rendezvous. Multi-host restart needs a
            # whole-job resubmit (every host's launcher rerun), like the
            # reference's Batch-AI resubmission.
            p.error("--max-restarts only supports local (--num-processes) "
                    "jobs; for --hostfile, wrap the launcher in a "
                    "whole-job resubmit loop on every host")
        if args.elastic:
            # Elastic re-formation re-plans the LOCAL process set; a
            # hostfile job's membership lives across machines where this
            # launcher only owns one child.
            p.error("--elastic only supports local (--num-processes) jobs")
        return run_from_hostfile(args.hostfile, args.process_id, command,
                                 port=args.port)
    n = args.num_processes or 1

    child_env: dict[int, dict[str, str]] = {}
    for item in args.child_fault_plan:
        idx_s, sep, plan = item.partition(":")
        if not sep or not idx_s.isdigit():
            p.error(f"--child-fault-plan expects IDX:PLAN, got {item!r}")
        faults.parse_plan(plan)  # fail fast on grammar errors
        child_env.setdefault(int(idx_s), {})[faults.ENV_PLAN] = plan

    progress_fn = None
    ckpt_dir = _checkpoint_dir_from_command(command)
    if ckpt_dir is not None:
        progress_fn = lambda: _latest_ckpt_step(ckpt_dir)  # noqa: E731

    heartbeat_dir = None
    if args.heartbeat_timeout > 0 or args.elastic:
        import tempfile
        heartbeat_dir = args.heartbeat_dir or tempfile.mkdtemp(
            prefix="ddl_heartbeat_")
        os.makedirs(heartbeat_dir, exist_ok=True)

    # Flight recorder (observability/flight.py): ONE run id for the whole
    # job, minted here and exported so every child of every restart attempt
    # appends to the same run's record under the shared identity scheme.
    # The launcher writes its own file (child exits, attribution verdicts,
    # restarts, re-formations) — the events that survive even when a child
    # died too fast to record anything.
    flight_dir = (args.flight_dir if args.flight_dir is not None
                  else _flag_from_command(command, "--flight-dir"))
    if flight_dir is not None:
        os.environ[flightlib.ENV_FLIGHT_DIR] = flight_dir
        os.environ.setdefault(flightlib.ENV_RUN_ID, flightlib.mint_run_id())
        flight = flightlib.configure(
            flight_dir, run_id=os.environ[flightlib.ENV_RUN_ID],
            host="launcher")
        flight.record("launch", num_processes=n,
                      max_restarts=args.max_restarts,
                      elastic=bool(args.elastic),
                      command=" ".join(command))
    else:
        flight = flightlib.get()

    # When the training command traces (--trace-dir), the launcher records
    # its restart/backoff/stale-heartbeat instants too and merges them into
    # process 0's trace AFTER the job ends — one Chrome-trace file then
    # shows the whole chaos story (step phases + faults + restarts).
    # Timestamps are CLOCK_MONOTONIC, shared across local processes.
    trace_dir = _flag_from_command(command, "--trace-dir")
    tele = None
    if trace_dir is not None:
        tele = telemetry.Telemetry(enabled=True, process_index=os.getpid(),
                                   process_name="launcher")

    elastic_ctl = None
    if args.elastic:
        dp_s = _flag_from_command(command, "--dp")
        if dp_s is None or not dp_s.isdigit():
            p.error("--elastic requires the training command to name an "
                    "explicit integer --dp (the degree the controller "
                    "re-plans)")
        if ckpt_dir is None:
            p.error("--elastic requires the training command to name "
                    "--checkpoint-dir (re-formation resumes from the "
                    "latest checkpoint)")
        fsdp_s = _flag_from_command(command, "--fsdp")
        if fsdp_s not in (None, "1"):
            # Shrinking fsdp re-shards parameters mid-plan; the converter
            # handles the CHECKPOINT side bitwise, but the per-host device
            # arithmetic here only re-plans the data axis.
            p.error("--elastic re-plans the --dp axis only; run with "
                    "--fsdp 1 (or drop --fsdp)")
        base_dp = int(dp_s)
        if base_dp % n:
            p.error(f"--elastic: --dp {base_dp} must divide evenly over "
                    f"--num-processes {n}")
        geometry = _parse_elastic_geometry(
            args.elastic_geometry, p, num_hosts=n, base_dp=base_dp,
            base_pp=_flag_from_command(command, "--pp"))
        # A stale rejoin/drain marker or reform barrier from a previous job
        # must not trigger a phantom re-formation on the first failure of
        # this one.
        health.consume_rejoin(heartbeat_dir)
        health.consume_drains(heartbeat_dir)
        health.clear_reform(heartbeat_dir)
        elastic_ctl = ElasticController(n, heartbeat_dir, base_dp=base_dp,
                                        min_hosts=args.min_hosts, tele=tele,
                                        geometry=geometry)
    elif args.elastic_geometry:
        p.error("--elastic-geometry requires --elastic")

    if elastic_ctl is not None:
        run_once = lambda: run_local(  # noqa: E731
            elastic_ctl.num_processes, elastic_ctl.command(command),
            port=args.port, child_env=elastic_ctl.child_env(child_env),
            heartbeat_dir=heartbeat_dir,
            heartbeat_timeout_s=args.heartbeat_timeout,
            heartbeat_epoch=elastic_ctl.epoch,
            tele=tele, elastic=elastic_ctl)
    else:
        run_once = lambda: run_local(  # noqa: E731
            n, command, port=args.port, child_env=child_env,
            heartbeat_dir=heartbeat_dir,
            heartbeat_timeout_s=args.heartbeat_timeout, tele=tele)

    rc = run_with_restarts(
        run_once, args.max_restarts, backoff_s=args.backoff,
        backoff_cap_s=args.backoff_cap, progress_fn=progress_fn, tele=tele,
        elastic=elastic_ctl)
    if elastic_ctl is not None and elastic_ctl.events:
        for ev in elastic_ctl.events:
            print(f"# launcher: elastic event: {ev['trigger']} degree "
                  f"{ev['degree_before']} -> {ev['degree_after']}",
                  file=sys.stderr, flush=True)
        print(f"# launcher: elastic: {len(elastic_ctl.events)} "
              f"re-formation(s), final degree {elastic_ctl.degree} "
              f"({elastic_ctl.num_processes}/{elastic_ctl.max_hosts} hosts)",
              file=sys.stderr, flush=True)
    if tele is not None:
        tele.export(telemetry.trace_path(trace_dir, 0))
    flight.record("job_end", rc=rc)
    flight.close()
    return rc


def _parse_elastic_geometry(items: Sequence[str], p, *, num_hosts: int,
                            base_dp: int, base_pp: Optional[str]
                            ) -> dict[int, dict]:
    """Parse repeated ``--elastic-geometry HOSTS:dp=D[,pp=P][,sharding=S]``
    entries into the controller's geometry table, validating each shape
    against the pod's device budget (dp*pp == hosts x devices-per-host)."""
    pp = int(base_pp) if base_pp and base_pp.isdigit() else 1
    if (base_dp * pp) % num_hosts:
        p.error(f"--elastic-geometry: base mesh dp={base_dp} pp={pp} does "
                f"not fill {num_hosts} host(s) evenly")
    devices_per_host = (base_dp * pp) // num_hosts
    geometry: dict[int, dict] = {}
    for item in items:
        hosts_s, sep, spec = item.partition(":")
        if not sep or not hosts_s.isdigit() or int(hosts_s) < 1:
            p.error(f"--elastic-geometry expects HOSTS:dp=D[,pp=P]"
                    f"[,sharding=S], got {item!r}")
        hosts = int(hosts_s)
        if hosts > num_hosts:
            p.error(f"--elastic-geometry {item!r}: {hosts} hosts exceeds "
                    f"--num-processes {num_hosts}")
        entry: dict = {}
        for kv in spec.split(","):
            key, sep2, value = kv.partition("=")
            if key == "dp" and sep2 and value.isdigit():
                entry["dp"] = int(value)
            elif key == "pp" and sep2 and value.isdigit():
                entry["pp"] = int(value)
            elif key == "sharding" and sep2 and value in (
                    "none", "zero1", "zero2", "zero3"):
                entry["sharding"] = value
            else:
                p.error(f"--elastic-geometry: bad field {kv!r} in {item!r}")
        if "dp" not in entry:
            p.error(f"--elastic-geometry {item!r}: dp= is required")
        shape = entry["dp"] * entry.get("pp", 1)
        if shape != devices_per_host * hosts:
            p.error(f"--elastic-geometry {item!r}: dp x pp = {shape} does "
                    f"not fill {hosts} host(s) x {devices_per_host} "
                    f"device(s)")
        geometry[hosts] = entry
    return geometry


def _flag_from_command(command: Sequence[str], flag: str) -> Optional[str]:
    """The value of ``flag`` in the training command, if present."""
    for i, tok in enumerate(command):
        if tok == flag and i + 1 < len(command):
            return command[i + 1]
        if tok.startswith(flag + "="):
            return tok.split("=", 1)[1]
    return None


def _with_flag_value(command: Sequence[str], flag: str,
                     value: str) -> list[str]:
    """The command with ``flag`` set to ``value`` (rewritten in place for
    both ``--flag V`` and ``--flag=V`` spellings; appended if absent) —
    how the elastic controller re-plans ``--dp`` at the surviving degree."""
    out = list(command)
    for i, tok in enumerate(out):
        if tok == flag and i + 1 < len(out):
            out[i + 1] = value
            return out
        if tok.startswith(flag + "="):
            out[i] = f"{flag}={value}"
            return out
    out.extend([flag, value])
    return out


def _checkpoint_dir_from_command(command: Sequence[str]) -> Optional[str]:
    """The training command's --checkpoint-dir, if present — lets the
    restart budget observe progress (new checkpoint step => refill)."""
    return _flag_from_command(command, "--checkpoint-dir")


if __name__ == "__main__":
    sys.exit(main())
