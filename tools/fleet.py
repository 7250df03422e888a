"""Fleet supervisor: launch, watch, and survivor-elastic-relaunch a
multi-process fit.

The reference inherited this from YARN — a lost executor was re-requested
and Spark's lineage replayed its partitions. The TPU port's fleets are N
long-lived jax.distributed processes whose collectives WEDGE when a
member dies mid-program, so supervision is explicit:

1. **launch** — N worker processes join a gloo/grpc rendezvous
   (``parallel.multihost.initialize`` with bounded retry) and run the
   streamed entity-sharded fit with COORDINATED checkpoints
   (``game.checkpoint`` quorum manifests) at every chunk boundary;
2. **watch** — exit codes plus the heartbeat-file liveness protocol
   (``proc-<i>.alive`` touched on a cadence; staleness beyond a deadline
   = dead). A member exiting with the injection code 113 (or losing its
   heartbeat) marks its host LOST;
3. **stop the survivors** — SIGTERM requests the boundary stop
   (``GracefulStop`` + the ``fleet_any`` collective agreement make every
   member stop at the SAME boundary); members wedged in a collective
   against a dead partner cannot reach the boundary, so after a grace
   period the supervisor escalates to SIGKILL — their progress since the
   last certified checkpoint is lost, and that is fine, because chunks
   replay deterministically;
4. **relaunch on the survivors** — a new, smaller fleet restores the
   newest CERTIFIED checkpoint via ``restore_placed()`` (the entity axis
   re-sliced onto the shrunken mesh) and recomputes its per-host splits
   deterministically (``ingest.planner.plans_for_host`` /
   ``multihost.process_slice``) — the dead host's work lands on
   survivors with no coordination state.

An external SIGTERM to ONE member (preemption) propagates through the
same boundary agreement: every member writes the coordinated final
checkpoint and exits 75 — interrupted, not relaunched.

Fleet observability (ISSUE 13): workers are launched with
``PHOTON_PROC_ID``/``PHOTON_TRACE_OUT``/``PHOTON_TELEMETRY_OUT`` so each
member writes its OWN suffixed artifact stream, one directory per
generation (``<workdir>/telemetry/gen<g>/trace.proc-<i>.jsonl``, … —
relaunches renumber members, so generations must not share files) plus
progress heartbeats — the input of ``cli report --fleet``; ``--status-file`` /
``--status-port`` publish the live supervisor snapshot an operator polls
(member liveness from heartbeat mtimes, last heartbeat fields per
member, deaths/relaunches, generation —
``photon_ml_tpu.parallel.fleet_status``).

CLI::

    python -m tools.fleet --workdir /tmp/fleet                # supervise
    python -m tools.fleet --workdir /tmp/fleet \
        --status-file /tmp/fleet/status.json --status-port 0  # + live status
    python -m tools.fleet --worker --proc 0 --nproc 2 ...     # (internal)

tools/chaos.py drives this harness for the DISTRIBUTED crash matrix:
one member hard-killed at each fleet fault seam, the survivor-resumed
fit's final loss checked against the uninterrupted fleet reference.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Optional

#: worker fit shape — shared with tools.chaos so the fleet reference and
#: the single-process matrix solve the same problem
N_ENTITIES = 16
N_ROWS = 8
DIM = 4
N_CHUNKS = 4
DATA_SEED = 20260803

#: exit code of a graceful boundary stop (cli train's "interrupted,
#: restart me" convention)
GRACEFUL_EXIT_CODE = 75

#: a worker that NOTICED the fleet break (a collective failed against a
#: dead peer) exits with this code via ``os._exit`` — unwinding normally
#: would wedge in jax's atexit distributed-shutdown barrier against the
#: very peer that died. The supervisor reads it as "host fine, fleet
#: broken": the member relaunches in the next generation.
FLEET_ABORT_EXIT_CODE = 76


def make_problem():
    """The deterministic worker problem ``(X, y)``: every fleet member —
    and the chaos matrix's reference scorer — generates the SAME data
    from DATA_SEED, so there is exactly one definition to drift."""
    import numpy as np

    rng = np.random.default_rng(DATA_SEED)
    X = rng.normal(size=(N_ENTITIES, N_ROWS, DIM))
    W = rng.normal(size=(N_ENTITIES, DIM))
    z = np.einsum("erk,ek->er", X, W)
    y = (rng.random((N_ENTITIES, N_ROWS)) < 1 / (1 + np.exp(-z))).astype(
        np.float32
    )
    return X.astype(np.float32), y


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclasses.dataclass
class FleetSpec:
    """One supervised fleet run (including any survivor relaunches)."""

    workdir: str
    num_processes: int = 2
    devices_per_process: int = 2
    heartbeat_every_s: float = 0.25
    #: staleness beyond which a member with no exit code counts dead
    heartbeat_deadline_s: float = 5.0
    #: how long survivors get to reach their boundary stop after SIGTERM
    #: before the supervisor escalates to SIGKILL
    grace_s: float = 12.0
    #: coordinated-checkpoint quorum wait inside the workers (kept well
    #: under grace_s so an abandoned save resolves before escalation)
    quorum_timeout_s: float = 4.0
    max_relaunches: int = 2
    timeout_s: float = 600.0
    #: fault plan armed onto EXACTLY ONE member (the victim) of the
    #: first generation — the chaos harness's kill switch
    victim_plan: Optional[dict] = None
    victim_process: int = 1
    #: deliver SIGTERM to this member this many seconds after its FIRST
    #: heartbeat (external preemption of one host; None = never).
    #: Anchoring on the heartbeat — not launch — keeps the signal inside
    #: the fit whatever jax import/compile latency the box has
    sigterm_after_s: Optional[float] = None
    sigterm_process: int = 0
    #: test-only: stretch each chunk boundary so mid-fit signals land
    chunk_sleep_s: float = 0.0
    #: which member the chunk sleep applies to (-1 = all) — sleeping ONE
    #: member makes it arrive last at every fleet_any barrier, i.e. a
    #: deterministic straggler for the collective-wait attribution tests
    chunk_sleep_proc: int = -1
    #: how a lost host is recognized: "exit_code" marks a member lost the
    #: moment it exits with the injection code 113; "heartbeat" ignores
    #: that fast path and waits for the member's ``proc-<i>.alive`` file
    #: to go stale — the pure liveness-protocol detection (the matrix's
    #: ``fleet.heartbeat`` row runs this mode so staleness detection is
    #: itself crash-proven)
    detect_by: str = "exit_code"
    #: per-member telemetry artifact streams (fleet observability): when
    #: True, every worker gets PHOTON_PROC_ID/PHOTON_TRACE_OUT/
    #: PHOTON_TELEMETRY_OUT pointed into ``telemetry_dir`` (default
    #: <workdir>/telemetry), so the run leaves trace.proc-<i>.jsonl +
    #: telemetry.proc-<i>.jsonl behind — the input of
    #: ``cli report --fleet``
    telemetry: bool = True
    telemetry_dir: Optional[str] = None
    #: worker-side progress-heartbeat cadence (the telemetry JSONL lines
    #: the live status tail-parses; distinct from the liveness-file touch)
    progress_heartbeat_every_s: float = 1.0
    #: live supervisor status (photon_ml_tpu.parallel.fleet_status): a
    #: JSON snapshot written atomically to status_file and/or served on
    #: http://127.0.0.1:<status_port>/statusz every status_interval_s
    status_file: Optional[str] = None
    status_port: Optional[int] = None
    status_interval_s: float = 1.0

    def resolved_telemetry_dir(self) -> Optional[str]:
        if not self.telemetry:
            return None
        return self.telemetry_dir or os.path.join(self.workdir, "telemetry")

    def generation_telemetry_dir(self, generation: int) -> Optional[str]:
        """One artifact directory PER GENERATION (``telemetry/gen0``, …):
        a relaunched fleet renumbers its members, so an unqualified path
        would let the new proc 0 truncate the DEAD member's stream and
        FleetReport would read the killed member as complete. One
        directory = one generation's fleet is the aggregation contract
        (``cli report --fleet <dir>/gen<g>``)."""
        d = self.resolved_telemetry_dir()
        return None if d is None else os.path.join(d, f"gen{generation}")

    def telemetry_out_base(self, generation: int) -> Optional[str]:
        """The UNSUFFIXED telemetry JSONL path generation ``g``'s workers
        point PHOTON_TELEMETRY_OUT at (identity suffixes it per member);
        also what the status writer tail-parses."""
        d = self.generation_telemetry_dir(generation)
        return None if d is None else os.path.join(d, "telemetry.jsonl")


def _worker_env(
    spec: FleetSpec, proc: int, nproc: int, armed: bool, generation: int
) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(
        f"--xla_force_host_platform_device_count={spec.devices_per_process}"
    )
    env["XLA_FLAGS"] = " ".join(flags)
    env.pop("PHOTON_FAULT_PLAN", None)
    if armed and spec.victim_plan is not None:
        env["PHOTON_FAULT_PLAN"] = json.dumps(spec.victim_plan)
    # fleet identity + per-member artifact streams: identity BEFORE jax
    # imports (telemetry.identity reads PHOTON_PROC_ID), artifact env
    # suffixed per member by telemetry.configure_from_env in the worker
    env["PHOTON_PROC_ID"] = str(proc)
    env["PHOTON_PROC_COUNT"] = str(nproc)
    telemetry_dir = spec.generation_telemetry_dir(generation)
    if telemetry_dir is not None:
        env["PHOTON_TRACE_OUT"] = os.path.join(telemetry_dir, "trace.jsonl")
        env["PHOTON_TELEMETRY_OUT"] = spec.telemetry_out_base(generation)
    else:
        env.pop("PHOTON_TRACE_OUT", None)
        env.pop("PHOTON_TELEMETRY_OUT", None)
    return env


@dataclasses.dataclass
class _Member:
    proc: subprocess.Popen
    process_id: int
    out_path: str
    err_path: str
    rc: Optional[int] = None
    lost_host: bool = False  # exited 113 / heartbeat-stale-killed


def _launch_generation(
    spec: FleetSpec, generation: int, nproc: int, arm_victim: bool
) -> list[_Member]:
    fleet_dir = os.path.join(spec.workdir, "fleet")
    os.makedirs(fleet_dir, exist_ok=True)
    telemetry_dir = spec.generation_telemetry_dir(generation)
    if telemetry_dir is not None:
        os.makedirs(telemetry_dir, exist_ok=True)
    # stale liveness files from the previous generation must not mask a
    # new member's death (mtime staleness is the signal)
    for name in os.listdir(fleet_dir):
        if name.endswith(".alive"):
            try:
                os.unlink(os.path.join(fleet_dir, name))
            except OSError:
                pass
    port = _free_port() if nproc > 1 else 0
    members = []
    for pid in range(nproc):
        out_path = os.path.join(
            spec.workdir, f"gen{generation}-proc{pid}.out"
        )
        err_path = os.path.join(
            spec.workdir, f"gen{generation}-proc{pid}.err"
        )
        armed = arm_victim and pid == spec.victim_process
        argv = [
            sys.executable, "-m", "tools.fleet", "--worker",
            "--proc", str(pid), "--nproc", str(nproc),
            "--port", str(port), "--dir", spec.workdir,
            "--quorum-timeout", str(spec.quorum_timeout_s),
            "--heartbeat-every", str(spec.heartbeat_every_s),
            "--progress-heartbeat-every",
            str(spec.progress_heartbeat_every_s),
            "--chunk-sleep", str(spec.chunk_sleep_s),
            "--chunk-sleep-proc", str(spec.chunk_sleep_proc),
        ]
        with open(out_path, "wb") as out, open(err_path, "wb") as err:
            proc = subprocess.Popen(
                argv,
                env=_worker_env(spec, pid, nproc, armed, generation),
                cwd=_repo_root(),
                stdout=out,
                stderr=err,
            )
        members.append(_Member(proc, pid, out_path, err_path))
    return members


def _signal_all(members: list[_Member], sig) -> None:
    for m in members:
        if m.proc.poll() is None:
            try:
                m.proc.send_signal(sig)
            except OSError:
                pass


def _supervise_generation(
    spec: FleetSpec, generation: int, nproc: int, deadline: float,
    status=None,
) -> dict:
    """Run one fleet generation to completion; the per-generation record
    (exit codes, detected deaths, whether escalation was needed)."""
    from photon_ml_tpu.parallel import multihost

    fleet_dir = os.path.join(spec.workdir, "fleet")
    members = _launch_generation(
        spec, generation, nproc, arm_victim=generation == 0
    )
    if status is not None:
        # per-generation state resets; the cumulative death_history is
        # run_fleet's to maintain (it survives relaunches)
        status.update(generation=generation, num_processes=nproc,
                      rcs={}, deaths=[], outcome=None,
                      telemetry_out=spec.telemetry_out_base(generation))
    started = time.monotonic()
    sigterm_sent = False
    sigterm_anchor: Optional[float] = None
    stopping = False
    stop_started = 0.0
    escalated: list[int] = []
    try:
        while True:
            now = time.monotonic()
            if now > deadline:
                _signal_all(members, signal.SIGKILL)
                for m in members:
                    m.proc.wait()
                    m.rc = m.proc.returncode
                return {
                    "generation": generation,
                    "num_processes": nproc,
                    "rcs": {m.process_id: m.rc for m in members},
                    "outcome": "timeout",
                    "escalated": escalated,
                }
            # external-preemption injection: SIGTERM one member mid-fit,
            # anchored on its first heartbeat so the signal lands inside
            # the fit regardless of jax import/compile latency
            if spec.sigterm_after_s is not None and not sigterm_sent:
                if sigterm_anchor is None and os.path.exists(
                    multihost.heartbeat_path(
                        fleet_dir, spec.sigterm_process
                    )
                ):
                    sigterm_anchor = now
                if (
                    sigterm_anchor is not None
                    and now - sigterm_anchor >= spec.sigterm_after_s
                ):
                    for m in members:
                        if (
                            m.process_id == spec.sigterm_process
                            and m.proc.poll() is None
                        ):
                            m.proc.send_signal(signal.SIGTERM)
                    sigterm_sent = True
            # collect exits. Exit-code classification: 113 (the injected
            # preemption/OOM-kill code) = host LOST; 76 = this member
            # noticed the fleet break and bailed (host retained); other
            # unexpected codes are crashes on a retained host.
            for m in members:
                if m.rc is None and m.proc.poll() is not None:
                    m.rc = m.proc.returncode
                    if m.rc == 113 and spec.detect_by == "exit_code":
                        m.lost_host = True
            # heartbeat staleness: the liveness-protocol detection. A
            # stale member that never delivered an exit code is a dead
            # or wedged HOST — reclaim (SIGKILL) and mark it lost.
            if now - started > spec.heartbeat_deadline_s:
                for pid in multihost.dead_peers(
                    fleet_dir, nproc, spec.heartbeat_deadline_s
                ):
                    m = members[pid]
                    if m.lost_host:
                        continue
                    if m.rc is None and m.proc.poll() is None:
                        m.proc.send_signal(signal.SIGKILL)
                        m.proc.wait()
                        m.rc = m.proc.returncode
                        m.lost_host = True
                        escalated.append(pid)
                    elif spec.detect_by == "heartbeat" and m.rc == 113:
                        # heartbeat-mode: the lost-host verdict waited
                        # for the file to go stale, not the exit code
                        m.lost_host = True
            lost = [m for m in members if m.lost_host]
            broken = [
                m for m in members
                if m.rc is not None
                and m.rc not in (0, GRACEFUL_EXIT_CODE)
                and m.process_id not in escalated
            ]
            alive = [m for m in members if m.rc is None]
            if (lost or broken) and not stopping:
                # member death (or a broken-fleet bail): stop the
                # survivors at their next boundary. Death COUNTING
                # happens in run_fleet over the generation's final
                # verdict — a broken-only stop is not a member death.
                stopping = True
                stop_started = now
                _signal_all(members, signal.SIGTERM)
            if (
                stopping
                and alive
                and now - stop_started > spec.grace_s
                and not any(m.process_id in escalated for m in alive)
            ):
                # survivors wedged in a collective against the dead
                # member can never reach the boundary — reclaim them;
                # the certified-checkpoint replay makes this lossless
                for m in alive:
                    escalated.append(m.process_id)
                _signal_all(members, signal.SIGKILL)
            if status is not None:
                # keep the live snapshot truthful mid-generation: exit
                # codes and detected deaths as they land (liveness itself
                # is pulled from heartbeat mtimes by the status thread)
                status.update(
                    rcs={m.process_id: m.rc for m in members
                         if m.rc is not None},
                    deaths=[m.process_id for m in members if m.lost_host],
                )
            if not alive:
                break
            time.sleep(0.05)
    finally:
        for m in members:
            if m.proc.poll() is None:
                m.proc.kill()
            m.proc.wait()
            if m.rc is None:
                m.rc = m.proc.returncode
    if spec.detect_by == "heartbeat":
        # pure liveness-protocol mode: the lost-host verdict comes ONLY
        # from proc-<i>.alive staleness. A fast fleet can finish (every
        # member exited) before the victim's file ever goes stale, so
        # resolve pending verdicts here — the victim is dead, its file
        # WILL stale out within one deadline
        pending = [m for m in members if m.rc == 113 and not m.lost_host]
        resolve_by = time.monotonic() + spec.heartbeat_deadline_s * 2
        while pending and time.monotonic() < resolve_by:
            stale = multihost.dead_peers(
                fleet_dir, nproc, spec.heartbeat_deadline_s
            )
            for m in pending:
                if m.process_id in stale:
                    m.lost_host = True
            pending = [m for m in pending if not m.lost_host]
            if pending:
                time.sleep(0.1)
    rcs = {m.process_id: m.rc for m in members}
    deaths = [m.process_id for m in members if m.lost_host]
    if deaths:
        outcome = "member_death"
    elif all(r == 0 for r in rcs.values()):
        outcome = "complete"
    elif all(r in (0, GRACEFUL_EXIT_CODE) for r in rcs.values()):
        outcome = "interrupted"
    else:
        outcome = "failed"
    return {
        "generation": generation,
        "num_processes": nproc,
        "rcs": rcs,
        "deaths": deaths,
        "outcome": outcome,
        "escalated": escalated,
    }


def run_fleet(spec: FleetSpec) -> dict:
    """Supervise a fit to completion across member loss: launch, watch,
    boundary-stop, relaunch on survivors. JSON-safe report; ``ok`` means
    the fit COMPLETED (survivor resume counts; a graceful external
    interruption reports ``interrupted`` instead)."""
    from photon_ml_tpu import telemetry

    os.makedirs(spec.workdir, exist_ok=True)
    deadline = time.monotonic() + spec.timeout_s
    nproc = spec.num_processes
    generations = []
    relaunches = 0
    report: dict = {"workdir": spec.workdir, "generations": generations}
    status = None
    if spec.status_file is not None or spec.status_port is not None:
        from photon_ml_tpu.parallel.fleet_status import FleetStatusWriter

        status = FleetStatusWriter(
            fleet_dir=os.path.join(spec.workdir, "fleet"),
            num_processes=nproc,
            heartbeat_deadline_s=spec.heartbeat_deadline_s,
            status_file=spec.status_file,
            port=spec.status_port,
            telemetry_out=spec.telemetry_out_base(0),
            interval_s=spec.status_interval_s,
        ).start()
        report["status_port"] = status.port
        report["status_file"] = spec.status_file
    death_history: list = []
    try:
        while True:
            gen = _supervise_generation(
                spec, len(generations), nproc, deadline, status=status
            )
            generations.append(gen)
            death_history.extend(
                {"generation": gen["generation"], "process_id": pid}
                for pid in gen.get("deaths") or ()
            )
            if status is not None:
                status.update(
                    rcs=gen["rcs"], deaths=gen.get("deaths") or [],
                    death_history=list(death_history),
                    outcome=gen["outcome"],
                )
            if gen.get("deaths"):
                telemetry.counter("recovery.fleet_member_deaths").inc(
                    len(gen["deaths"])
                )
            if gen["outcome"] == "complete":
                report.update(ok=True, interrupted=False)
                break
            if gen["outcome"] == "interrupted":
                report.update(ok=False, interrupted=True)
                break
            if gen["outcome"] in ("timeout", "failed") and not gen.get(
                "deaths"
            ):
                report.update(ok=False, interrupted=False)
                break
            survivors = nproc - len(gen["deaths"])
            if survivors < 1 or relaunches >= spec.max_relaunches:
                report.update(ok=False, interrupted=False)
                break
            relaunches += 1
            telemetry.counter("recovery.fleet_relaunches").inc()
            if status is not None:
                status.update(relaunches=relaunches)
            nproc = survivors
    finally:
        if status is not None:
            status.stop()
    report["relaunches"] = relaunches
    report["deaths_total"] = sum(
        len(g.get("deaths") or ()) for g in generations
    )
    report["final_path"] = os.path.join(spec.workdir, "final.npy")
    if spec.resolved_telemetry_dir() is not None:
        # one artifact dir PER GENERATION (relaunches renumber members);
        # `telemetry_dir` points at the newest generation's — the one a
        # completed run's fleet report reads
        dirs = [
            spec.generation_telemetry_dir(g)
            for g in range(len(generations))
        ]
        report["telemetry_dirs"] = dirs
        report["telemetry_dir"] = dirs[-1]
    return report


# ---------------------------------------------------------------------------
# serving-fleet supervision (shard-owning members + in-process router)
# ---------------------------------------------------------------------------


def make_serving_model(
    registry_dir: str,
    n_entities: int = 48,
    fe_dim: int = 4,
    re_dim: int = 3,
    n_buckets: int = 2,
    task: str = "logistic",
    seed: int = 20260807,
) -> str:
    """Build and publish one small deterministic GAME model (FE
    ``global`` + per-``userId`` RE over ``n_entities`` entities) into
    ``registry_dir``; returns the published version directory. The
    serving chaos matrix and the e2e fleet test share this
    builder so their subprocess members score the same coefficients."""
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.game.models import (
        FixedEffectModel,
        GameModel,
        RandomEffectBucketModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.serving import publish_version

    rng = np.random.default_rng(seed)
    fe = FixedEffectModel(
        coefficients=jnp.asarray(rng.normal(size=fe_dim), jnp.float32),
        shard_name="global",
    )
    w_users = rng.normal(size=(n_entities, re_dim))
    entity_bucket = (np.arange(n_entities) % n_buckets).astype(np.int64)
    entity_pos = np.zeros(n_entities, np.int64)
    buckets = []
    for b in range(n_buckets):
        codes_b = np.nonzero(entity_bucket == b)[0]
        entity_pos[codes_b] = np.arange(len(codes_b))
        proj = np.tile(np.arange(re_dim, dtype=np.int32), (len(codes_b), 1))
        buckets.append(
            RandomEffectBucketModel(
                coefficients=jnp.asarray(w_users[codes_b], jnp.float32),
                projection=jnp.asarray(proj),
                entity_codes=jnp.asarray(codes_b, jnp.int32),
            )
        )
    re_model = RandomEffectModel(
        id_name="userId",
        shard_name="user",
        buckets=tuple(buckets),
        entity_bucket=entity_bucket,
        entity_pos=entity_pos,
        vocab=np.arange(n_entities),
    )
    model = GameModel(task=task, models={"fixed": fe, "perUser": re_model})
    index_maps = {
        "global": [f"g{j}" for j in range(fe_dim)],
        "user": [f"u{j}" for j in range(re_dim)],
    }
    return publish_version(registry_dir, model, index_maps)


@dataclasses.dataclass
class ServingFleetSpec:
    """One supervised SERVING fleet run: N shard-owning ``cli serve
    --member`` processes, an in-process :class:`FleetRouter` driving
    sustained traffic, and the same heartbeat/relaunch supervision the
    training fleet uses — plus live elastic resizes through the
    stage/commit barrier."""

    workdir: str
    #: published model directory (feature-indexes/ + model-metadata.json)
    model_dir: str
    fleet_size: int = 3
    max_batch: int = 64
    #: per-member slice HBM budget (the fleet's reason to exist); None
    #: skips enforcement
    hbm_budget_mb: Optional[float] = None
    heartbeat_every_s: float = 0.25
    #: staleness beyond which a member with no exit code counts dead
    heartbeat_deadline_s: float = 3.0
    #: how long one member gets to load + warm + announce
    warm_timeout_s: float = 180.0
    timeout_s: float = 600.0
    #: router fan-out timeout per member call
    member_timeout_s: float = 3.0
    router_refresh_s: float = 0.15
    # -- sustained traffic the supervisor drives through the router
    traffic_seconds: float = 6.0
    traffic_rows: int = 8
    traffic_hz: float = 20.0
    #: dense feature noise synthesized onto traffic rows as
    #: ``((shard_name, n_cols), ...)`` — each row gets ``[col, value]``
    #: pairs for cols [0, n_cols) on that shard (the caller owns the
    #: model, so it knows the feature space; empty = ids-only rows)
    traffic_features: tuple = ()
    rng_seed: int = 20260807
    # -- hard-kill one member mid-traffic (None = no kill)
    kill_member: Optional[int] = None
    kill_after_s: float = 1.5
    relaunch: bool = True
    # -- live resize schedule: [(after_s, new_fleet_size), ...]
    resizes: tuple = ()
    # -- fault plan armed onto exactly one member's environment
    victim_plan: Optional[dict] = None
    victim_member: int = 1
    # -- live status surface (parallel.fleet_status)
    status_file: Optional[str] = None
    status_port: Optional[int] = None
    status_interval_s: float = 0.5
    #: router-side head sampling: mint a sampled trace context every Nth
    #: routed batch (0 = never; slow/error/degraded requests still
    #: persist via tail sampling)
    trace_sample_every: int = 0

    def announce_dir(self) -> str:
        return os.path.join(self.workdir, "announce")

    def fleet_dir(self) -> str:
        return os.path.join(self.workdir, "fleet")

    def telemetry_base(self) -> str:
        return os.path.join(self.workdir, "telemetry", "serving.jsonl")

    def trace_base(self) -> str:
        return os.path.join(self.workdir, "telemetry", "trace.jsonl")


@dataclasses.dataclass
class _ServingMember:
    proc: subprocess.Popen
    member: int
    fleet_size: int
    epoch: int
    out_path: str
    err_path: str
    rc: Optional[int] = None


def _serving_member_env(spec: ServingFleetSpec, member: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PHOTON_PROC_ID"] = str(member)
    env.pop("PHOTON_FAULT_PLAN", None)
    if spec.victim_plan is not None and member == spec.victim_member:
        env["PHOTON_FAULT_PLAN"] = json.dumps(spec.victim_plan)
    return env


def _launch_serving_member(
    spec: ServingFleetSpec, member: int, fleet_size: int, epoch: int
) -> _ServingMember:
    from photon_ml_tpu.telemetry import identity

    os.makedirs(spec.workdir, exist_ok=True)
    os.makedirs(os.path.dirname(spec.telemetry_base()), exist_ok=True)
    out_path = os.path.join(spec.workdir, f"member{member}-e{epoch}.out")
    err_path = os.path.join(spec.workdir, f"member{member}-e{epoch}.err")
    argv = [
        sys.executable, "-m", "photon_ml_tpu.cli", "serve",
        "--model-dir", spec.model_dir,
        "--member", str(member),
        "--fleet-size", str(fleet_size),
        "--announce-dir", spec.announce_dir(),
        "--epoch", str(epoch),
        "--host", "127.0.0.1", "--port", "0",
        "--max-batch", str(spec.max_batch),
        "--heartbeat-dir", spec.fleet_dir(),
        "--telemetry-out",
        identity.member_artifact_path(spec.telemetry_base(), member),
        # kill-safe span stream: PHOTON_PROC_ID in the member env makes
        # cli serve suffix this to trace.proc-<member>.jsonl, and the
        # supervisor harvests it into flight-proc-<member>.json when the
        # member dies without draining
        "--trace-out", spec.trace_base(),
    ]
    if spec.hbm_budget_mb is not None:
        argv += ["--hbm-budget-mb", str(spec.hbm_budget_mb)]
    with open(out_path, "wb") as out, open(err_path, "wb") as err:
        proc = subprocess.Popen(
            argv,
            env=_serving_member_env(spec, member),
            cwd=_repo_root(),
            stdout=out,
            stderr=err,
        )
    return _ServingMember(
        proc, member, fleet_size, epoch, out_path, err_path
    )


def _admin_post(url: str, op: str, payload: dict, timeout_s: float) -> dict:
    import urllib.request

    req = urllib.request.Request(
        f"{url}/v1/admin/{op}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return json.loads(resp.read())


def _wait_for_epoch(
    spec: ServingFleetSpec, epoch: int, fleet_size: int, deadline: float
) -> dict:
    """Block until every member of ``(epoch, fleet_size)`` has announced
    ready; returns {member: record}."""
    from photon_ml_tpu.serving import scan_announce

    want = set(range(fleet_size))
    records: dict[int, dict] = {}
    while time.monotonic() < deadline:
        records = {
            int(r["member"]): r
            for r in scan_announce(spec.announce_dir())
            if int(r.get("epoch", -1)) == epoch
            and int(r.get("fleet_size", -1)) == fleet_size
            and r.get("ready")
        }
        if set(records) == want:
            return records
        time.sleep(0.1)
    raise TimeoutError(
        f"serving fleet epoch {epoch} (size {fleet_size}) incomplete "
        f"after warm timeout; have {sorted(records)}"
    )


class _TrafficDriver:
    """Sustained closed-loop traffic through the router on a thread:
    per-request wall latency samples with timestamps, so disturbance
    windows (kill, resize) can be cut out and compared afterward."""

    def __init__(self, router, rows_fn, hz: float):
        import threading

        self.router = router
        self.rows_fn = rows_fn
        self.period_s = 1.0 / max(hz, 0.1)
        self.samples: list = []  # (t_rel, latency_ms, rows)
        self.failures: list = []  # (t_rel, error string)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="serving-traffic", daemon=True
        )
        self.t0 = 0.0

    def start(self) -> "_TrafficDriver":
        self.t0 = time.monotonic()
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.is_set():
            rows = self.rows_fn()
            t_start = time.monotonic()
            try:
                self.router.score_rows(rows)
                self.samples.append(
                    (
                        round(t_start - self.t0, 4),
                        round((time.monotonic() - t_start) * 1000.0, 3),
                        len(rows),
                    )
                )
            except Exception as e:  # noqa: BLE001 — a non-shed failure IS the finding
                self.failures.append(
                    (round(t_start - self.t0, 4), f"{type(e).__name__}: {e}")
                )
            rest = self.period_s - (time.monotonic() - t_start)
            if rest > 0:
                self._stop.wait(rest)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=30)

    def p99_between(self, t_lo: float, t_hi: float) -> Optional[float]:
        import numpy as np

        lat = [s[1] for s in self.samples if t_lo <= s[0] < t_hi]
        if not lat:
            return None
        return float(np.percentile(np.asarray(lat), 99))


def _traffic_rows_fn(spec: ServingFleetSpec, lookups: dict):
    """Deterministic traffic generator: every request sprays ids across
    the full vocab of every coordinate (so every member owns some of
    every batch) plus optional dense feature noise."""
    import numpy as np

    rng = np.random.default_rng(spec.rng_seed)
    values = {
        id_name: list(table) for id_name, table in lookups.items()
    }

    def rows_fn():
        rows = []
        for _ in range(spec.traffic_rows):
            row: dict = {
                "features": {
                    shard: [
                        [j, float(rng.normal())] for j in range(n_cols)
                    ]
                    for shard, n_cols in spec.traffic_features
                },
                "ids": {
                    id_name: str(vals[int(rng.integers(len(vals)))])
                    for id_name, vals in values.items()
                    if vals
                },
            }
            rows.append(row)
        return rows

    return rows_fn


def run_serving_fleet(spec: ServingFleetSpec) -> dict:
    """Supervise a shard-owning serving fleet end to end: launch N
    members, route sustained traffic, survive a hard kill (heartbeat
    detection -> same-slot relaunch -> degraded window closes), execute
    live resizes through the stage/commit barrier, and drain everyone at
    the end. JSON-safe report with latency samples, shed accounting, and
    per-event timings."""
    import numpy as np  # noqa: F401 — percentile in the driver

    from photon_ml_tpu import telemetry
    from photon_ml_tpu.parallel import multihost
    from photon_ml_tpu.serving import (
        FleetRouter,
        fleet_lookups_from_version_dir,
    )
    from photon_ml_tpu.telemetry import identity
    from photon_ml_tpu.telemetry.progress import tail_heartbeat_fields

    os.makedirs(spec.workdir, exist_ok=True)
    os.makedirs(spec.announce_dir(), exist_ok=True)
    os.makedirs(spec.fleet_dir(), exist_ok=True)
    deadline = time.monotonic() + spec.timeout_s
    report: dict = {"workdir": spec.workdir, "events": []}
    task, link, lookups = fleet_lookups_from_version_dir(spec.model_dir)
    fleet_size = spec.fleet_size
    epoch = 0
    members: dict[int, _ServingMember] = {}
    retired: list[_ServingMember] = []
    router = None
    traffic = None
    status = None
    degraded0 = telemetry.counter("serving.degraded_scores").value
    routed0 = telemetry.counter("serving.routed_rows").value
    member_failures0 = telemetry.counter("serving.member_failures").value

    def _push_status(records: dict) -> None:
        if status is None:
            return
        extras = {}
        down = router.members_status() if router is not None else {}
        for m, rec in records.items():
            d = down.get(m, {})
            entry = {
                "url": rec.get("url"),
                "model_version": rec.get("version"),
                "owned": rec.get("owned") or {},
                "degraded": bool(
                    d.get("degraded", d.get("cooling_down", False))
                ),
                "cooldown_remaining_s": d.get("cooldown_remaining_s", 0.0),
            }
            if d.get("fanout_rtt_ms"):
                entry["fanout_rtt_ms"] = d["fanout_rtt_ms"]
            tail = tail_heartbeat_fields(
                identity.member_artifact_path(spec.telemetry_base(), m),
                expect_proc=m,
            )
            if tail is not None:
                last_t, last_total = _req_cursor.get(m, (None, None))
                total = tail.get("serving_requests_total")
                now = time.monotonic()
                if (
                    total is not None
                    and last_total is not None
                    and now > last_t
                ):
                    entry["requests_per_s"] = round(
                        max(total - last_total, 0) / (now - last_t), 2
                    )
                if total is not None:
                    _req_cursor[m] = (now, total)
            extras[m] = entry
        status.update(
            num_processes=fleet_size, generation=epoch,
            member_extras=extras,
        )

    _req_cursor: dict[int, tuple] = {}
    try:
        if spec.status_file is not None or spec.status_port is not None:
            from photon_ml_tpu.parallel.fleet_status import FleetStatusWriter

            status = FleetStatusWriter(
                fleet_dir=spec.fleet_dir(),
                num_processes=fleet_size,
                heartbeat_deadline_s=spec.heartbeat_deadline_s,
                status_file=spec.status_file,
                port=spec.status_port,
                telemetry_out=spec.telemetry_base(),
                interval_s=spec.status_interval_s,
            ).start()
            report["status_port"] = status.port
            report["status_file"] = spec.status_file
        for m in range(fleet_size):
            members[m] = _launch_serving_member(spec, m, fleet_size, epoch)
        records = _wait_for_epoch(
            spec, epoch, fleet_size,
            min(deadline, time.monotonic() + spec.warm_timeout_s),
        )
        version = str(records[0]["version"])
        # router-side span stream: the supervisor process persists its
        # request:route spans next to the members' per-proc streams so
        # `cli report --fleet` can join one trace_id across the fan-out
        telemetry.configure(
            trace_out=os.path.join(
                os.path.dirname(spec.telemetry_base()), "trace.router.jsonl"
            )
        )
        router = FleetRouter(
            spec.announce_dir(),
            lookups,
            task=task,
            link=link,
            member_timeout_s=spec.member_timeout_s,
            refresh_interval_s=spec.router_refresh_s,
            retries=1,
            backoff_s=0.05,
            cooldown_s=0.4,
            sample_every=spec.trace_sample_every,
        )
        router.refresh()
        _push_status(records)
        traffic = _TrafficDriver(
            router, _traffic_rows_fn(spec, lookups), spec.traffic_hz
        ).start()
        t0 = traffic.t0

        def _rel() -> float:
            return round(time.monotonic() - t0, 4)

        # -- event schedule: kill + resizes interleave on the timeline --
        kill_at = (
            None if spec.kill_member is None
            else t0 + spec.kill_after_s
        )
        resize_plan = [
            (t0 + after_s, int(new_size)) for after_s, new_size in spec.resizes
        ]
        traffic_end = t0 + spec.traffic_seconds
        killed: Optional[dict] = None
        # a resize that slipped past traffic_end (slow warms on small
        # hosts) still completes before teardown: the headline is that
        # EVERY scheduled swap lands under live traffic, not that it
        # lands on a wall-clock mark — so traffic keeps flowing while
        # the plan has entries left
        while time.monotonic() < deadline and (
            time.monotonic() < traffic_end or resize_plan
        ):
            now = time.monotonic()
            if kill_at is not None and now >= kill_at:
                kill_at = None
                victim = members[spec.kill_member]
                t_kill = _rel()
                victim.proc.kill()
                victim.proc.wait()
                victim.rc = victim.proc.returncode
                killed = {"member": spec.kill_member, "t_kill": t_kill}
                report["events"].append({"kill": dict(killed)})
                # heartbeat-staleness detection, then same-slot relaunch
                # (same epoch: the announce refresh is an endpoint update,
                # not an ownership change — serving.resize_swap must NOT
                # fire for it)
                while time.monotonic() < deadline:
                    if spec.kill_member in multihost.dead_peers(
                        spec.fleet_dir(), fleet_size,
                        spec.heartbeat_deadline_s,
                    ):
                        break
                    time.sleep(0.05)
                killed["detect_s"] = round(_rel() - t_kill, 3)
                # flight-recorder harvest: the victim died without its
                # drain-path dump, so recover its last words from the
                # kill-safe trace stream (bounded tail read; a torn last
                # line is dropped, never adopted)
                from photon_ml_tpu.telemetry import requests as rq

                flight = rq.harvest_flight(
                    identity.member_artifact_path(
                        spec.trace_base(), spec.kill_member
                    ),
                    rq.flight_path(
                        os.path.dirname(spec.telemetry_base()),
                        spec.kill_member,
                    ),
                )
                if flight is not None:
                    killed["flight_spans"] = flight
                if spec.relaunch:
                    members[spec.kill_member] = _launch_serving_member(
                        spec, spec.kill_member, fleet_size, epoch
                    )
                    old_pid = records[spec.kill_member].get("pid")
                    while time.monotonic() < deadline:
                        recs = {
                            int(r["member"]): r
                            for r in _scan_ready(spec, epoch, fleet_size)
                        }
                        fresh = recs.get(spec.kill_member)
                        if fresh is not None and fresh.get("pid") != old_pid:
                            records = recs
                            break
                        time.sleep(0.05)
                    router.refresh()
                    killed["recovery_s"] = round(_rel() - t_kill, 3)
                continue
            if resize_plan and now >= resize_plan[0][0]:
                _t, new_size = resize_plan.pop(0)
                event = {
                    "resize": {
                        "from": fleet_size,
                        "to": new_size,
                        "t_start": _rel(),
                        "epoch": epoch + 1,
                    }
                }
                survivors = list(range(min(fleet_size, new_size)))
                # 1) growth: launch the new slots straight into epoch+1
                #    FIRST — their load+warm overlaps the survivors'
                #    staging below instead of serializing after it
                for m in range(fleet_size, new_size):
                    members[m] = _launch_serving_member(
                        spec, m, new_size, epoch + 1
                    )
                # 2) stage the new slice on every surviving member while
                #    the old one keeps serving (concurrently: staging is
                #    member-local work in N separate processes)
                from concurrent.futures import ThreadPoolExecutor

                with ThreadPoolExecutor(
                    max_workers=max(len(survivors), 1)
                ) as stage_pool:
                    stage_futs = [
                        stage_pool.submit(
                            _admin_post,
                            records[m]["url"], "stage",
                            {"fleet_size": new_size, "version": version},
                            spec.warm_timeout_s,
                        )
                        for m in survivors
                    ]
                    for fut in stage_futs:
                        fut.result()
                # 3) barrier: commit the survivors (their on_commit hook
                #    re-announces at the new size/epoch)
                for m in survivors:
                    _admin_post(
                        records[m]["url"], "commit",
                        {
                            "fleet_size": new_size,
                            "version": version,
                            "epoch": epoch + 1,
                        },
                        spec.member_timeout_s * 4,
                    )
                old_size, old_records = fleet_size, records
                epoch += 1
                records = _wait_for_epoch(
                    spec, epoch, new_size,
                    min(deadline, time.monotonic() + spec.warm_timeout_s),
                )
                fleet_size = new_size
                router.refresh()
                event["resize"]["t_swap"] = _rel()
                # 4) shrink: retire the now-unowned slots via graceful
                #    drain (SIGTERM -> 503 + Retry-After -> exit 75)
                for m in range(new_size, old_size):
                    gone = members.pop(m)
                    gone.proc.send_signal(signal.SIGTERM)
                    retired.append(gone)
                    try:
                        os.unlink(
                            os.path.join(
                                spec.announce_dir(), f"member-{m}.json"
                            )
                        )
                    except OSError:
                        pass
                report["events"].append(event)
                _push_status(records)
                continue
            _push_status(records)
            time.sleep(0.05)
        traffic.stop()
        if killed is not None:
            report["kill"] = killed
        # -- graceful teardown: every member drains and exits 75 --------
        for m in list(members.values()) + retired:
            if m.proc.poll() is None:
                m.proc.send_signal(signal.SIGTERM)
        for m in list(members.values()) + retired:
            try:
                m.rc = m.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                m.proc.kill()
                m.rc = m.proc.wait()
        report["rcs"] = {
            m.member: m.rc for m in list(members.values()) + retired
        }
        report["samples"] = traffic.samples
        report["failures"] = traffic.failures
        report["routed_rows"] = int(
            telemetry.counter("serving.routed_rows").value - routed0
        )
        report["degraded_scores"] = int(
            telemetry.counter("serving.degraded_scores").value - degraded0
        )
        report["member_failures"] = int(
            telemetry.counter("serving.member_failures").value
            - member_failures0
        )
        report["degraded_fraction"] = (
            report["degraded_scores"] / report["routed_rows"]
            if report["routed_rows"]
            else 0.0
        )
        report["fleet_size"] = fleet_size
        report["epoch"] = epoch
        report["telemetry_dir"] = os.path.dirname(spec.telemetry_base())
        report["ok"] = not traffic.failures
        return report
    finally:
        if traffic is not None and traffic._thread.is_alive():
            traffic.stop()
        if router is not None:
            router.close()
        if status is not None:
            status.stop()
        for m in list(members.values()) + retired:
            if m.proc.poll() is None:
                m.proc.kill()
                m.proc.wait()


def _scan_ready(
    spec: ServingFleetSpec, epoch: int, fleet_size: int
) -> list[dict]:
    from photon_ml_tpu.serving import scan_announce

    return [
        r
        for r in scan_announce(spec.announce_dir())
        if int(r.get("epoch", -1)) == epoch
        and int(r.get("fleet_size", -1)) == fleet_size
        and r.get("ready")
    ]


def verify_certified_checkpoints(
    checkpoint_dir: str, num_entities: int, dim: int
) -> list[str]:
    """Audit every CERTIFIED checkpoint under ``checkpoint_dir``: each
    ``chunk-*`` directory must carry a quorum/complete manifest whose
    shards contiguously cover [0, num_entities) with readable payloads.
    Returns a list of violation strings (empty = no partial checkpoint
    was ever certified — the distributed matrix's third assertion)."""
    from photon_ml_tpu.game.checkpoint import (
        CheckpointError,
        CheckpointSpec,
        StreamingCheckpointManager,
    )

    if not os.path.isdir(checkpoint_dir):
        return []
    mgr = StreamingCheckpointManager(
        CheckpointSpec(directory=checkpoint_dir, every=1)
    )
    problems = []
    for _c, path in mgr._chunk_dirs():
        try:
            manifest = mgr._read_manifest(path)
            if int(manifest["num_entities"]) != num_entities:
                raise CheckpointError(
                    f"{path}: wrong entity count "
                    f"{manifest['num_entities']}"
                )
            if int(manifest["dim"]) != dim:
                raise CheckpointError(f"{path}: wrong dim {manifest['dim']}")
            reader = mgr._row_reader(path, manifest, "coefficients")
            reader(0, num_entities)  # every payload byte readable
        except (CheckpointError, ValueError, OSError, KeyError) as e:
            problems.append(f"{path}: certified but partial/corrupt: {e}")
    return problems


# ---------------------------------------------------------------------------
# the worker fit (one fleet member)
# ---------------------------------------------------------------------------


def _worker_main(args) -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from photon_ml_tpu import faults, telemetry
    from photon_ml_tpu.parallel import multihost

    faults.warn_if_armed()
    # per-member artifact streams: PHOTON_PROC_ID is already in this
    # worker's env (set by the supervisor BEFORE jax existed), so the
    # trace/telemetry sinks open per-member suffixed files and the trace
    # header records this member's identity + epoch anchor
    telemetry.configure_from_env()
    if args.nproc > 1:
        multihost.initialize(
            multihost.DistributedConfig(
                coordinator_address=f"127.0.0.1:{args.port}",
                num_processes=args.nproc,
                process_id=args.proc,
                init_retries=2,
                init_backoff_s=0.2,
            )
        )
        assert jax.process_count() == args.nproc
    # the progress heartbeat starts only AFTER the distributed client is
    # up: a beat probes memory.hbm_stats() -> jax.devices(), and
    # initializing the backend while jax.distributed.initialize is still
    # rendezvousing would wedge the fleet on local-only devices
    progress_heartbeat = None
    telemetry_out = os.environ.get("PHOTON_TELEMETRY_OUT")
    if telemetry_out and args.progress_heartbeat_every > 0:
        progress_heartbeat = telemetry.Heartbeat(
            interval=args.progress_heartbeat_every,
            jsonl_path=telemetry.member_artifact_path(telemetry_out),
        ).start()
    heartbeat = multihost.HeartbeatWriter(
        os.path.join(args.dir, "fleet"),
        args.proc,
        interval_s=args.heartbeat_every,
    ).start()
    try:
        return _worker_fit(args, np)
    finally:
        heartbeat.stop()
        if progress_heartbeat is not None:
            progress_heartbeat.stop()


def _worker_fit(args, np) -> int:
    import jax
    import jax.numpy as jnp  # noqa: F401 — jax must be live before mesh use

    from photon_ml_tpu.game.checkpoint import (
        CheckpointSpec,
        GracefulStop,
        StreamingCheckpointManager,
        TrainingInterrupted,
    )
    from photon_ml_tpu.game.streaming import (
        LocalChunk,
        ShardedCoefficientTable,
        StreamingRandomEffectTrainer,
    )
    from photon_ml_tpu.ops.dense import DenseBatch
    from photon_ml_tpu.optim import (
        OptimizerConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_ml_tpu.parallel import multihost

    stop = GracefulStop().install()
    n_dev = jax.device_count()
    mesh = multihost.global_mesh({"entity": n_dev})
    # shared deterministic problem: every member generates the same data
    X, y = make_problem()
    per = N_ENTITIES // N_CHUNKS

    def local_chunk(start: int) -> LocalChunk:
        # this process's slice of the chunk's global [start, start+per)
        # rows — recomputed from the CURRENT mesh, so a survivor fleet's
        # members absorb the dead host's rows deterministically
        lo, hi = multihost.process_slice(per, mesh, "entity")
        glo, ghi = start + lo, start + hi
        return LocalChunk(
            DenseBatch(
                x=X[glo:ghi],
                labels=y[glo:ghi],
                offsets=np.zeros((ghi - glo, N_ROWS), np.float32),
                weights=np.ones((ghi - glo, N_ROWS), np.float32),
            ),
            global_size=per,
        )

    chunks = [(i * per, local_chunk(i * per)) for i in range(N_CHUNKS)]
    cfg = OptimizerConfig(
        max_iterations=60,
        tolerance=1e-9,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=0.3,
    )
    mgr = StreamingCheckpointManager(
        CheckpointSpec(
            directory=os.path.join(args.dir, "ckpt"),
            every=1,
            quorum_timeout_s=args.quorum_timeout,
        )
    )
    restored = mgr.restore_placed(mesh=mesh)
    if restored is not None:
        table = ShardedCoefficientTable.from_coefficients(
            restored.coefficients, mesh=mesh
        )
        start_chunk = restored.next_chunk
    else:
        table = ShardedCoefficientTable(N_ENTITIES, DIM, mesh=mesh)
        start_chunk = 0

    def should_stop() -> bool:
        if args.chunk_sleep > 0 and args.chunk_sleep_proc in (-1, args.proc):
            time.sleep(args.chunk_sleep)
        # fleet-consistent agreement: every member sees the same verdict
        # at the same boundary, so nobody sails alone into a collective
        return multihost.fleet_any(stop.requested, mesh)

    trainer = StreamingRandomEffectTrainer(
        "logistic", cfg, mesh=mesh, prefetch=False
    )
    try:
        trainer.train(
            table,
            chunks,
            checkpointer=mgr,
            start_chunk=start_chunk,
            should_stop=should_stop,
        )
        final = table.to_numpy()  # every member runs the gather collective
    except TrainingInterrupted as e:
        print(json.dumps({
            "interrupted": True,
            "at_chunk": e.step,
            "checkpoint": e.checkpoint_path,
            "start_chunk": start_chunk,
            "process_id": args.proc,
        }))
        return GRACEFUL_EXIT_CODE
    except Exception as e:  # noqa: BLE001 — any failure in a degraded fleet
        if jax.process_count() > 1:
            # a collective failed (gloo "connection closed by peer" et
            # al): the fleet is broken and this process cannot help it.
            # Exit through os._exit — normal unwinding would WEDGE in
            # jax's atexit distributed-shutdown barrier against the dead
            # peer, turning one lost host into a hung survivor.
            print(json.dumps({
                "fleet_abort": True,
                "process_id": args.proc,
                "error": f"{type(e).__name__}: {e}"[:500],
            }))
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(FLEET_ABORT_EXIT_CODE)
        raise
    if jax.process_index() == 0:
        np.save(os.path.join(args.dir, "final.npy"), final)
    print(json.dumps({
        "interrupted": False,
        "resumed": restored is not None,
        "start_chunk": start_chunk,
        "process_id": args.proc,
        "num_processes": args.nproc,
    }))
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="tools.fleet", description=__doc__.splitlines()[0]
    )
    parser.add_argument("--worker", action="store_true",
                        help="run as ONE fleet member (internal)")
    parser.add_argument("--proc", type=int, default=0)
    parser.add_argument("--nproc", type=int, default=1)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--dir", help="fleet working directory")
    parser.add_argument("--quorum-timeout", type=float, default=4.0)
    parser.add_argument("--heartbeat-every", type=float, default=0.25)
    parser.add_argument("--progress-heartbeat-every", type=float,
                        default=1.0,
                        help="worker progress-heartbeat cadence into the "
                        "per-member telemetry JSONL (0 disables)")
    parser.add_argument("--chunk-sleep", type=float, default=0.0)
    parser.add_argument("--chunk-sleep-proc", type=int, default=-1)
    parser.add_argument("--workdir", help="supervisor working directory")
    parser.add_argument("--num-processes", type=int, default=2)
    parser.add_argument("--devices-per-process", type=int, default=2)
    parser.add_argument("--max-relaunches", type=int, default=2)
    parser.add_argument("--json", dest="json_out",
                        help="write the supervisor report to this path")
    parser.add_argument("--status-file",
                        help="write an atomic live-status JSON snapshot "
                        "here on a cadence (member liveness, last "
                        "heartbeat fields, deaths/relaunches, generation)")
    parser.add_argument("--status-port", type=int,
                        help="serve the live-status snapshot on "
                        "http://127.0.0.1:PORT/statusz (0 = ephemeral "
                        "port, reported in the supervisor JSON)")
    parser.add_argument("--status-interval", type=float, default=1.0,
                        help="seconds between status snapshots")
    parser.add_argument("--no-telemetry", action="store_true",
                        help="disable the per-member trace/telemetry "
                        "artifact streams (on by default under "
                        "<workdir>/telemetry)")
    parser.add_argument("--serve-model-dir",
                        help="supervise a SERVING fleet of shard-owning "
                        "cli-serve members over this published model "
                        "directory instead of a training fit")
    parser.add_argument("--serve-fleet-size", type=int, default=3,
                        help="serving fleet size (entity counts must "
                        "divide by it)")
    parser.add_argument("--serve-seconds", type=float, default=6.0,
                        help="how long to drive router traffic")
    args = parser.parse_args(argv)
    if args.serve_model_dir:
        if not args.workdir:
            parser.error("--serve-model-dir requires --workdir")
        report = run_serving_fleet(ServingFleetSpec(
            workdir=args.workdir,
            model_dir=args.serve_model_dir,
            fleet_size=args.serve_fleet_size,
            traffic_seconds=args.serve_seconds,
            status_file=args.status_file,
            status_port=args.status_port,
            status_interval_s=args.status_interval,
        ))
        if args.json_out:
            with open(args.json_out, "w", encoding="utf-8") as fh:
                json.dump(report, fh, indent=2, sort_keys=True)
        print(json.dumps(report, indent=2, sort_keys=True))
        return 0 if report.get("ok") else 1
    if args.worker:
        if not args.dir:
            parser.error("--worker requires --dir")
        return _worker_main(args)
    if not args.workdir:
        parser.error("--workdir is required (or --worker --dir)")
    # the supervisor owns recovery.fleet_* — export them
    # (PHOTON_TELEMETRY_OUT / PHOTON_TRACE_OUT opt-in) so a real
    # fleet run's member deaths/relaunches reach the RunReport Recovery
    # section, not just this process's memory
    from photon_ml_tpu import telemetry

    telemetry.configure_from_env()
    report = run_fleet(FleetSpec(
        workdir=args.workdir,
        num_processes=args.num_processes,
        devices_per_process=args.devices_per_process,
        max_relaunches=args.max_relaunches,
        telemetry=not args.no_telemetry,
        progress_heartbeat_every_s=args.progress_heartbeat_every,
        status_file=args.status_file,
        status_port=args.status_port,
        status_interval_s=args.status_interval,
    ))
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
    print(json.dumps(report, indent=2, sort_keys=True))
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
